"""tail_ms.block (host tail): summed ``bls.final_exp`` spans of the window
(limb conversion and the host's final exponentiation of the device's
Miller product), per block verified in it."""


def read(ctx):
    tail = ctx.span_seconds("bls.final_exp")
    if not tail or not ctx.jobs:
        return None
    return 1e3 * tail / len(ctx.jobs)
