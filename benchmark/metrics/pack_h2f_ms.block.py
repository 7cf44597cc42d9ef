"""pack_h2f_ms.block (host pack): summed ``bls.pack.hash_to_field`` spans
of the window (the SHA-256 expand_message draws of every lane), per block
verified in it."""


def read(ctx):
    part = ctx.span_seconds("bls.pack.hash_to_field")
    if not part or not ctx.jobs:
        return None
    return 1e3 * part / len(ctx.jobs)
