"""pack_sigs_ms.block (host pack): summed ``bls.pack.signatures`` spans of
the window (cache lookups and G2 signature decompression), per block
verified in it."""


def read(ctx):
    part = ctx.span_seconds("bls.pack.signatures")
    if not part or not ctx.jobs:
        return None
    return 1e3 * part / len(ctx.jobs)
