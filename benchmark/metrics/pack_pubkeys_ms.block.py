"""pack_pubkeys_ms.block (host pack): summed ``bls.pack.pubkeys`` spans of
the window (cache lookups and G1 aggregation of the committee members),
per block verified in it."""


def read(ctx):
    part = ctx.span_seconds("bls.pack.pubkeys")
    if not part or not ctx.jobs:
        return None
    return 1e3 * part / len(ctx.jobs)
