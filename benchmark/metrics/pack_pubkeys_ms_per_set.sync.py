"""pack_pubkeys_ms_per_set.sync (host pack): summed ``bls.pack.pubkeys``
spans of the window (cache lookups and G1 aggregation of the committee
members), per set verified in it."""


def read(ctx):
    part = ctx.span_seconds("bls.pack.pubkeys")
    if not part or not ctx.sets:
        return None
    return 1e3 * part / ctx.sets
