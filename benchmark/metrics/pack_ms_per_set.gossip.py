"""pack_ms_per_set.gossip (host pack): summed ``bls.pack`` spans of the
window, re-checks of failed batches included, per set of the jobs due in
it."""


def read(ctx):
    pack = ctx.span_seconds("bls.pack")
    if not pack or not ctx.sets:
        return None
    return 1e3 * pack / ctx.sets
