"""batch_sets.gossip (pool): the mean ``sets`` of the pool's ``pool.batch``
spans that start inside the window, the sets merged into one device
batch (a failed batch's per-job re-check is not a ``pool.batch``)."""


def read(ctx):
    sizes = [s.attrs["sets"] for s in ctx.window_spans("pool.batch") if "sets" in s.attrs]
    if not sizes:
        return None
    return sum(sizes) / len(sizes)
