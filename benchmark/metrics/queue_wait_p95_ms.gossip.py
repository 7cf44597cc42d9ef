"""queue_wait_p95_ms.gossip (pool): the 95th percentile of the pool's
``bls.queue_wait`` spans that start inside the window, each from a job's
push into the pool to the drain of the merged batch that takes it."""

import statistics


def read(ctx):
    waits = [b - a for _, a, b, _ in ctx.window_spans("bls.queue_wait")]
    if len(waits) < 2:
        return None
    return 1e3 * statistics.quantiles(waits, n=100)[94]
