"""gossip_p95_ms: the 95th percentile (``statistics.quantiles``, n=100)
of the latency of every job due in the window, from its due time to its
verdict (open loop).  A job without a verdict is a miss at +inf; where
the percentile reaches one, the run already fails ``no_verdict`` and
there is nothing to report."""

import math
import statistics


def read(ctx):
    lat = [r.done - r.due if r.verdict is not None else math.inf for r in ctx.jobs]
    if len(lat) < 2:
        return None
    p95 = statistics.quantiles(lat, n=100)[94]
    return 1e3 * p95 if math.isfinite(p95) else None
