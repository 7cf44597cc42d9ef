"""pack_overlap.sync (pool): the share of ``bls.pack`` time in the traced
part of the window during which the device is busy, the overlap that the
pool's pipelining is there to buy.  Program spans and the device trace,
on one clock."""

from benchlib import trace


def read(ctx):
    if not ctx.spans or not ctx.device_ops:
        return None
    lo, hi = ctx.device_window()
    busy = ctx.device_busy()
    pack = covered = 0.0
    for name, a, b, _ in ctx.spans:
        if name != "bls.pack" or not lo <= a < hi:
            continue
        pack += b - a
        covered += trace.total(trace.clip(busy, a, b))
    if not pack:
        return None
    return 100.0 * covered / pack
