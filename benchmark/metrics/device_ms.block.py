"""device_ms.block (device program): the union of the device's operation
intervals in the traced part of the window, per block whose verdict
returned in it."""


def read(ctx):
    busy = ctx.device_busy_s()
    if not busy or not ctx.device_jobs:
        return None
    return 1e3 * busy / len(ctx.device_jobs)
