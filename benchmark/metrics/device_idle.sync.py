"""device_idle.sync (device): the share of the traced part of the window
in which no operation ran on the device."""


def read(ctx):
    busy = ctx.device_busy_s()
    if not busy:
        return None
    lo, hi = ctx.device_window()
    return 100.0 * (1.0 - busy / (hi - lo))
