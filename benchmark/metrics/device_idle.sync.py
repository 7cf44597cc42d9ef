"""device_idle.sync (device): the share of the traced part of the window
in which no operation ran on the device."""


def read(ctx):
    return ctx.device_idle()
