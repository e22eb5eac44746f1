"""device_idle_share: the share of the profiled part of the window in which
no operation ran on the device, in percent (trace: union of the device's
op intervals)."""


def read(ctx):
    if ctx.device is None or ctx.device["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])
