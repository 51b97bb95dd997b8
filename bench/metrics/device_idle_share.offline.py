"""device_idle_share.offline: 1 - (union of device-operation intervals) /
traced window, averaged over the cell's devices, in percent."""


def read(ctx):
    s = ctx.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_mean_s / s.window_s)
