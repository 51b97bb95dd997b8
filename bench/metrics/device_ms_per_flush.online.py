"""device_ms_per_flush.online: device busy time in the traced window per
flush, averaged over the cell's devices."""


def read(ctx):
    s = ctx.summary
    if s is None or not ctx.run.get("n_flush"):
        return None
    return s.busy_mean_s / ctx.run["n_flush"] * 1e3
