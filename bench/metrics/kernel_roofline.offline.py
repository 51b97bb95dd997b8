"""kernel_roofline.offline: the least time one chip needs for the plan's
evaluated work (``work.least_seconds``: the larger of operations over peak
FLOP/s and HBM bytes over peak bandwidth) over the device busy time in the
traced window, summed over the cell's devices, in percent."""

import work


def read(ctx):
    s, run = ctx.summary, ctx.run
    busy = sum(s.busy_s) if s is not None else 0.0
    if busy <= 0 or run["ex"].size == 0:
        return None
    flops = work.ops(ctx.ens, ctx.cfg, run["ex"], ctx.order)
    nbytes = work.hbm_bytes(
        ctx.ens, ctx.cfg, ctx.features, run["ex"], run["flush_max_exit"], ctx.order
    )
    return 100.0 * work.least_seconds(flops, nbytes, ctx.peak) / busy
