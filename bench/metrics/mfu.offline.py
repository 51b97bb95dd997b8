"""mfu.offline: the plan's evaluated operations (``work.ops``) over the
window's seconds times the cell's chips times one chip's peak FLOP/s, in
percent."""

import work


def read(ctx):
    run = ctx.run
    if ctx.peak is None or run["ex"].size == 0:
        return None
    flops = work.ops(ctx.ens, ctx.cfg, run["ex"], ctx.order)
    return 100.0 * flops / (run["elapsed_s"] * ctx.chips * ctx.peak["flops_per_s"])
