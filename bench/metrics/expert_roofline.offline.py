"""expert_roofline.offline: the least time one chip needs for the work of
the held-expert kernel on the rows served in the traced window (the kind's
``expert_work``: the routed slots of each row's evaluated layers, and per
flush the held experts' weights up to its deepest exit), over the kernel's
device time in the window, in percent (``work.least_seconds``)."""

import work


def read(ctx):
    s, ens, run = ctx.summary, ctx.ens, ctx.run
    name = getattr(ens, "EXPERT_KERNEL", None)
    if s is None or name is None or run["ex"].size == 0:
        return None
    t = sum(sec for op, sec in s.device_ops if name in op)
    need = ens.expert_work(ctx.cfg, run["idx"], run["ex"], run["flush_max_exit"]) if t > 0 else None
    if need is None:
        return None
    return 100.0 * work.least_seconds(*need, ctx.peak) / t
