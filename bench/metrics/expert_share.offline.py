"""expert_share.offline: the device time of the program's held-expert
kernel (the ops named ``EXPERT_KERNEL`` by the cell's ensemble module)
over the device busy time in the traced window, summed over the cell's
devices, in percent.  Nothing where the kind has no such kernel or the
trace shows none."""


def read(ctx):
    s, name = ctx.summary, getattr(ctx.ens, "EXPERT_KERNEL", None)
    busy = sum(s.busy_s) if s is not None else 0.0
    if name is None or busy <= 0:
        return None
    t = sum(sec for op, sec in s.device_ops if name in op)
    return 100.0 * t / busy if t > 0 else None
