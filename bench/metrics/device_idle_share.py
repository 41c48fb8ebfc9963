"""device_idle_share (%): the share of the traced window in which no op
ran on the device, 1 - busy / window, averaged over the cell's chips.
Busy is the union of the op intervals of each chip's ``XLA Ops`` line."""
from bench import xtrace


def read(ctx):
    cores = [ctx.trace.device_ops[c] for c in sorted(ctx.trace.device_ops)]
    cores = cores[:ctx.chips]
    if not cores:
        return None
    t0, t1 = ctx.window
    idle = [1.0 - xtrace.busy_ns(ev, ctx.window) / (t1 - t0) for ev in cores]
    return 100.0 * sum(idle) / len(idle)
