"""local_step_roofline (%): the fused client step tail
(``fused_local_step``, ``kernels/fused_update.py:local_step``) against
its roofline.  Each client step reads the params and the gradient and
writes the params, all at the stored width, with no momentum, scaffold
term or weight decay in these cells: 3 x params x bytes, of the params
that fall to the first chip (``ctx.chip_params``: all of them on one
chip, an even share on a mesh).  The least time of the window's steps
(rounds x clients x local steps of them) at the chip's HBM bandwidth,
or at its peak for the 2 FLOPs per element if that is longer, over the
summed device time of the kernel's events on the first chip."""
from bench import xtrace

KERNEL = "fused_local_step"


def read(ctx):
    events = xtrace.of_kind(ctx.trace.device_ops[min(ctx.trace.device_ops)],
                            KERNEL)
    busy = xtrace.summed_ns(events, ctx.window) * 1e-9
    if busy <= 0:
        return None
    calls = ctx.rounds * ctx.clients_per_round * ctx.local_steps
    n = ctx.chip_params
    least = max(calls * 3 * n * ctx.param_bytes / ctx.peaks.hbm_bw,
                calls * 2 * n / ctx.peaks.flops_bf16)
    return 100.0 * least / busy
