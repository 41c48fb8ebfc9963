"""delta_accum_roofline (%): the pod backend's per-client delta
accumulation (``fused_delta_accum``, ``kernels/fused_update.py:
delta_accum``) against its roofline.  Each client reads the float32
delta, its end params and the round's params (stored width) and writes
the float32 delta: (4 + 2 x bytes + 4) x params, of the params that
fall to the first chip (``ctx.chip_params``).  The least time of the
window's calls (rounds x clients) at the chip's HBM bandwidth, or at
its peak for the 3 FLOPs per element if that is longer, over the summed
device time of the kernel's events on the first chip."""
from bench import xtrace

KERNEL = "fused_delta_accum"


def read(ctx):
    events = xtrace.of_kind(ctx.trace.device_ops[min(ctx.trace.device_ops)],
                            KERNEL)
    busy = xtrace.summed_ns(events, ctx.window) * 1e-9
    if busy <= 0:
        return None
    calls = ctx.rounds * ctx.clients_per_round
    n = ctx.chip_params
    least = max(calls * (8 + 2 * ctx.param_bytes) * n / ctx.peaks.hbm_bw,
                calls * 3 * n / ctx.peaks.flops_bf16)
    return 100.0 * least / busy
