"""mfu (%): model FLOPs of the traced window's rounds over the window's
host-clock time and the chips' bf16 peak.  The FLOPs per round are the
configuration's forward plus backward FLOPs per sample (its reference
module's ``train_flops_per_sample``: no recompute, no evaluation) times
the samples a round trains on."""


def read(ctx):
    flops = ctx.flops_per_round * ctx.rounds
    return 100.0 * flops / ctx.window_s / (ctx.chips * ctx.peaks.flops_bf16)
