"""fwd_bwd_device_ms_per_round (ms): the clients' local forward and
backward passes, the first chip's own device ms per round of the round
program's ops in ``fl_fwd_bwd`` and ``fl_conv_wgrad`` (the conv kernel
gradients merged over the clients).  None without the round program's
phases."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, ("fl_fwd_bwd", "fl_conv_wgrad"))
