"""unflatten_device_ms_per_round (ms): the flat carry turned into the
model's parameter tree inside the loss, and its transpose packing the
gradients flat: the first chip's own device ms per round of the round
program's ops in ``fl_unflatten``.  None without the round program's
phases."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, ("fl_unflatten",))
