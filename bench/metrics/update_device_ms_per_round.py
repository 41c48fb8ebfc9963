"""update_device_ms_per_round (ms): the updates of a round, the first
chip's own device ms per round of the round program's ops in
``fl_step_tail`` (the clients' fused step tails), ``fl_aggregate`` (the
weighted delta sum and, on a mesh, its combine) and
``fl_server_update``.  None without the round program's phases."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, ("fl_step_tail", "fl_aggregate",
                                     "fl_server_update"))
