"""combine_ms_per_round (ms): the cross-chip combine of a round on a
mesh, the first chip's own device ms per round of the round program's
collective ops (``scopes.COLLECTIVES``: all-reduce, all-gather,
reduce-scatter, collective-permute, each with its async ``-start`` and
``-done``) in the aggregation phases, ``fl_aggregate`` and
``fl_server_update``: the ``psum`` over ``data`` that sums the pods'
partials.  None without the round program's phases or where no such op
ran."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, ("fl_aggregate", "fl_server_update"),
                               collective=True)
