"""tp_collective_ms_per_round (ms): the tensor-parallel collectives of a
round on a mesh, the first chip's own device ms per round of the round
program's collective ops (``scopes.COLLECTIVES``) in every phase but
the aggregation's: ``fl_fwd_bwd``, ``fl_conv_wgrad``, ``fl_unflatten``,
``fl_step_tail``, ``fl_eval`` and unscoped.  None without the round
program's phases or where no such op ran."""
from bench import scopes

PHASES = ("fl_fwd_bwd", "fl_conv_wgrad", "fl_unflatten", "fl_step_tail",
          "fl_eval", scopes.UNSCOPED)


def read(ctx):
    return scopes.ms_per_round(ctx, PHASES, collective=True)
