"""engine_pack_ms_per_call (ms): host time the round engine spends before
its first dispatch, per ``run_rounds`` call of the traced window: the
``engine.pack`` span (flatten and place the params, init the algorithm
and server state) plus ``engine.prepare_data`` (upload the data and the
eval stream).  None where the program marks no such span."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_call(ctx.trace, ctx.window,
                              ("engine.pack", "engine.prepare_data"))
