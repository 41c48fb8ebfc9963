"""engine_unpack_ms_per_call (ms): host time the round engine spends after
its last dispatch turning the flat carries back into parameter trees,
per ``run_rounds`` call of the traced window: the ``engine.unpack``
span.  None where the program marks no such span."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_call(ctx.trace, ctx.window, ("engine.unpack",))
