"""unscoped_device_ms_per_round (ms): the round program's plumbing, the
first chip's own device ms per round of its ops under no ``fl_*`` phase:
scan carries, client and batch sampling, XLA's layout copies.  None
without the round program's phases, and so for a program that names
none (all of its ops would read as unscoped)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_round(ctx, (scopes.UNSCOPED,))
