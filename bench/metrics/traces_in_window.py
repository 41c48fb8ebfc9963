"""traces_in_window (count): JAX traces inside the timed window, each a
jit-cache miss whose program is then compiled or, warmed up, loaded
from the persistent compile cache: host work that the window's call
repeats on every call."""


def read(ctx):
    return ctx.traces_in_window
