"""compiles_in_window (count): programs compiled inside the timed
window, each a request to the backend that the persistent compile cache
did not answer.  A warmed-up window reads 0."""


def read(ctx):
    return ctx.compiles_in_window
