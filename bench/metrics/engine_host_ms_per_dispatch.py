"""engine_host_ms_per_dispatch (ms): host time the round engine spends
per dispatch outside the device wait, from the ``EngineResult.timing``
of the window's ``run_rounds`` call: (dispatch_enqueue_ms +
host_residency_ms) / dispatches."""


def read(ctx):
    if not ctx.dispatches:
        return None
    t = ctx.timing
    return (t["dispatch_enqueue_ms"] + t["host_residency_ms"]) / \
        ctx.dispatches
