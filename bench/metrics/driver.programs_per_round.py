"""Device program runs per round in the window on the busiest chip: the
jitted sub-round programs and the mover, and every op-by-op program the
host driver dispatches between them (trace, ``XLA Modules`` events). On a
mesh the driver's op-by-op programs run on the first chip alone."""


def read(ctx):
    if ctx.trace is None or not any(ctx.trace.runs):
        return None
    return max(ctx.trace.runs) / ctx.rounds
