"""Device milliseconds per round in the version mover's program."""


def read(ctx):
    if ctx.trace is None or "version_mover" not in ctx.trace.programs:
        return None
    return 1e3 * ctx.trace.programs["version_mover"][1] / ctx.rounds
