"""Device milliseconds per round in collective operations (all-reduce,
all-gather, psum …), averaged over the chips; nothing to read on one chip."""


def read(ctx):
    if ctx.trace is None or ctx.trace.chips < 2:
        return None
    return 1e3 * ctx.trace.collective_s / ctx.rounds
