"""Device milliseconds per round in the five sub-round programs
(``neworder_round`` … ``stocklevel_round``); the split per program is in the
result's ``breakdown``."""

PROGRAMS = ("neworder_round", "payment_round", "delivery_round",
            "orderstatus_round", "stocklevel_round")


def read(ctx):
    if ctx.trace is None:
        return None
    found = [ctx.trace.programs[p][1] for p in PROGRAMS
             if p in ctx.trace.programs]
    if not found:
        return None
    return 1e3 * sum(found) / ctx.rounds
