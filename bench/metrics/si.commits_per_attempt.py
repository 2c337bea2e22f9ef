"""Commits over attempts of all five types in the window, from the driver's
statistics: the share of the protocol's work that is not wasted on aborts
(a retried transaction is attempted again)."""


def read(ctx):
    attempts = sum(ctx.stats["attempts"].values())
    if not attempts:
        return None
    return sum(ctx.stats["commits"].values()) / attempts
