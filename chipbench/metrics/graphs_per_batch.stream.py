"""Graphs served per device batch (scheduler counters of the window)."""


def read(ctx):
    n = ctx.win.n_batches
    return len(ctx.served()) / n if n else None
