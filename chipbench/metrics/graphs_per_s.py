"""ok answers completed inside the window, per second of the window."""


def read(ctx):
    return len(ctx.served(until=ctx.win.t_end)) / ctx.win.seconds
