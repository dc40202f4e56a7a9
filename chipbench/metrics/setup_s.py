"""Process start to the first due request (host clock)."""


def read(ctx):
    return ctx.setup_s
