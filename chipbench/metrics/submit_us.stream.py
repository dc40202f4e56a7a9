"""Mean time inside AsyncEngine.submit_async (front end), host clock."""


def read(ctx):
    recs = ctx.win.records
    return 1e6 * sum(r.submit_s for r in recs) / len(recs) if recs else None
