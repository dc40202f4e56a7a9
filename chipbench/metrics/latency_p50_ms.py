"""Median due (or send) -> answer time over every request of the window."""
from chipbench.stats import percentile


def read(ctx):
    lat = ctx.latencies_ms()
    return percentile(lat, 50) if lat else None
