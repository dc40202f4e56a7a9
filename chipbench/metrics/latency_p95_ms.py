"""95th percentile due (or send) -> answer time over every request."""
from chipbench.stats import percentile


def read(ctx):
    lat = ctx.latencies_ms()
    return percentile(lat, 95) if lat else None
