"""Share of the aggregation kernels' roofline (device trace)."""
from chipbench.layer_metrics import agg_kernels_roofline


def read(ctx):
    return agg_kernels_roofline(ctx)
