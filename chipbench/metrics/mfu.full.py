"""Forward FLOPs served over traced span x peak (closed loop)."""
from chipbench.layer_metrics import mfu_wall


def read(ctx):
    return mfu_wall(ctx)
