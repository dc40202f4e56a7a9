"""Forward FLOPs served over traced span x peak (open loops: fixed by the
offered rate while every request is served)."""
from chipbench.layer_metrics import mfu_wall


def read(ctx):
    return mfu_wall(ctx)
