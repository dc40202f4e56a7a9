"""Share of the traced span with no operation on the device, mean over
the cell's devices (device trace)."""
from chipbench.layer_metrics import idle_share


def read(ctx):
    return idle_share(ctx)
