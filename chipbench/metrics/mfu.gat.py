"""Forward FLOPs of the GAT served in the traced span over span x devices
x peak (device trace): ``mfu_wall``'s arithmetic with each layer's work
from ``models/gat.py`` and the config's ``heads``."""
from chipbench import trace as T
from chipbench.work import peaks, share


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr["devices"]:
        return None
    heads = ctx.cell.config["model"]["heads"]
    last = len(ctx.dims) - 1
    flops = 0.0
    for rec in ctx.served():
        e = rec.item.edge
        for i, ((fi, fo), h) in enumerate(zip(ctx.dims, heads)):
            flops += ctx.model.layer_work(e.n, e.nnz, fi, fo, h,
                                          i < last).ops
    peak = peaks(ctx.device_kind)
    return share(flops / peak["flops_per_s"],
                 len(tr["devices"]) * T.window_ns(tr) / 1e9)
