"""Share of the GAT aggregation kernel's roofline (device trace): the
least time of the attention work of every layer served on the kernel,
over the device time of the ``gat_agg`` events."""
from chipbench import trace as T
from chipbench.work import least_time, peaks, share


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr["devices"]:
        return None
    k_ns = T.kernel_ns(tr, ("gat_agg",))
    if k_ns <= 0:
        return None
    heads = ctx.cell.config["model"]["heads"]
    peak = peaks(ctx.device_kind)
    least = 0.0
    for rec in ctx.served():
        e = rec.item.edge
        flags = ctx.pallas.get(ctx.policy.bucket_of(rec.item.csr),
                               [False] * len(ctx.dims))
        last = len(ctx.dims) - 1
        for i, ((fi, fo), h, on) in enumerate(zip(ctx.dims, heads, flags)):
            if on:
                width = fo if i < last else h * fo
                least += least_time(
                    ctx.model.attention_work(e.n, e.nnz, width, h), peak)[0]
    if not least:
        return None
    print(f"gat_agg: least time {least!r} s over kernel time "
          f"{k_ns / 1e9!r} s", flush=True)
    return share(least, k_ns / 1e9)
