"""Reductions shared by the per-layer metric readers in ``metrics/``.

Each returns ``None`` where the run has nothing to read (no trace, no
device events, no layer on a Pallas kernel), never 0 for a share.
"""
from __future__ import annotations

from . import trace as T
from .work import Work, least_time, peaks, share

#: the Pallas aggregation kernels, by the names their ``pallas_call`` gives
AGG_KERNELS = ("fused_agg_cmb", "spmm_ell")


def _device_trace(ctx):
    tr = ctx.trace
    return tr if tr is not None and tr["devices"] else None


def agg_kernels_roofline(ctx) -> float | None:
    """Least time of every layer served on a Pallas kernel over the device
    time of the kernels' events, in %."""
    tr = _device_trace(ctx)
    if tr is None:
        return None
    k_ns = T.kernel_ns(tr, AGG_KERNELS)
    if k_ns <= 0:
        return None
    peak = peaks(ctx.device_kind)
    least, bound = 0.0, {"compute": 0, "memory": 0}
    for rec in ctx.served():
        for w, on_pallas in ctx.layer_works(rec):
            if on_pallas:
                t, b = least_time(w, peak)
                least += t
                bound[b] += 1
    if not least:
        return None
    print(f"agg kernels: least time {least!r} s over kernel time "
          f"{k_ns / 1e9!r} s; layers bound by compute {bound['compute']}, "
          f"by memory {bound['memory']}", flush=True)
    return share(least, k_ns / 1e9)


def forward_flops(ctx) -> float:
    total = Work(0.0, 0.0)
    for rec in ctx.served():
        for w, _ in ctx.layer_works(rec):
            total = total + w
    return total.ops


def mfu_wall(ctx) -> float | None:
    """Forward FLOPs served in the traced span over span x devices x peak,
    in %: the whole step's share of the chips' peak."""
    tr = _device_trace(ctx)
    if tr is None:
        return None
    peak = peaks(ctx.device_kind)
    return share(forward_flops(ctx) / peak["flops_per_s"],
                 len(tr["devices"]) * T.window_ns(tr) / 1e9)


def idle_share(ctx) -> float | None:
    tr = _device_trace(ctx)
    if tr is None:
        return None
    return 100.0 * T.idle_share(tr)
