"""From a profiler trace to the numbers the benchmark reports.

:func:`extract` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
only what the reduction needs, in plain lists (the committed test trace is
in this form):

* ``span``: start and end (ns) of the harness's ``bench.window`` span;
* ``devices``: per device plane, the events of its ``XLA Ops`` line as
  ``[name, start_ns, duration_ns]``, named by the HLO instruction
  (``fused_agg_cmb.2``);
* ``host``: the harness's own ``bench.*`` spans.

The reduction (busy union, idle share, time by kernel name, top
operations, idle gaps named by the host span that overlaps them) works on
that form only.
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "bench.window"


def op_name(name: str) -> str:
    """``fused_agg_cmb.2`` from an event named by its HLO instruction,
    ``%fused_agg_cmb.2 = f32[4096,16]{...} custom-call(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(xplane_path: str | Path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    out = {"span": None, "devices": {}, "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out["devices"].setdefault(plane.name, []).extend(
                        [op_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append([e.name, e.start_ns, e.duration_ns])
    for name, start, dur in out["host"]:
        if name == WINDOW_SPAN:
            out["span"] = [start, start + dur]
    if out["span"] is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {xplane_path}")
    return out


def merged(intervals) -> list[list[float]]:
    """Sorted union of ``[start, end]`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(events, lo: float, hi: float) -> list[list[float]]:
    """The union of the events' intervals, clipped to [lo, hi]."""
    return merged(
        [max(s, lo), min(s + d, hi)]
        for _, s, d in events if s + d > lo and s < hi
    )


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in busy_intervals(events, lo, hi))


def window_ns(trace: dict) -> float:
    lo, hi = trace["span"]
    return hi - lo


def mean_busy_ns(trace: dict) -> float | None:
    """Busy time averaged over the device planes, or None without any."""
    devs = trace["devices"]
    if not devs:
        return None
    lo, hi = trace["span"]
    return sum(busy_ns(ev, lo, hi) for ev in devs.values()) / len(devs)


def idle_share(trace: dict) -> float | None:
    """1 - busy / window, averaged over the devices (a fraction)."""
    busy = mean_busy_ns(trace)
    if busy is None:
        return None
    return 1.0 - busy / window_ns(trace)


def matches(name: str, kernels) -> bool:
    return any(name == k or name.startswith(k + ".") for k in kernels)


def kernel_ns(trace: dict, kernels) -> float:
    """Summed device durations of the events named after ``kernels``,
    over every device, for events that start inside the span."""
    lo, hi = trace["span"]
    return float(sum(d for ev in trace["devices"].values()
                     for n, s, d in ev if lo <= s < hi and matches(n, kernels)))


def top_ops(trace: dict, k: int = 10) -> list[list]:
    """The ``k`` device operations with most summed time in the span."""
    lo, hi = trace["span"]
    tot: dict[str, float] = {}
    for ev in trace["devices"].values():
        for n, s, d in ev:
            if lo <= s < hi:
                tot[n] = tot.get(n, 0.0) + d
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in ranked]


def idle_gaps(trace: dict, k: int = 10) -> list[list]:
    """The ``k`` longest gaps between device operations inside the span,
    each named by the ``bench.*`` host span (other than the window) that
    overlaps it most, or ``unattributed``."""
    lo, hi = trace["span"]
    spans = [(n, s, s + d) for n, s, d in trace["host"] if n != WINDOW_SPAN]
    gaps = []
    for ev in trace["devices"].values():
        edges = [lo]
        for s, e in busy_intervals(ev, lo, hi):
            edges += [s, e]
        edges.append(hi)
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in gaps[:k]:
        best, best_ov = "unattributed", 0.0
        for n, s, e in spans:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = n, ov
        out.append([best, (g1 - g0) / 1e9])
    return out
