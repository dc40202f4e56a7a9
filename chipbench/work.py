"""Work of a GCN layer from its logical shapes, and shares of the peaks.

One function counts a layer's operations and bytes, whatever kernel or
phase order implements it: real nodes and real nonzeros of Â, no ELL or
bucket padding.  The peaks come from ``peaks.json``, keyed by the
``device_kind`` JAX reports; a device that is not in it is an error.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


@dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)


def gcn_layer_work(n_nodes: int, nnz: int, f_in: int, f_out: int) -> Work:
    """One GCN layer, relu(Â X W + b), on a graph of ``n_nodes`` nodes
    whose Â has ``nnz`` nonzeros (self-loops included).

    ops = 2·V·f_in·f_out (the dense product) + 2·nnz·min(f_in, f_out)
    (the aggregation, done on the narrower side of the product);
    bytes = 4·(V·f_in + f_in·f_out + V·f_out) (float32 features in,
    weights, output) + 8·nnz (one index and one weight per nonzero).
    """
    v, e = int(n_nodes), int(nnz)
    ops = 2.0 * v * f_in * f_out + 2.0 * e * min(f_in, f_out)
    nbytes = 4.0 * (v * f_in + f_in * f_out + v * f_out) + 8.0 * e
    return Work(ops, nbytes)


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_time(work: Work, peak: dict) -> tuple[float, str]:
    """The least seconds the chip could take for ``work``, and which peak
    bounds it (``"compute"`` or ``"memory"``)."""
    t_ops = work.ops / peak["flops_per_s"]
    t_mem = work.bytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def share(least_s: float, measured_s: float) -> float:
    """``least_s / measured_s`` as a percentage.  A share above 100% means
    the work is counted too high or the time misses part of it, so it is
    refused, never clipped."""
    if measured_s <= 0:
        raise ValueError(f"measured time {measured_s} s is not positive")
    s = least_s / measured_s
    if s > 1.0:
        raise ValueError(
            f"share {100 * s:.3f}% above 100%: {least_s:.6g} s of least "
            f"time in {measured_s:.6g} s measured")
    return 100.0 * s
