"""What the plain references of ``models/`` share.

A model kind's reference (``chipbench/models/<kind>.py``) is written in
straightforward ``jax.numpy`` at float32 "highest" matmul precision.  It
imports nothing of the program and takes nothing the program made except
the parameters it served with (weights are inputs of both); the graph
enters as Â = D^-1/2 (A + I) D^-1/2, built densely here from the
request's own edge list (``D`` counts the nonzeros of each row of A + I).

``dtype`` selects the control: the same arithmetic with every stored
operand, intermediate and output rounded to a lower precision
(``bfloat16`` or ``float8_e4m3fn``) and the products still accumulated in
float32, as a lower-precision serving path would.

Graphs are run in blocks of equal padded size, so a run compiles one
program per (block, padded nodes, features) shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .graphs import EdgeGraph


def normalized_adjacency(g: EdgeGraph, n_pad: int | None = None) -> np.ndarray:
    """Dense Â of ``g`` as float32, zero-padded to ``n_pad`` rows/cols."""
    n_pad = n_pad or g.n
    src, dst = g.unique_edges()
    a = np.zeros((n_pad, n_pad), np.float32)
    a[src, dst] = 1.0
    deg = a.sum(axis=1)
    dinv = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1.0)), 0.0)
    return (dinv[:, None] * a * dinv[None, :]).astype(np.float32)


def round_to(a, dtype):
    """``a`` rounded to ``dtype`` and back to float32 (``None``: as is)."""
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _pad_size(n: int) -> int:
    return max(32, 1 << (int(n) - 1).bit_length())


def blocked_outputs(forward, graphs, feats, params, *, readout: str | None,
                    dtype=None, block: int = 16) -> list[np.ndarray]:
    """``forward(adj, x, mask, params, readout, dtype)`` over blocks of
    (graph, features) pairs: node outputs ``(n, f_out)`` for
    ``readout=None``, one ``(f_out,)`` row per graph otherwise."""
    out: list[np.ndarray | None] = [None] * len(graphs)
    by_size: dict[int, list[int]] = {}
    for i, g in enumerate(graphs):
        by_size.setdefault(_pad_size(g.n) if len(graphs) > 1 else g.n,
                           []).append(i)
    with jax.default_matmul_precision("highest"):
        for p_n, idxs in sorted(by_size.items()):
            # one block size per padded size, so few shapes compile
            b_n = min(block, max(1, (2**28) // (4 * p_n * p_n)))
            for s in range(0, len(idxs), b_n):
                chunk = idxs[s:s + b_n]
                f = feats[chunk[0]].shape[1]
                adj = np.zeros((b_n, p_n, p_n), np.float32)
                x = np.zeros((b_n, p_n, f), np.float32)
                mask = np.zeros((b_n, p_n), np.float32)
                for k, i in enumerate(chunk):
                    n = graphs[i].n
                    adj[k] = normalized_adjacency(graphs[i], p_n)
                    x[k, :n] = feats[i]
                    mask[k, :n] = 1.0
                res = np.asarray(forward(jnp.asarray(adj), jnp.asarray(x),
                                         jnp.asarray(mask), params,
                                         readout, dtype))
                for k, i in enumerate(chunk):
                    out[i] = res[k] if readout else res[k, :graphs[i].n]
    return out  # type: ignore[return-value]


def max_gap(outputs, refs) -> float:
    """The widest gap between a served output and its reference, as a share
    of the largest reference magnitude over the whole sample."""
    scale = max(float(np.max(np.abs(r))) for r in refs)
    gap = 0.0
    for o, r in zip(outputs, refs):
        o = np.asarray(o, np.float32)
        if o.shape != r.shape or not np.isfinite(o).all():
            return float("inf")
        gap = max(gap, float(np.max(np.abs(o - r))))
    return gap / max(scale, np.finfo(np.float32).tiny)
