"""Shared kernel utilities."""
from __future__ import annotations

import time

import jax
import numpy as np

#: the TPU's (sublane, lane) tile: a block's last two dimensions must be
#: multiples of these, or the whole array dimension.
SUBLANE, LANE = 8, 128

#: bytes of one buffer of a kernel's resident (rows, block_f) vertex-table
#: block.  Pallas double-buffers it, which leaves most of a TPU v5e's
#: 16 MiB of scoped VMEM for the output and scratch blocks.
TABLE_BLOCK_BYTES = 2 * 2**20

#: SMEM bytes for one row block's neighbour indices and weights (two
#: int32/f32 arrays, minor dimension padded to the lane width) and its
#: occupied widths (one int32 a row), all double-buffered: 512 KiB for the
#: first two and 2 KiB for the widths of at most 256 rows; the chip has
#: 1 MiB of SMEM.
SMEM_BLOCK_BYTES = 514 * 2**10


def default_interpret() -> bool:
    """Whether Pallas kernels run in the interpreter: on the CPU, where
    tests check the kernel bodies, and never on the TPU they target.  Any
    other backend is refused rather than silently interpreted."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels target the TPU (interpreted on the CPU); "
        f"backend {backend!r} is neither"
    )


def resolve_use_pallas(use_pallas: bool | None) -> bool:
    """An explicit flag as given; ``None`` means Pallas exactly when JAX's
    default backend is the TPU the kernels are written for."""
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def lane_block_f(block_f: int | None, f: int, rows: int, itemsize: int = 4) -> int:
    """The feature block a kernel can tile ``f`` columns with on the TPU.

    The schedule's ``block_f`` (``None`` = as wide as possible) is rounded
    up to whole 128-lane tiles and capped so one ``(rows, block)`` table
    buffer fits :data:`TABLE_BLOCK_BYTES`; a block that would cover ``f``
    becomes ``f`` itself (a full dimension is always legal).
    """
    cap = max(LANE, TABLE_BLOCK_BYTES // (rows * itemsize) // LANE * LANE)
    bf = min(cdiv(min(block_f or f, f), LANE) * LANE, cap)
    return f if bf >= f else bf


def row_block(block_v: int, v: int, d: int) -> int:
    """Rows per grid step: ``block_v`` rounded up to whole sublane tiles
    and capped so the ``(rows, d)`` index and weight blocks and the
    ``(rows,)`` occupied widths fit :data:`SMEM_BLOCK_BYTES`; a block that
    would cover ``v`` becomes ``v``."""
    cap = SMEM_BLOCK_BYTES // (2 * 4 * (2 * cdiv(d, LANE) * LANE + 1))
    bv = min(cdiv(block_v, SUBLANE) * SUBLANE, max(SUBLANE, cap // SUBLANE * SUBLANE))
    return v if bv >= v else bv


def measure_wall(
    fn,
    *,
    warmup: int = 1,
    iters: int = 5,
    reduce: str = "median",
) -> float:
    """Wall-clock seconds of one ``fn()`` call, measured properly.

    The one timing helper shared by the calibration harness, the serving
    engine's measured re-ranking and the benchmark lanes, so warmup and
    aggregation rules cannot drift between them:

    - every call is followed by ``jax.block_until_ready`` on its result
      (async dispatch otherwise times the enqueue, not the kernel);
    - the first ``warmup`` calls are discarded (compilation/tracing and
      allocator warmup land there);
    - the remaining ``iters`` timings are reduced by ``median`` (robust
      to scheduler noise; default), ``min`` or ``mean``.
    """
    if reduce not in ("median", "min", "mean"):
        raise ValueError(
            f"reduce must be 'median', 'min' or 'mean', got {reduce!r}"
        )
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    agg = {"median": np.median, "min": np.min, "mean": np.mean}[reduce]
    return float(agg(ts))
