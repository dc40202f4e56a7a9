"""Aggregation-phase SpMM kernel over padded-ELL adjacency.

TPU adaptation of the paper's CSR aggregation (Sec. 2.1): rows are grouped
into blocks of ``block_v`` (the paper's T_V), neighbor lists are padded to
the ELL width D, and features are blocked by ``block_f`` (T_F).  The grid
is (feature blocks x row blocks) — both "spatial" in taxonomy terms — and
the neighbor dimension is walked temporally inside the kernel
(``V_s F_s N_t``).  Row blocks are the inner grid axis, so one feature
block of the vertex table stays resident in VMEM while every row block
gathers from it.

The row block's neighbor indices, weights and occupied widths sit in
SMEM; each output row accumulates its neighbor rows, read one at a time by
a dynamic ``pl.ds`` slice of the table (the chip's vector units cannot
gather by a vector of row indices).

The padded slots (weight 0, index 0) are the lockstep/evil-row waste the
paper's simulator charges for.  The kernel does not pay it: each row walks
only its occupied width (:func:`occupied_width`, one past its last nonzero
weight), so a row costs its own degree plus a fixed row overhead, and an
empty pad row costs the overhead alone.  Every skipped slot has weight 0,
so for finite features the result is bit for bit that of the padded walk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def occupied_width(weights: jax.Array) -> jax.Array:
    """Per row, one past its last slot with a nonzero weight (0 for a row
    with none): the slots :func:`gather_rows` walks.  ``(V_pad,)`` int32."""
    slot = jnp.arange(1, weights.shape[1] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(weights != 0, slot, 0), axis=1, initial=0)


def walk_rows(cnt_ref, rows: int, init, slot, store) -> None:
    """The occupied walk of a row block: for every row b < ``rows``,
    ``carry = init(b)``, then ``carry = slot(b, d, carry)`` for each
    d < cnt[0, b], then ``store(b, carry)``.  ``cnt_ref`` is the block's
    ``(1, rows)`` slice of :func:`occupied_width`.  Every aggregation
    kernel walks its slots through this one loop."""

    def row(b, carry):
        acc = jax.lax.fori_loop(
            0, cnt_ref[0, b], functools.partial(slot, b), init(b)
        )
        store(b, acc)
        return carry

    jax.lax.fori_loop(0, rows, row, 0)


def gather_rows(cnt_ref, idx_ref, wts_ref, x_ref, h_ref) -> None:
    """h[b, :] = sum_{d < cnt[0, b]} wts[b, d] * x[idx[b, d], :] for every
    row b of the block, accumulated in float32 (:func:`walk_rows`)."""
    width = h_ref.shape[1]

    def slot(b, d, acc):
        nbr = x_ref[pl.ds(idx_ref[b, d], 1), :].astype(jnp.float32)
        return acc + wts_ref[b, d] * nbr

    def store(b, acc):
        h_ref[pl.ds(b, 1), :] = acc.astype(h_ref.dtype)

    walk_rows(cnt_ref, idx_ref.shape[0],
              lambda b: jnp.zeros((1, width), jnp.float32), slot, store)


def _kernel(cnt_ref, idx_ref, wts_ref, x_ref, o_ref):
    gather_rows(cnt_ref, idx_ref, wts_ref, x_ref, o_ref)


def spmm_ell(
    counts: jax.Array,  # (V_pad,) int32, see occupied_width
    indices: jax.Array,  # (V_pad, D) int32
    weights: jax.Array,  # (V_pad, D) f32
    x: jax.Array,  # (V, F)
    *,
    block_v: int,
    block_f: int,
    interpret: bool,
) -> jax.Array:
    """out[v] = sum_{d < counts[v]} weights[v, d] * x[indices[v, d]]  —
    (V_pad, F).

    ``block_v`` must divide V_pad and ``block_f`` must divide F; both must
    be legal TPU block extents (see :mod:`repro.kernels.spmm.ops`)."""
    v_pad, d = indices.shape
    v, f = x.shape
    bv, bf = block_v, block_f
    smem = pl.BlockSpec((bv, d), lambda j, i: (i, 0), memory_space=pltpu.SMEM)
    # the widths as (row blocks, 1, bv): a 1-D SMEM block must match the
    # array's HBM tiling, and a (1, bv) block of a 2-D array is not legal
    cnt = pl.BlockSpec((None, 1, bv), lambda j, i: (i, 0, 0),
                       memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((v_pad, f), x.dtype),
        grid=(f // bf, v_pad // bv),
        in_specs=[
            cnt,
            smem,
            smem,
            pl.BlockSpec((v, bf), lambda j, i: (0, j)),  # full vertex table
        ],
        out_specs=pl.BlockSpec((bv, bf), lambda j, i: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="spmm_ell",
    )(counts.reshape(v_pad // bv, 1, bv), indices, weights, x)
