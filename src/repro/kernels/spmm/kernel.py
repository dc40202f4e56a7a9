"""Aggregation-phase SpMM kernel over padded-ELL adjacency.

TPU adaptation of the paper's CSR aggregation (Sec. 2.1): rows are grouped
into blocks of ``block_v`` (the paper's T_V), neighbor lists are padded to
the ELL width D, and features are blocked by ``block_f`` (T_F).  The grid
is (feature blocks x row blocks) — both "spatial" in taxonomy terms — and
the neighbor dimension is walked temporally inside the kernel
(``V_s F_s N_t``).  Row blocks are the inner grid axis, so one feature
block of the vertex table stays resident in VMEM while every row block
gathers from it.

The row block's neighbor indices and weights sit in SMEM; each output row
accumulates its D neighbor rows, read one at a time by a dynamic
``pl.ds`` slice of the table (the chip's vector units cannot gather by a
vector of row indices).

The padded slots (weight 0, index 0) are the lockstep/evil-row waste the
paper's simulator charges for — here they cost real gather steps, so the
kernel's cost structure matches the cost model's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def gather_rows(idx_ref, wts_ref, x_ref, h_ref) -> None:
    """h[b, :] = sum_d wts[b, d] * x[idx[b, d], :] for every row b of the
    block, accumulated in float32."""
    rows, ell_width = idx_ref.shape
    width = h_ref.shape[1]

    def row(b, carry):
        def slot(d, acc):
            nbr = x_ref[pl.ds(idx_ref[b, d], 1), :].astype(jnp.float32)
            return acc + wts_ref[b, d] * nbr

        acc = jax.lax.fori_loop(
            0, ell_width, slot, jnp.zeros((1, width), jnp.float32)
        )
        h_ref[pl.ds(b, 1), :] = acc.astype(h_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows, row, 0)


def _kernel(idx_ref, wts_ref, x_ref, o_ref):
    gather_rows(idx_ref, wts_ref, x_ref, o_ref)


def spmm_ell(
    indices: jax.Array,  # (V_pad, D) int32
    weights: jax.Array,  # (V_pad, D) f32
    x: jax.Array,  # (V, F)
    *,
    block_v: int,
    block_f: int,
    interpret: bool,
) -> jax.Array:
    """out[v] = sum_d weights[v, d] * x[indices[v, d]]  — (V_pad, F).

    ``block_v`` must divide V_pad and ``block_f`` must divide F; both must
    be legal TPU block extents (see :mod:`repro.kernels.spmm.ops`)."""
    v_pad, d = indices.shape
    v, f = x.shape
    bv, bf = block_v, block_f
    smem = pl.BlockSpec((bv, d), lambda j, i: (i, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((v_pad, f), x.dtype),
        grid=(f // bf, v_pad // bv),
        in_specs=[
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
    )(indices, weights, x)
