"""SP-Optimized fused aggregation+combination kernel.

The paper's SP-Optimized inter-phase dataflow (Sec. 4.2, Table 2 row 2):
the aggregated tile is kept *in the PEs* and consumed directly by the
combination phase — ``SP_AC({V_x F_x} N_t, {V_x F_x} G_t)`` with
T_V/T_F shared between phases and temporal reduction (T_N = 1).

TPU translation: one ``pallas_call`` whose grid walks row blocks (T_V)
and, innermost, feature blocks (T_F).  Each step (a) gathers + accumulates
the neighbor rows of one feature block into a VMEM scratch tile h (the
aggregation, see :func:`repro.kernels.spmm.kernel.gather_rows`), then
(b) immediately feeds h into the MXU matmul with the matching rows of the
weight (the combination), adding into the row block's float32 output.
The V x F intermediate never exists in HBM — that is the entire point of
SP-Optimized, and it is the same trick flash-attention plays on the
attention GEMM-GEMM chain.  Only one (V, T_F) feature block of the vertex
table is resident at a time, so the kernel's VMEM does not grow with F.

The price of the row-block-outer grid: with more than one feature block,
every row block streams the whole (V, F) table from HBM again, so one
call reads (V_pad / T_V) x V x F elements of it (the ELL SpMM kernel,
row blocks inner, reads the table once).  With a single feature block
the block index never changes and the table is read once.  The gather
walks only each row's occupied ELL slots, so its cost per feature block
is the row block's nonzeros plus a fixed overhead per row, not
T_V x D; the table stream and the MXU matmul are paid in full.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..spmm.kernel import gather_rows


def _kernel(cnt_ref, idx_ref, wts_ref, x_ref, w_ref, o_ref, h_ref):
    """out[b, :] += (sum_d wts[b,d] * x[idx[b,d], fblock]) @ w[fblock] — fused."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # the tile lives only in VMEM
    gather_rows(cnt_ref, idx_ref, wts_ref, x_ref, h_ref)
    o_ref[...] += jnp.dot(
        h_ref[...], w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def fused_agg_cmb_kernel(
    counts: jax.Array,  # (V_pad,) int32, see spmm.kernel.occupied_width
    indices: jax.Array,  # (V_pad, D)
    weights: jax.Array,  # (V_pad, D)
    x: jax.Array,  # (V, F)
    w: jax.Array,  # (F, G)
    *,
    block_v: int,
    block_f: int,
    interpret: bool,
) -> jax.Array:
    """Fused (A @ X) @ W with the intermediate pinned in VMEM; float32
    (V_pad, G).  ``block_v`` must divide V_pad and ``block_f`` must
    divide F (see :mod:`repro.kernels.fused_agg_cmb.ops`)."""
    v_pad, d = indices.shape
    v, f = x.shape
    f2, g = w.shape
    assert f == f2
    bv, bf = block_v, block_f
    smem = pl.BlockSpec((bv, d), lambda i, k: (i, 0), memory_space=pltpu.SMEM)
    # the widths as (row blocks, 1, bv): a 1-D SMEM block must match the
    # array's HBM tiling, and a (1, bv) block of a 2-D array is not legal
    cnt = pl.BlockSpec((None, 1, bv), lambda i, k: (i, 0, 0),
                       memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((v_pad, g), jnp.float32),
        grid=(v_pad // bv, f // bf),
        in_specs=[
            cnt,
            smem,
            smem,
            pl.BlockSpec((v, bf), lambda i, k: (0, k)),  # one feature block
            pl.BlockSpec((bf, g), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bv, g), lambda i, k: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bv, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="fused_agg_cmb",
    )(counts.reshape(v_pad // bv, 1, bv), indices, weights, x, w)
