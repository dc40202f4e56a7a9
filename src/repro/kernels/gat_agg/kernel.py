"""GAT aggregation kernel: edge scores, a per-row softmax and the
per-head weighted sum in one walk of each row's occupied ELL slots.

The first layer whose edge weights come from the features (Velickovic et
al., "Graph Attention Networks", arXiv:1710.10903): for head h of row i
and each neighbour j of the row (self-loop included),

    e_ij = LeakyReLU(s_i + t_j),  alpha_ij = softmax_j(e_ij),
    out_i = sum_j alpha_ij z_j,

with z = X W computed first (the CA order) and s = z a_self, t = z a_nbr
per head.  Each neighbour is one row of a ``[z | t]`` table: z in lanes
``[0, W)`` and t, repeated over each head's F' columns, in lanes
``[half, half + W)`` with ``half`` = P / 2.  One roll of the row by
``half`` lanes lines t up with z, so the scores, the running max and sum
of an online softmax and the weighted sum are all elementwise over the
row's vector: no lane crosses to another.  Lanes outside ``[0, W)`` carry
bounded garbage (every exp has a non-positive argument) and are sliced
off by the caller.

The row walk is :func:`~repro.kernels.spmm.kernel.walk_rows`: a row costs
its own degree plus a fixed overhead, and a row with no slot (bucket
padding) gives 0.  The whole table is one block whose index never changes,
so it is fetched into VMEM once per call; the ops wrapper raises the
kernel's scoped-VMEM limit to hold it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..spmm.kernel import walk_rows
from .ref import SLOPE

#: the running max before a row's first slot: finite, so a masked slot's
#: rescale exp(m - m) is 1, never exp(-inf + inf)
_NEG = -1e30


def _kernel(cnt_ref, idx_ref, wts_ref, s_ref, zt_ref, o_ref):
    width = o_ref.shape[1]

    def init(b):
        s = s_ref[pl.ds(b, 1), :].astype(jnp.float32)
        zero = jnp.zeros((1, width), jnp.float32)
        return s, jnp.full((1, width), _NEG, jnp.float32), zero, zero

    def slot(b, d, carry):
        s, m, l, acc = carry
        row = zt_ref[pl.ds(idx_ref[b, d], 1), :].astype(jnp.float32)
        e = s + pltpu.roll(row, width // 2, 1)
        e = jnp.where(e > 0, e, SLOPE * e)
        live = wts_ref[b, d] != 0
        m_new = jnp.where(live, jnp.maximum(m, e), m)
        a = jnp.exp(m - m_new)
        p = jnp.where(live, jnp.exp(e - m_new), 0.0)
        return s, m_new, l * a + p, acc * a + p * row

    def store(b, carry):
        _, _, l, acc = carry
        out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[pl.ds(b, 1), :] = out.astype(o_ref.dtype)

    walk_rows(cnt_ref, idx_ref.shape[0], init, slot, store)


def gat_agg_kernel(
    counts: jax.Array,  # (V_pad,) int32, see spmm.kernel.occupied_width
    indices: jax.Array,  # (V_pad, D) int32
    weights: jax.Array,  # (V_pad, D) — the edge mask (nonzero = edge)
    s: jax.Array,  # (V_pad, P) self scores, each head's repeated F' times
    zt: jax.Array,  # (V, P) the [z | t] table
    *,
    block_v: int,
    vmem_limit_bytes: int | None,
    interpret: bool,
) -> jax.Array:
    """Float32 (V_pad, P): lanes [0, W) hold each row's per-head softmax-
    weighted sums.  ``block_v`` must divide V_pad and P must be a multiple
    of 128 (see :mod:`repro.kernels.gat_agg.ops`)."""
    v_pad, d = indices.shape
    v, p = zt.shape
    bv = block_v
    smem = pl.BlockSpec((bv, d), lambda i: (i, 0), memory_space=pltpu.SMEM)
    # the widths as (row blocks, 1, bv), as in spmm.kernel
    cnt = pl.BlockSpec((None, 1, bv), lambda i: (i, 0, 0),
                       memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((v_pad, p), jnp.float32),
        grid=(v_pad // bv,),
        in_specs=[
            cnt,
            smem,
            smem,
            pl.BlockSpec((bv, p), lambda i: (i, 0)),
            # the whole table, fetched once: one buffer is enough
            pl.BlockSpec((v, p), lambda i: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
        ],
        out_specs=pl.BlockSpec((bv, p), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
        name="gat_agg",
    )(counts.reshape(v_pad // bv, 1, bv), indices, weights, s, zt)
