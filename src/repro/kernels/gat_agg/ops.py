"""Jitted wrapper for the GAT aggregation kernel.

Builds the kernel's ``[z | t]`` table and repeated self scores from the
layer's z (V, H*F') and scores s, t (V, H), pads the rows to whole row
blocks (:func:`~repro.kernels.common.row_block`) and sizes the kernel's
scoped VMEM for its resident table (:func:`vmem_need`).  The table is
float32 whatever z's dtype (a bfloat16 z is held exactly): the chip's
compiler refuses the kernel's one-row dynamic read from a packed
bfloat16 table.

As for :mod:`repro.kernels.spmm.ops`, reverse-mode differentiation takes
the VJP of the jnp oracle :func:`~repro.kernels.gat_agg.ref.gat_agg_ref`,
since ``pallas_call`` has no transpose rule.
"""
import functools

import jax
import jax.numpy as jnp

from ..common import LANE, SUBLANE, cdiv, default_interpret, row_block
from ..spmm.kernel import occupied_width
from .kernel import gat_agg_kernel as _raw
from .ref import gat_agg_ref

#: the scoped VMEM a kernel gets unless it asks for more (TPU v5e)
DEFAULT_SCOPED_VMEM = 16 * 2**20
#: the most this kernel asks for: the v5e's 128 MiB of VMEM less room for
#: the compiler's own scratch
MAX_SCOPED_VMEM = 100 * 2**20
#: headroom for the compiler's internal scratch inside the limit
VMEM_HEADROOM = 4 * 2**20


def table_lanes(width: int) -> int:
    """P, the table's lanes: z and t each in half of it, P a multiple of
    the 128-lane tile."""
    return 2 * cdiv(width, LANE // 2) * (LANE // 2)


def vmem_need(v: int, width: int, block_v: int) -> int:
    """Bytes of VMEM the kernel's float32 blocks take: the (V, P) table,
    one buffer (its block never changes), and the (block_v, P) self-score
    and output blocks, double-buffered."""
    p = table_lanes(width)
    return 4 * p * (cdiv(v, SUBLANE) * SUBLANE + 2 * 2 * block_v)


def _gat_kernel(indices, weights, z, s, t, block_v):
    v_pad, d = indices.shape
    v, width = z.shape
    heads = s.shape[1]
    fh = width // heads
    p = table_lanes(width)
    half = p // 2
    bv = row_block(block_v, v_pad, d)
    vp = cdiv(v_pad, bv) * bv
    need = vmem_need(v, width, bv)
    if need + VMEM_HEADROOM > MAX_SCOPED_VMEM:
        raise ValueError(
            f"gat_agg keeps its ({v}, {p}) table resident in VMEM: "
            f"{need} bytes of blocks are over the kernel's "
            f"{MAX_SCOPED_VMEM}-byte limit")
    limit = None if need + VMEM_HEADROOM <= DEFAULT_SCOPED_VMEM else (
        need + VMEM_HEADROOM)

    def halfwidth(a):  # (V, W) -> (V, half), float32
        return jnp.pad(a.astype(jnp.float32), ((0, 0), (0, half - width)))

    zt = jnp.concatenate([halfwidth(z), halfwidth(jnp.repeat(t, fh, axis=1))],
                         axis=1)
    se = jnp.pad(jnp.repeat(s, fh, axis=1).astype(jnp.float32),
                 ((0, vp - v), (0, p - width)))
    idx = jnp.pad(indices, ((0, vp - v_pad), (0, 0)))
    wts = jnp.pad(weights, ((0, vp - v_pad), (0, 0)))
    out = _raw(occupied_width(wts), idx, wts, se, zt, block_v=bv,
               vmem_limit_bytes=limit, interpret=default_interpret())
    return out[:v_pad, :width]


_gat = jax.custom_vjp(_gat_kernel, nondiff_argnums=(5,))


def _gat_fwd(indices, weights, z, s, t, block_v):
    out = _gat_kernel(indices, weights, z, s, t, block_v)
    return out, (indices, weights, z, s, t)


def _gat_bwd(block_v, res, g):
    indices, weights, z, s, t = res
    _, vjp = jax.vjp(
        lambda zz, ss, tt: gat_agg_ref(indices, weights, zz, ss, tt), z, s, t
    )
    return (None, None, *vjp(g))


_gat.defvjp(_gat_fwd, _gat_bwd)


@functools.partial(jax.jit, static_argnames=("block_v",))
def gat_agg(indices, weights, z, s, t, block_v=128):
    """Float32 (V_pad, H*F'): per-head softmax-weighted neighbour sums
    (see :func:`~repro.kernels.gat_agg.ref.gat_agg_ref`)."""
    return _gat(indices, weights, z, s, t, block_v)
