"""Jitted wrapper for the ELL SpMM aggregation kernel.

The schedule's ``block_v`` / ``block_f`` become legal TPU blocks here
(:func:`~repro.kernels.common.row_block`,
:func:`~repro.kernels.common.lane_block_f`), and the rows and feature
columns are zero-padded to whole blocks.

Pallas gives ``pallas_call`` no transpose rule, so reverse-mode
differentiation (``Program.train_step``) takes the VJP of the jnp oracle
:func:`~repro.kernels.spmm.ref.spmm_ref`; the forward pass stays on the
kernel.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..common import cdiv, default_interpret, lane_block_f, row_block
from .kernel import occupied_width, spmm_ell as _raw
from .ref import spmm_ref


def _spmm_kernel(indices, weights, x, block_v, block_f):
    v_pad, d = indices.shape
    v, f = x.shape
    bv, bf = row_block(block_v, v_pad, d), lane_block_f(block_f, f, v)
    vp = cdiv(v_pad, bv) * bv
    fp = cdiv(f, bf) * bf
    idx = jnp.pad(indices, ((0, vp - v_pad), (0, 0)))
    wts = jnp.pad(weights, ((0, vp - v_pad), (0, 0)))
    xp = jnp.pad(x, ((0, 0), (0, fp - f)))
    out = _raw(occupied_width(wts), idx, wts, xp, block_v=bv, block_f=bf,
               interpret=default_interpret())
    return out[:v_pad, :f]


_spmm = jax.custom_vjp(_spmm_kernel, nondiff_argnums=(3, 4))


def _spmm_fwd(indices, weights, x, block_v, block_f):
    return _spmm_kernel(indices, weights, x, block_v, block_f), (indices, weights, x)


def _spmm_bwd(block_v, block_f, res, g):
    indices, weights, x = res
    _, vjp = jax.vjp(lambda w, xx: spmm_ref(indices, w, xx), weights, x)
    return (None, *vjp(g))


_spmm.defvjp(_spmm_fwd, _spmm_bwd)


@functools.partial(jax.jit, static_argnames=("block_v", "block_f"))
def spmm(indices, weights, x, block_v=128, block_f=128):
    return _spmm(indices, weights, x, block_v, block_f)


def spmm_streamed(indices, weights, x, *, block_rows=4096,
                  block_v=128, block_f=128):
    """Row-streamed SpMM for feature tables too large to stage at once.

    Splits the ELL rows into ``block_rows`` slabs; each slab gathers only
    the feature rows it references (the halo gather) and runs :func:`spmm`
    on the compact table, so the per-call working set is bounded by the
    slab's closure instead of the full V x F matrix.  Rows are independent,
    so the concatenated result is bit-identical to
    ``spmm(indices, weights, x)``.
    """
    v_pad = indices.shape[0]
    if v_pad <= block_rows:
        return spmm(indices, weights, x, block_v=block_v, block_f=block_f)
    idx_h = np.asarray(indices)
    outs = []
    for s in range(0, v_pad, block_rows):
        blk = idx_h[s:s + block_rows]
        uniq, inv = np.unique(blk, return_inverse=True)
        outs.append(spmm(
            jnp.asarray(inv.reshape(blk.shape).astype(idx_h.dtype)),
            weights[s:s + block_rows],
            x[uniq],
            block_v=block_v,
            block_f=block_f,
        ))
    return jnp.concatenate(outs, axis=0)
