"""Jitted wrapper for the fused SP-Optimized kernel.

``band_size`` is the Pallas row block (the schedule's T_V) and ``block_f``
the feature block (T_F) over which the contraction dimension is walked
with a float32 accumulator.  Both become legal TPU blocks here
(:func:`~repro.kernels.common.row_block`,
:func:`~repro.kernels.common.lane_block_f`): a mapper choice like
``Vs(64)Fs(8)`` runs with 64-row blocks and one 128-lane feature block.

As for :mod:`repro.kernels.spmm.ops`, reverse-mode differentiation takes
the VJP of the jnp oracle :func:`~repro.kernels.fused_agg_cmb.ref.fused_ref`,
since ``pallas_call`` has no transpose rule.
"""
import functools

import jax
import jax.numpy as jnp

from ..common import cdiv, default_interpret, lane_block_f, row_block
from ..spmm.kernel import occupied_width
from .kernel import fused_agg_cmb_kernel as _raw
from .ref import fused_ref


def _fused_kernel(indices, weights, x, w, band_size, block_f):
    v_pad, d = indices.shape
    v, f = x.shape
    bv, bf = row_block(band_size, v_pad, d), lane_block_f(block_f, f, v)
    vp = cdiv(v_pad, bv) * bv
    fp = cdiv(f, bf) * bf
    idx = jnp.pad(indices, ((0, vp - v_pad), (0, 0)))
    wts = jnp.pad(weights, ((0, vp - v_pad), (0, 0)))
    xp = jnp.pad(x, ((0, 0), (0, fp - f)))
    wp = jnp.pad(w, ((0, fp - f), (0, 0)))
    out = _raw(occupied_width(wts), idx, wts, xp, wp,
               block_v=bv, block_f=bf, interpret=default_interpret())
    return out[:v_pad].astype(x.dtype)


_fused = jax.custom_vjp(_fused_kernel, nondiff_argnums=(4, 5))


def _fused_fwd(indices, weights, x, w, band_size, block_f):
    out = _fused_kernel(indices, weights, x, w, band_size, block_f)
    return out, (indices, weights, x, w)


def _fused_bwd(band_size, block_f, res, g):
    indices, weights, x, w = res
    _, vjp = jax.vjp(
        lambda a, xx, ww: fused_ref(indices, a, xx, ww), weights, x, w
    )
    return (None, *vjp(g))


_fused.defvjp(_fused_fwd, _fused_bwd)


@functools.partial(jax.jit, static_argnames=("band_size", "block_f"))
def fused_agg_cmb(indices, weights, x, w, band_size=128, block_f=None):
    return _fused(indices, weights, x, w, band_size, block_f)
