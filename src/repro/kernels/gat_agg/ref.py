"""Pure-jnp oracle for the GAT aggregation: per-head edge scores, a
softmax over each row's slots and the weighted sum of neighbour rows."""
import jax.numpy as jnp

#: LeakyReLU slope of the edge scores (Velickovic et al., 2018)
SLOPE = 0.2


def gat_agg_ref(indices, weights, z, s, t, slope=SLOPE):
    """out[v, h] = sum_d alpha[v, d, h] * z[idx[v, d], h] over the slots
    with a nonzero weight, alpha = softmax_d LeakyReLU(s[v, h] + t[idx[v, d], h]).

    ``indices`` / ``weights`` are the (V_pad, D) padded ELL (the weights
    act only as the edge mask), ``z`` (V, H*F') the combined features with
    the heads side by side, ``s`` / ``t`` (V, H) the self and neighbour
    scores.  Rows with no slot give 0.  Float32, (V_pad, H*F')."""
    v_pad, d = indices.shape
    v, heads = s.shape
    fh = z.shape[1] // heads
    zf, sf, tf = (a.astype(jnp.float32) for a in (z, s, t))
    sp = jnp.pad(sf, ((0, v_pad - v), (0, 0)))
    e = sp[:, None, :] + tf[indices]  # (V_pad, D, H)
    e = jnp.where(e > 0, e, slope * e)
    live = (weights != 0)[:, :, None]
    m = jnp.max(jnp.where(live, e, -jnp.inf), axis=1, keepdims=True)
    p = jnp.where(live, jnp.exp(e - jnp.where(live, m, 0.0)), 0.0)
    l = p.sum(axis=1)  # (V_pad, H)
    zg = zf[indices].reshape(v_pad, d, heads, fh)
    o = jnp.einsum("vdh,vdhf->vhf", p, zg)
    o = o / jnp.where(l > 0, l, 1.0)[..., None]
    return o.reshape(v_pad, heads * fh)
