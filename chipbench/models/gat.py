"""Plain reference of a GAT (Velickovic et al., arXiv:1710.10903) and the
work of one of its layers.

Per layer and head h, with z = h_in W split into heads of F' columns and
j over N(i) and i itself (the nonzeros of A + I):

    e_ij = LeakyReLU_0.2(z_i^h a_self^h + z_j^h a_nbr^h)
    alpha_ij = softmax_j(e_ij),  o_i^h = sum_j alpha_ij z_j^h

then ELU(concat_h o_i^h + b) for a hidden layer and mean_h o_i^h + b (the
logits) for the last.  Parameters are the program's, one ``{"w",
"a_self", "a_nbr", "b"}`` per layer; H and F' are read from the shape of
``a_self``.  The edge mask is rebuilt from the dense Â of
:func:`chipbench.reference.normalized_adjacency` (Â > 0 exactly on
A + I), and the softmax is computed in blocks of target rows: a dense
(V, V, H) score tensor of a whole citation graph does not fit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _pad_size, normalized_adjacency, round_to
from chipbench.work import Work

#: LeakyReLU slope of the scores
SLOPE = 0.2
#: target rows per block of the softmax
BLOCK_ROWS = 512
#: operations per (edge, head): the score's add and LeakyReLU, the
#: softmax's max subtraction, exp and sum, and the normalisation
OPS_PER_EDGE_HEAD = 6


def attention_work(n_nodes: int, nnz: int, width: int, heads: int) -> Work:
    """The aggregation of one GAT layer, what its attention kernel does:
    per (edge, head) :data:`OPS_PER_EDGE_HEAD` operations and the weighted
    sum (2·nnz·H·F' = 2·nnz·width); bytes = 4·V·(width + 2H) (z and both
    scores read once) + 4·V·width (the output) + 8·nnz (one index and one
    mask weight per nonzero)."""
    v, e = int(n_nodes), int(nnz)
    ops = float(OPS_PER_EDGE_HEAD) * e * heads + 2.0 * e * width
    nbytes = 4.0 * v * (width + 2 * heads) + 4.0 * v * width + 8.0 * e
    return Work(ops, nbytes)


def layer_work(n_nodes: int, nnz: int, f_in: int, f_out: int, heads: int,
               concat: bool) -> Work:
    """One GAT layer on a graph of ``n_nodes`` nodes whose A + I has
    ``nnz`` nonzeros, from logical shapes (no padding).  ``width`` = H·F'
    is ``f_out`` for a layer that concatenates its heads, H·f_out for one
    that averages them.

    ops = 2·V·f_in·width (the GEMM) + 4·V·width (the two score
    projections, 4·V·H·F') + :func:`attention_work` + V·width when the
    heads are averaged; bytes = 4·(V·f_in + f_in·width + 2·width + V·f_out)
    (features in, W, a_self and a_nbr, output) + 8·nnz."""
    v, e = int(n_nodes), int(nnz)
    width = f_out if concat else heads * f_out
    ops = (2.0 * v * f_in * width + 4.0 * v * width
           + attention_work(v, e, width, heads).ops
           + (0.0 if concat else float(v * width)))
    nbytes = 4.0 * (v * f_in + f_in * width + 2 * width + v * f_out) + 8.0 * e
    return Work(ops, nbytes)


@functools.partial(jax.jit, static_argnames=("last", "dtype", "rows"))
def _layer(mask, h, p, last, dtype, rows):
    """One layer over a (P, P) edge mask and (P, F) input, P a multiple
    of ``rows``."""
    heads, fh = p["a_self"].shape
    z = round_to(h @ round_to(p["w"], dtype), dtype)
    zh = z.reshape(z.shape[0], heads, fh)
    s = round_to(jnp.einsum("vhf,hf->vh", zh, round_to(p["a_self"], dtype)),
                 dtype)
    t = round_to(jnp.einsum("vhf,hf->vh", zh, round_to(p["a_nbr"], dtype)),
                 dtype)

    def block(args):
        m, sb = args  # (rows, P) mask, (rows, H) self scores
        e = sb[:, None, :] + t[None, :, :]
        e = jnp.where(e > 0, e, SLOPE * e)
        e = jnp.where(m[:, :, None], e, -jnp.inf)
        mx = jnp.max(e, axis=1, keepdims=True)
        mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
        w = jnp.exp(e - mx)  # 0 off the mask
        l = w.sum(axis=1)
        o = jnp.einsum("rnh,nhf->rhf", w, zh)
        return o / jnp.where(l > 0, l, 1.0)[..., None]

    n = mask.shape[0]
    o = jax.lax.map(block, (mask.reshape(n // rows, rows, n),
                            s.reshape(n // rows, rows, heads)))
    o = o.reshape(n, heads, fh)
    b = round_to(p["b"], dtype)
    if last:
        return round_to(o.mean(axis=1) + b, dtype)
    return round_to(jax.nn.elu(o.reshape(n, heads * fh) + b), dtype)


def forward(mask, x, params, readout, dtype, rows=BLOCK_ROWS):
    """(P, P) bool edge mask, (P, F) x; node outputs, or their mean over
    the rows of the mask that have an edge (real nodes) for a readout."""
    h = round_to(x, dtype)
    for i, p in enumerate(params):
        h = _layer(mask, h, p, i == len(params) - 1, dtype, rows)
    if readout == "mean":
        real = mask.any(axis=1)[:, None]
        return round_to((h * real).sum(axis=0) / real.sum(), dtype)
    return h


def outputs(graphs, feats, params, *, readout, dtype=None):
    """Node outputs ``(n, f_out)`` for ``readout=None``, one ``(f_out,)``
    row per graph for ``"mean"``.  One graph at a time; graphs that are
    the same object share their mask."""
    params = [{k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
              for p in params]
    masks: dict[int, tuple] = {}
    out = []
    with jax.default_matmul_precision("highest"):
        for g, x in zip(graphs, feats):
            if id(g) not in masks:
                # small graphs padded to few sizes, large ones to whole blocks
                p_n = _pad_size(g.n)
                rows = min(BLOCK_ROWS, p_n)
                p_n = -(-g.n // rows) * rows
                masks[id(g)] = (jnp.asarray(
                    normalized_adjacency(g, p_n) > 0), p_n, rows)
            mask, p_n, rows = masks[id(g)]
            xp = np.zeros((p_n, x.shape[1]), np.float32)
            xp[:g.n] = x
            res = np.asarray(forward(mask, jnp.asarray(xp), params, readout,
                                     dtype, rows))
            out.append(res if readout else res[:g.n])
    return out
