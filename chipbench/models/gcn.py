"""Plain reference of a Kipf & Welling GCN: ``h <- relu(Â (h W) + b)`` per
layer, and for graph classification the mean of the last layer over the
graph's nodes.  Parameters are the program's: one ``{"w", "b"}`` per
layer."""
import functools

import jax
import jax.numpy as jnp

from chipbench.reference import blocked_outputs, round_to
from chipbench.work import gcn_layer_work

layer_work = gcn_layer_work


@functools.partial(jax.jit, static_argnames=("readout", "dtype"))
def forward(adj, x, mask, params, readout, dtype):
    """(B, P, P) Â, (B, P, F) x, (B, P) mask of real nodes."""
    h = round_to(x, dtype)
    adj = round_to(adj, dtype)
    for p in params:
        w, b = round_to(p["w"], dtype), round_to(p["b"], dtype)
        hw = round_to(jnp.einsum("bpf,fg->bpg", h, w), dtype)
        h = round_to(jax.nn.relu(jnp.einsum("bpq,bqg->bpg", adj, hw) + b),
                     dtype)
    if readout == "mean":
        m = mask[..., None]
        return round_to((h * m).sum(axis=1) / m.sum(axis=1), dtype)
    return h


def outputs(graphs, feats, params, *, readout, dtype=None):
    params = [{"w": jnp.asarray(p["w"], jnp.float32),
               "b": jnp.asarray(p["b"], jnp.float32)} for p in params]
    return blocked_outputs(forward, graphs, feats, params, readout=readout,
                           dtype=dtype)
