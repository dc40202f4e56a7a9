"""Numerics matrix for `repro.gnn.layers`: every policy x order x kind
combination must match a dense reference built from `aggregate_full` on a
random CSR graph, including when ``v_pad % band_size != 0``.  A gat layer
runs CA only and has no pp path: the other combinations raise."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gnn import EllAdjacency, POLICIES, init_layer, multiphase_matmul
from repro.gnn.layers import LAYER_FNS, aggregate_full
from repro.graphs import from_edges

V = 157  # prime: v_pad % band_size != 0 for every power-of-two band
F_IN, F_OUT = 20, 12
BAND = 32  # 157 % 32 != 0
HEADS = 4  # a gat layer's 12 outputs are 4 heads of 3


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(11)
    return from_edges(V, rng.integers(0, V, 600), rng.integers(0, V, 600))


@pytest.fixture(scope="module")
def adj(graph):
    return EllAdjacency.from_csr(graph)


@pytest.fixture(scope="module")
def x(graph):
    rng = np.random.default_rng(12)
    return jnp.asarray(rng.normal(size=(V, F_IN)).astype(np.float32))


def dense_layer_reference(kind, params, adj, x):
    """The layer math with the aggregation done by dense `aggregate_full`."""
    agg = aggregate_full(adj, x)[: adj.n_nodes]
    xs = x[: adj.n_nodes]
    if kind == "gcn":
        return jax.nn.relu(agg @ params["w"] + params["b"])
    if kind == "sage":
        return jax.nn.relu(
            xs @ params["w_top"] + agg @ params["w_bottom"] + params["b"]
        )
    if kind == "gin":
        unit = EllAdjacency(
            adj.indices, (adj.weights > 0).astype(x.dtype), adj.n_nodes
        )
        s = aggregate_full(unit, x)[: adj.n_nodes]
        h = jax.nn.relu(
            s @ params["w1"]
            + (1.0 + params["eps"]) * xs @ params["w1"]
            + params["b1"]
        )
        return jax.nn.relu(h @ params["w2"] + params["b2"])
    if kind == "gat":
        # dense masked softmax over A + I (the nonzero ELL slots)
        heads, fh = params["a_self"].shape
        z = (xs @ params["w"]).reshape(-1, heads, fh)
        s = jnp.einsum("vhf,hf->vh", z, params["a_self"])
        t = jnp.einsum("vhf,hf->vh", z, params["a_nbr"])
        mask = np.zeros((adj.n_nodes, adj.n_nodes), bool)
        idx, w = np.asarray(adj.indices), np.asarray(adj.weights)
        rows = np.repeat(np.arange(idx.shape[0]), idx.shape[1])
        live = w.ravel() != 0
        mask[rows[live], idx.ravel()[live]] = True
        e = s[:, None, :] + t[None, :, :]
        e = jnp.where(e > 0, e, 0.2 * e)
        e = jnp.where(mask[:, :, None], e, -jnp.inf)
        a = jax.nn.softmax(e, axis=1)
        o = jnp.einsum("ijh,jhf->ihf", a, z).reshape(adj.n_nodes, -1)
        return jax.nn.elu(o + params["b"])
    raise KeyError(kind)


@pytest.mark.parametrize("kind", sorted(LAYER_FNS))
@pytest.mark.parametrize("order", ["AC", "CA"])
@pytest.mark.parametrize("policy", POLICIES)
def test_policy_order_kind_matrix(kind, order, policy, adj, x):
    """`pp` with mesh=None exercises its documented sp_generic fallback."""
    params = init_layer(kind, jax.random.PRNGKey(42), F_IN, F_OUT, heads=HEADS)
    if kind == "gat" and (order == "AC" or policy == "pp"):
        with pytest.raises(ValueError, match="CA"):
            LAYER_FNS[kind](params, adj, x, policy=policy, order=order,
                            band_size=BAND)
        return
    ref = dense_layer_reference(kind, params, adj, x)
    out = LAYER_FNS[kind](
        params, adj, x, policy=policy, order=order, band_size=BAND
    )
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref),
        rtol=2e-4,
        atol=2e-4,
        err_msg=f"{kind}/{policy}/{order}",
    )


@pytest.mark.parametrize("order", ["AC", "CA"])
@pytest.mark.parametrize("policy", ["seq", "sp_opt"])
def test_pallas_lowering_matches(policy, order, adj, x):
    """The Pallas-backed paths (spmm for seq, fused agg+cmb for sp_opt) with
    schedule-style block shapes agree with the jnp reference."""
    rng = np.random.default_rng(13)
    w = jnp.asarray(rng.normal(size=(F_IN, F_OUT)).astype(np.float32))
    ref = multiphase_matmul(adj, x, w, policy="seq", order="AC")
    out = multiphase_matmul(
        adj, x, w, policy=policy, order=order,
        band_size=BAND, block_f=8, use_pallas=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_ragged_band_sizes_agree(adj, x):
    rng = np.random.default_rng(14)
    w = jnp.asarray(rng.normal(size=(F_IN, F_OUT)).astype(np.float32))
    ref = multiphase_matmul(adj, x, w, policy="seq", order="AC")
    for band in (7, 13, 32, 100, 1024):  # none divide v_pad evenly
        out = multiphase_matmul(
            adj, x, w, policy="sp_generic", order="AC", band_size=band
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4,
            err_msg=f"band={band}",
        )
