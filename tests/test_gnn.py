"""GNN substrate tests: policy equivalence, phase order, models, datasets,
and the device-level Parallel Pipeline (subprocess, 2 virtual devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.gnn import (
    EllAdjacency,
    GNNConfig,
    POLICIES,
    gnn_forward,
    gnn_loss,
    init_gnn,
    make_node_classification_task,
    multiphase_matmul,
)
from repro.graphs import TABLE4, from_edges, load_dataset


@pytest.fixture(scope="module")
def small_graph():
    g, spec = load_dataset("mutag")
    return g, spec


class TestPolicyEquivalence:
    """All inter-phase policies and both phase orders compute (A X) W."""

    def test_policies_match_dense_reference(self, small_graph):
        g, spec = small_graph
        adj = EllAdjacency.from_csr(g)
        x, _, _ = make_node_classification_task(g, spec.n_features, 4)
        w = jax.random.normal(jax.random.PRNGKey(0), (spec.n_features, 16)) * 0.1
        dense = jnp.asarray(g.to_dense())
        ref = (dense @ x) @ w
        for policy in ("seq", "sp_generic", "sp_opt"):
            for order in ("AC", "CA"):
                out = multiphase_matmul(adj, x, w, policy=policy, order=order)
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4,
                    err_msg=f"{policy}/{order}",
                )

    def test_band_size_does_not_change_result(self, small_graph):
        g, spec = small_graph
        adj = EllAdjacency.from_csr(g)
        x, _, _ = make_node_classification_task(g, spec.n_features, 4)
        w = jax.random.normal(jax.random.PRNGKey(0), (spec.n_features, 8)) * 0.1
        outs = [
            multiphase_matmul(adj, x, w, policy="sp_generic", band_size=b)
            for b in (32, 128, 1024)
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]), rtol=1e-5)

    def test_invalid_policy_raises(self, small_graph):
        g, spec = small_graph
        adj = EllAdjacency.from_csr(g)
        x = jnp.zeros((g.n_nodes, 4))
        w = jnp.zeros((4, 4))
        with pytest.raises(ValueError, match="policy"):
            multiphase_matmul(adj, x, w, policy="bogus")


class TestModels:
    @pytest.mark.parametrize("kind", ["gcn", "sage", "gin"])
    def test_forward_and_grads_finite(self, small_graph, kind):
        g, spec = small_graph
        adj = EllAdjacency.from_csr(g)
        x, labels, mask = make_node_classification_task(g, spec.n_features, 4)
        cfg = GNNConfig(kind=kind, f_in=spec.n_features, n_classes=4)
        params = init_gnn(cfg, jax.random.PRNGKey(1))
        logits = gnn_forward(cfg, params, adj, x)
        assert logits.shape == (g.n_nodes, 4)
        loss, grads = jax.value_and_grad(
            lambda p: gnn_loss(cfg, p, adj, x, labels, mask)
        )(params)
        assert np.isfinite(float(loss))
        for leaf in jax.tree_util.tree_leaves(grads):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_training_reduces_loss(self, small_graph):
        g, spec = small_graph
        adj = EllAdjacency.from_csr(g)
        x, labels, mask = make_node_classification_task(g, spec.n_features, 4)
        cfg = GNNConfig(kind="gcn", f_in=spec.n_features, n_classes=4)
        params = init_gnn(cfg, jax.random.PRNGKey(1))

        @jax.jit
        def step(p):
            l, g_ = jax.value_and_grad(
                lambda q: gnn_loss(cfg, q, adj, x, labels, mask)
            )(p)
            return l, jax.tree_util.tree_map(lambda a, b: a - 0.05 * b, p, g_)

        l0, params = step(params)
        for _ in range(30):
            l, params = step(params)
        assert float(l) < float(l0)


class TestDatasets:
    @pytest.mark.parametrize("name", list(TABLE4))
    def test_stats_near_table4(self, name):
        g, spec = load_dataset(name)
        g.validate()
        expect_v = spec.avg_nodes * spec.n_graphs
        assert 0.5 * expect_v <= g.n_nodes <= 2.0 * expect_v
        # self-loops add V edges on top of ~2x undirected listing
        raw_e = spec.avg_edges * spec.n_graphs
        assert g.n_edges >= raw_e * 0.5
        assert g.nnz.min() >= 1  # self loops guarantee no empty rows

    def test_hf_datasets_have_skewed_degrees(self):
        for name in ("reddit-bin", "citeseer", "cora"):
            g, _ = load_dataset(name)
            assert g.max_degree > 4 * g.avg_degree, name  # evil rows exist

    def test_deterministic_given_seed(self):
        a, _ = load_dataset("mutag", seed=7)
        b, _ = load_dataset("mutag", seed=7)
        assert np.array_equal(a.col_idx, b.col_idx)
        c, _ = load_dataset("mutag", seed=8)
        assert not np.array_equal(a.col_idx, c.col_idx)


@settings(max_examples=20, deadline=None)
@given(
    v=st.integers(4, 60),
    extra=st.integers(0, 120),
    f=st.integers(1, 32),
    gdim=st.integers(1, 16),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_policies_agree_on_random_graphs(v, extra, f, gdim, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, v, size=extra)
    dst = rng.integers(0, v, size=extra)
    g = from_edges(v, src, dst)
    adj = EllAdjacency.from_csr(g)
    x = jnp.asarray(rng.normal(size=(v, f)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(f, gdim)).astype(np.float32))
    ref = multiphase_matmul(adj, x, w, policy="seq", order="AC")
    for policy, order in [("sp_generic", "AC"), ("sp_opt", "AC"), ("seq", "CA")]:
        out = multiphase_matmul(adj, x, w, policy=policy, order=order, band_size=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3, atol=1e-4)


PP_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from repro.gnn import EllAdjacency, multiphase_matmul
    from repro.graphs import load_dataset

    g, spec = load_dataset("mutag")
    adj = EllAdjacency.from_csr(g)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(g.n_nodes, spec.n_features)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(spec.n_features, 16)).astype(np.float32))
    mesh = jax.make_mesh((2,), ("phase",))
    ref = multiphase_matmul(adj, x, w, policy="seq")
    out = multiphase_matmul(adj, x, w, policy="pp", mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3, atol=1e-4)
    print("PP-OK")
    """
)


def test_parallel_pipeline_two_device_groups():
    """The paper's PP dataflow as producer/consumer device groups with a
    collective_permute hand-off — run in a subprocess so the 2-device
    override does not pollute this process's jax."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", PP_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert "PP-OK" in r.stdout, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# GAT against a plain model-level reference
# ---------------------------------------------------------------------------

GAT_DIMS = [(50, 64), (64, 3)]  # 8 heads of 8 concatenated, 8 of 3 averaged


def gat_reference(n, src, dst, x, params):
    """Velickovic et al. (arXiv:1710.10903), eqs. 1-6, in plain jax.numpy
    at float32 "highest" matmul precision, one graph at a time:

        z = h W split into H heads of F';  e_ij = LeakyReLU_0.2(z_i a_self
        + z_j a_nbr) per head, j over N(i) and i;  alpha = softmax_j(e);
        hidden layer ELU(concat_h sum_j alpha_ij z_j + b), last layer
        mean_h sum_j alpha_ij z_j + b.

    Departures from the paper: the attention vector a = [a_self | a_nbr]
    is stored as its two halves; a bias is added after the aggregation (as
    the authors' code does); no dropout (inference); the neighbourhood is
    the nonzeros of A + I of the undirected edge list."""
    mask = np.zeros((n, n), bool)
    mask[src, dst] = True
    mask[np.arange(n), np.arange(n)] = True
    h = jnp.asarray(x, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for i, p in enumerate(params):
            heads, fh = p["a_self"].shape
            z = (h @ p["w"]).reshape(n, heads, fh)
            e = (jnp.einsum("vhf,hf->vh", z, p["a_self"])[:, None, :]
                 + jnp.einsum("vhf,hf->vh", z, p["a_nbr"])[None, :, :])
            e = jnp.where(e > 0, e, 0.2 * e)
            alpha = jax.nn.softmax(
                jnp.where(mask[:, :, None], e, -jnp.inf), axis=1)
            o = jnp.einsum("ijh,jhf->ihf", alpha, z)
            if i == len(params) - 1:
                h = o.mean(axis=1) + p["b"]
            else:
                h = jax.nn.elu(o.reshape(n, heads * fh) + p["b"])
    return np.asarray(h)


def citation_like(n, seed):
    """An undirected graph of ``n`` nodes: each node after the first links
    to one or two earlier ones, preferring the well linked."""
    rng = np.random.default_rng(seed)
    deg = np.ones(n)
    src, dst = [], []
    for i in range(1, n):
        k = min(i, 1 + int(rng.random() < 0.5))
        for t in rng.choice(i, size=k, replace=False, p=deg[:i] / deg[:i].sum()):
            src.append(i)
            dst.append(int(t))
            deg[[i, t]] += 1
    src, dst = np.array(src), np.array(dst)
    return n, np.concatenate([src, dst]), np.concatenate([dst, src])


@pytest.fixture(scope="module")
def gat_requests():
    """A ~300-node citation graph and four of 60-70 nodes (one bucket, so
    they batch block-diagonally), each with 50 seeded features."""
    rng = np.random.default_rng(5)
    graphs = [citation_like(300, 1)] + [citation_like(n, 10 + n)
                                         for n in (60, 64, 66, 70)]
    xs = [rng.normal(size=(g[0], 50)).astype(np.float32) for g in graphs]
    return graphs, xs


# the jnp path runs the reference's float32 arithmetic in another order
# (an online softmax, default matmul precision on the CPU): 1e-5 relative
GAT_RTOL, GAT_ATOL = 1e-5, 1e-5


class TestGat:
    def test_program_matches_reference(self, gat_requests):
        import repro
        from repro.api import layer_workloads
        from repro.graphs import block_diagonal

        graphs, xs = gat_requests
        csrs = [from_edges(*g) for g in graphs]
        batched = block_diagonal(csrs)
        prog = repro.compile(
            layer_workloads(batched.nnz, GAT_DIMS, kind="gat", heads=8),
            graph=batched, kind="gat")
        assert prog.heads == 8
        assert all(s.order == "CA" and s.policy != "pp" for s in prog.specs)
        params = prog.init(jax.random.PRNGKey(0))
        assert [p["a_self"].shape for p in params] == [(8, 8), (8, 3)]
        out = np.asarray(prog.run(params, jnp.asarray(np.concatenate(xs))))
        off = 0
        for g, x in zip(graphs, xs):
            ref = gat_reference(*g, x, params)
            np.testing.assert_allclose(out[off:off + g[0]], ref,
                                       rtol=GAT_RTOL, atol=GAT_ATOL)
            off += g[0]

    def test_softmax_does_not_leak_across_graphs(self, gat_requests):
        """Changing one member graph's features leaves the others' rows of
        a block-diagonal batch bit for bit as they were."""
        import repro
        from repro.api import layer_workloads
        from repro.graphs import block_diagonal

        graphs, xs = gat_requests
        batched = block_diagonal([from_edges(*g) for g in graphs[1:]])
        prog = repro.compile(
            layer_workloads(batched.nnz, GAT_DIMS, kind="gat"),
            graph=batched, kind="gat")
        params = prog.init(jax.random.PRNGKey(1))
        x = np.concatenate(xs[1:])
        a = np.asarray(prog.run(params, jnp.asarray(x)))
        x[:graphs[1][0]] *= 100.0
        b = np.asarray(prog.run(params, jnp.asarray(x)))
        n0 = graphs[1][0]
        assert not np.allclose(a[:n0], b[:n0])
        np.testing.assert_array_equal(a[n0:], b[n0:])

    @pytest.mark.parametrize("front", ["sync", "async"])
    def test_engines_serve_a_block_diagonal_batch(self, gat_requests, front):
        from repro.runtime import AsyncEngine, InferenceEngine, Request

        graphs, xs = gat_requests
        reqs = [Request(graph=from_edges(*g), x=x, rid=i)
                for i, (g, x) in enumerate(zip(graphs, xs))]
        if front == "sync":
            eng = InferenceEngine(GAT_DIMS, kind="gat", readout=None)
            params = eng.init(jax.random.PRNGKey(2))
            results = eng.submit(reqs)
            st = eng.stats()
        else:
            params = InferenceEngine(GAT_DIMS, kind="gat").init(
                jax.random.PRNGKey(2))
            with AsyncEngine(GAT_DIMS, params, kind="gat", readout=None,
                             window_ms=500.0) as eng:
                futs = [eng.submit_async(r) for r in reqs]
                results = [f.result(timeout=120) for f in futs]
            st = eng.stats()
        assert [r.status for r in results] == ["ok"] * len(reqs)
        n_batches = (sum(d["n_batches"] for d in st.per_device.values())
                     if front == "async" else st.n_batches)
        assert n_batches < len(reqs)  # the small graphs share a micro-batch
        for r, g, x in zip(results, graphs, xs):
            np.testing.assert_allclose(r.output, gat_reference(*g, x, params),
                                       rtol=GAT_RTOL, atol=GAT_ATOL)

    def test_attn_edge_heads_counts_every_layers_edges(self, gat_requests):
        """attn_edge_heads = sum over layers of nnz(A + I) x heads."""
        from repro.runtime import InferenceEngine, Request

        graphs, xs = gat_requests
        eng = InferenceEngine(GAT_DIMS, kind="gat", heads=4, readout=None)
        eng.init(jax.random.PRNGKey(3))
        csrs = [from_edges(*g) for g in graphs]
        eng.submit([Request(graph=c, x=x, rid=i)
                    for i, (c, x) in enumerate(zip(csrs, xs))])
        nnz = sum(c.n_edges for c in csrs)
        assert eng.stats().attn_edge_heads == nnz * 4 * len(GAT_DIMS)
        plain = InferenceEngine(GAT_DIMS, kind="gcn", readout=None)
        plain.init(jax.random.PRNGKey(3))
        plain.submit([Request(graph=csrs[0], x=xs[0], rid=0)])
        assert plain.stats().attn_edge_heads == 0
