"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in Pallas interpret mode (kernels target TPU; this container is
CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.graphs import from_edges
from repro.kernels.fused_agg_cmb import fused_agg_cmb, fused_ref
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.gemm_dataflow import DATAFLOWS, gemm_ref
from repro.kernels.gemm_dataflow.ops import gemm
from repro.kernels.gat_agg import gat_agg, gat_agg_ref
from repro.kernels.common import default_interpret, lane_block_f, row_block
from repro.kernels.spmm import spmm, spmm_ref
from repro.kernels.spmm.kernel import occupied_width
import repro.kernels.fused_agg_cmb.ops as fused_ops
import repro.kernels.spmm.ops as spmm_ops

RNG = np.random.default_rng(42)


def rand(shape, dtype=np.float32, rng=RNG):
    return jnp.asarray(rng.normal(size=shape).astype(dtype))


class TestGemmDataflow:
    @pytest.mark.parametrize("dataflow", DATAFLOWS)
    @pytest.mark.parametrize(
        "v,f,g", [(128, 128, 128), (96, 80, 72), (33, 17, 5), (256, 64, 512)]
    )
    def test_matches_oracle(self, dataflow, v, f, g):
        x, w = rand((v, f)), rand((f, g))
        out = gemm(x, w, dataflow=dataflow, block_v=32, block_g=32, block_f=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gemm_ref(x, w)), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = rand((64, 64)).astype(dtype)
        w = rand((64, 64)).astype(dtype)
        out = gemm(x, w, dataflow="output_stationary", block_v=32, block_g=32, block_f=32)
        ref = gemm_ref(x, w)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(ref, np.float32),
            rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
            atol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
        )

    @settings(max_examples=15, deadline=None)
    @given(
        v=st.integers(1, 150),
        f=st.integers(1, 150),
        g=st.integers(1, 150),
        df=st.sampled_from(DATAFLOWS),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_property_shapes(self, v, f, g, df, seed):
        rng = np.random.default_rng(seed)
        x, w = rand((v, f), rng=rng), rand((f, g), rng=rng)
        out = gemm(x, w, dataflow=df, block_v=32, block_g=32, block_f=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gemm_ref(x, w)), rtol=2e-4, atol=2e-4
        )


def random_ell(v, max_deg, seed=0):
    rng = np.random.default_rng(seed)
    extra = rng.integers(0, v * max_deg // 2 + 1)
    g = from_edges(v, rng.integers(0, v, extra), rng.integers(0, v, extra))
    idx, wts, _ = g.to_ell()
    return jnp.asarray(idx), jnp.asarray(wts)


def skewed_ell(v, v_pad, hub, d, seed=0):
    """A power-law-like bucket: one hub row of ``hub`` slots, every other
    real row 1-3, rows ``v:v_pad`` empty (bucket padding), ELL width ``d``
    (``hub == d``: the hub sets the width; ``hub < d``: the bucket is
    wider than any row)."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(1, 4, size=v_pad)
    deg[v:], deg[v // 2] = 0, hub
    idx = np.zeros((v_pad, d), np.int32)
    wts = np.zeros((v_pad, d), np.float32)
    for r, k in enumerate(deg):
        idx[r, :k] = rng.integers(0, v, k)
        wts[r, :k] = rng.normal(size=k)
    return jnp.asarray(idx), jnp.asarray(wts)


def make_ell(v, deg, seed):
    """``deg`` an int: :func:`random_ell`; a ``(v_pad, hub, d)`` tuple:
    :func:`skewed_ell`."""
    if isinstance(deg, tuple):
        return skewed_ell(v, *deg, seed=seed)
    return random_ell(v, deg, seed=seed)


#: skewed buckets as ``deg`` cases of the oracle tests: the hub at the
#: full ELL width, and a bucket width past the max degree; both with pad
#: rows and two row blocks of 32 or more
SKEWED_CASES = [
    pytest.param((80, 40, 40), id="skewed-hub-full-width"),
    pytest.param((128, 12, 64), id="skewed-pad_to-wide"),
]


def full_width(weights):
    """Every row's slot count at the full ELL width: the padded walk the
    kernels made before they stopped at each row's occupied width."""
    return jnp.full(weights.shape[:1], weights.shape[1], jnp.int32)


def fixed_ell(v, d, seed=0):
    """ELL rows of exactly ``d`` random slots (D need not be a multiple
    of the 8-row sublane tile)."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, v, size=(v, d)).astype(np.int32)
    wts = rng.normal(size=(v, d)).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(wts)


#: (v, f, d, block_v, block_f): F not lane-aligned, block_f below the
#: 128-lane tile, D not a multiple of 8 — each legalized by the wrappers
TPU_BLOCK_CASES = [
    (40, 3703, 5, 16, 128),
    (40, 300, 3, 8, 8),
    (33, 16, 7, 128, 8),
    (70, 130, 13, 12, None),
]
TPU_BLOCK_IDS = ["f3703", "f300-block_f8", "f16-block_f8", "f130-d13"]


class TestOccupiedWalk:
    def test_one_past_the_last_nonzero(self):
        wts = jnp.asarray([[0.5, 0.0, 2.0, 0.0],  # interior zero walked
                           [0.0, 0.0, 0.0, 0.0],  # empty: no slot
                           [1.0, 1.0, 1.0, -1.0],  # full width
                           [0.0, np.nan, 0.0, 0.0]],  # NaN is not zero
                          jnp.float32)
        assert occupied_width(wts).tolist() == [3, 0, 4, 2]
        assert occupied_width(wts).dtype == jnp.int32

    @pytest.mark.parametrize("kernel", ["spmm", "fused_agg_cmb"])
    def test_padded_slots_are_not_read(self, kernel):
        """Padded slots point at row 0; with row 0 infinite and no real slot
        pointing at it, the padded walk reads inf * 0 = NaN, the occupied
        walk never reads it."""
        idx, wts = skewed_ell(40, 64, 12, 16, seed=5)
        idx = jnp.where(wts != 0, jnp.maximum(idx, 1), 0)
        x = rand((40, 24)).at[0].set(jnp.inf)
        if kernel == "spmm":
            out = spmm(idx, wts, x, block_v=16, block_f=128)
        else:
            out = fused_agg_cmb(idx, wts, x, rand((24, 8)), band_size=16)
        assert np.isfinite(np.asarray(out)).all()


class TestSpmm:
    @pytest.mark.parametrize(
        "v,f,deg",
        [(64, 32, 4), (200, 96, 8), (17, 5, 3),
         *(pytest.param(50, 40, c.values[0], id=c.id) for c in SKEWED_CASES)],
    )
    def test_matches_oracle(self, monkeypatch, v, f, deg):
        idx, wts = make_ell(v, deg, seed=v)
        x = rand((v, f))
        out = spmm(idx, wts, x, block_v=32, block_f=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(spmm_ref(idx, wts, x)), rtol=1e-4, atol=1e-5
        )
        monkeypatch.setattr(spmm_ops, "occupied_width", full_width)
        padded = jax.jit(spmm_ops._spmm_kernel, static_argnums=(3, 4))(
            idx, wts, x, 32, 32)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(padded))

    def test_matches_dense_spmm(self):
        g = from_edges(50, np.arange(49), np.arange(1, 50))
        idx, wts, _ = g.to_ell()
        x = rand((50, 24))
        dense = jnp.asarray(g.to_dense())
        out = spmm(jnp.asarray(idx), jnp.asarray(wts), x, block_v=16, block_f=8)
        np.testing.assert_allclose(
            np.asarray(out[:50]), np.asarray(dense @ x), rtol=1e-4, atol=1e-5
        )

    @settings(max_examples=15, deadline=None)
    @given(
        v=st.integers(2, 120),
        f=st.integers(1, 80),
        deg=st.integers(1, 10),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_property(self, v, f, deg, seed):
        idx, wts = random_ell(v, deg, seed=seed)
        rng = np.random.default_rng(seed)
        x = rand((v, f), rng=rng)
        out = spmm(idx, wts, x, block_v=32, block_f=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(spmm_ref(idx, wts, x)), rtol=1e-4, atol=1e-4
        )


    @pytest.mark.parametrize("v,f,d,block_v,block_f", TPU_BLOCK_CASES,
                             ids=TPU_BLOCK_IDS)
    def test_tpu_block_legalization(self, v, f, d, block_v, block_f):
        idx, wts = fixed_ell(v, d, seed=f)
        x = rand((v, f))
        out = spmm(idx, wts, x, block_v=block_v, block_f=block_f or 128)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(spmm_ref(idx, wts, x)), rtol=1e-4, atol=1e-4
        )


class TestFusedAggCmb:
    """The SP-Optimized kernel: fused == aggregate-then-GEMM."""

    @pytest.mark.parametrize(
        "v,f,g,deg",
        [(64, 32, 16, 4), (130, 48, 8, 6),
         *(pytest.param(50, 300, 8, c.values[0], id=c.id)
           for c in SKEWED_CASES)],
    )
    def test_matches_oracle(self, monkeypatch, v, f, g, deg):
        idx, wts = make_ell(v, deg, seed=v)
        x, w = rand((v, f)), rand((f, g))
        out = fused_agg_cmb(idx, wts, x, w, band_size=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(fused_ref(idx, wts, x, w)), rtol=1e-4, atol=1e-4
        )
        monkeypatch.setattr(fused_ops, "occupied_width", full_width)
        padded = jax.jit(fused_ops._fused_kernel, static_argnums=(4, 5))(
            idx, wts, x, w, 32, None)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(padded))

    def test_fused_equals_two_phase(self):
        v, f, g, deg = 96, 40, 12, 5
        idx, wts = random_ell(v, deg, seed=1)
        x, w = rand((v, f)), rand((f, g))
        fused = fused_agg_cmb(idx, wts, x, w, band_size=32)
        seq = spmm(idx, wts, x, block_v=32, block_f=32) @ w
        np.testing.assert_allclose(np.asarray(fused), np.asarray(seq), rtol=1e-4, atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(
        v=st.integers(4, 100),
        f=st.integers(1, 64),
        g=st.integers(1, 32),
        deg=st.integers(1, 8),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_property(self, v, f, g, deg, seed):
        idx, wts = random_ell(v, deg, seed=seed)
        rng = np.random.default_rng(seed)
        x, w = rand((v, f), rng=rng), rand((f, g), rng=rng)
        out = fused_agg_cmb(idx, wts, x, w, band_size=16)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(fused_ref(idx, wts, x, w)), rtol=2e-4, atol=2e-4
        )


    @pytest.mark.parametrize("v,f,d,block_v,block_f", TPU_BLOCK_CASES,
                             ids=TPU_BLOCK_IDS)
    def test_tpu_block_legalization(self, v, f, d, block_v, block_f):
        idx, wts = fixed_ell(v, d, seed=f)
        x, w = rand((v, f)), rand((f, 6))
        out = fused_agg_cmb(idx, wts, x, w, band_size=block_v, block_f=block_f)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(fused_ref(idx, wts, x, w)), rtol=2e-4, atol=2e-3
        )


class TestReverseMode:
    """Gradients through the Pallas aggregation kernels (their VJPs are
    the jnp oracles', since ``pallas_call`` has no transpose rule)."""

    @pytest.mark.parametrize("kernel", ["spmm", "fused_agg_cmb"])
    def test_grad_matches_oracle(self, kernel):
        idx, wts = random_ell(48, 5, seed=3)
        x, w = rand((48, 20)), rand((20, 7))
        if kernel == "spmm":
            fns = (lambda a, b, c: spmm(idx, a, b, block_v=16, block_f=8) @ c,
                   lambda a, b, c: spmm_ref(idx, a, b) @ c)
        else:
            fns = (lambda a, b, c: fused_agg_cmb(idx, a, b, c, band_size=16),
                   lambda a, b, c: fused_ref(idx, a, b, c))
        got, want = (
            jax.grad(lambda a, b, c: (fn(a, b, c) ** 2).sum(),
                     argnums=(0, 1, 2))(wts, x, w)
            for fn in fns
        )
        for gg, ww in zip(got, want):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(ww),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("policy", ["seq", "sp_opt"])
    def test_train_step_through_pallas(self, policy):
        import repro
        from repro.core import ModelSchedule, resolve_kernel_key
        from repro.gnn import GNNConfig
        from repro.gnn.model import make_node_classification_task

        rng = np.random.default_rng(7)
        graph = from_edges(60, rng.integers(0, 60, 200), rng.integers(0, 60, 200))
        cfg = GNNConfig(kind="gcn", f_in=20, hidden=8, n_classes=3)
        x, labels, mask = make_node_classification_task(graph, 20, 3)
        sched = ModelSchedule.from_policies(policy, "AC", list(cfg.dims))
        steps = []
        for use_pallas in (True, False):
            prog = repro.compile(cfg, graph=graph, schedule=sched,
                                 use_pallas=use_pallas)
            keys = {resolve_kernel_key(s.policy, s.order, s.use_pallas)[2]
                    for s in prog.specs}
            assert keys == {use_pallas}
            steps.append(prog.train_step(prog.init(jax.random.PRNGKey(0)),
                                         x, labels, mask))
        (loss_p, new_p), (loss_j, new_j) = steps
        np.testing.assert_allclose(float(loss_p), float(loss_j), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(new_p),
                        jax.tree_util.tree_leaves(new_j)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestTpuBlocks:
    """The block arithmetic the kernels rely on to compile for the TPU."""

    @pytest.mark.parametrize(
        "block_f,f,rows,want",
        [
            (None, 3703, 4096, 128),  # capped by the table buffer budget
            (8, 3703, 4096, 128),  # rounded up to whole lanes
            (256, 3703, 512, 256),
            (None, 16, 4096, 16),  # narrower than a lane: full dimension
            (32, 200, 64, 128),
            (None, 200, 64, 200),
        ],
    )
    def test_lane_block_f(self, block_f, f, rows, want):
        assert lane_block_f(block_f, f, rows) == want

    @pytest.mark.parametrize(
        "block_v,v,d,want",
        [(128, 4096, 64, 128), (128, 20, 8, 20), (7, 100, 8, 8),
         (12, 100, 8, 16), (128, 4096, 1000, 32)],
    )
    def test_row_block(self, block_v, v, d, want):
        assert row_block(block_v, v, d) == want

    @pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
    def test_default_interpret_by_backend(self, monkeypatch, backend, want):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert default_interpret() is want

    def test_default_interpret_refuses_other_backends(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            default_interpret()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize(
        "b,hq,hkv,sq,sk,d",
        [(2, 4, 2, 96, 96, 32), (1, 8, 1, 64, 128, 16), (2, 2, 2, 33, 33, 64)],
    )
    def test_matches_oracle(self, b, hq, hkv, sq, sk, d, causal):
        q = rand((b, hq, sq, d))
        k = rand((b, hkv, sk, d))
        v = rand((b, hkv, sk, d))
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        rep = hq // hkv
        kr = jnp.repeat(k, rep, axis=1).reshape(b * hq, sk, d)
        vr = jnp.repeat(v, rep, axis=1).reshape(b * hq, sk, d)
        ref = attention_ref(q.reshape(b * hq, sq, d), kr, vr, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out).reshape(b * hq, sq, d), np.asarray(ref), rtol=2e-4, atol=2e-5
        )

    def test_bf16(self):
        q = rand((1, 2, 64, 32)).astype(jnp.bfloat16)
        k = rand((1, 2, 64, 32)).astype(jnp.bfloat16)
        v = rand((1, 2, 64, 32)).astype(jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        ref = attention_ref(
            q.reshape(2, 64, 32), k.reshape(2, 64, 32), v.reshape(2, 64, 32), causal=True
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32).reshape(2, 64, 32),
            np.asarray(ref, np.float32),
            rtol=5e-2,
            atol=5e-2,
        )

    @settings(max_examples=10, deadline=None)
    @given(
        sq=st.integers(1, 120),
        sk=st.integers(1, 120),
        d=st.sampled_from([8, 16, 32]),
        causal=st.booleans(),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_property(self, sq, sk, d, causal, seed):
        rng = np.random.default_rng(seed)
        q = rand((1, 2, sq, d), rng=rng)
        k = rand((1, 2, sk, d), rng=rng)
        v = rand((1, 2, sk, d), rng=rng)
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        ref = attention_ref(
            q.reshape(2, sq, d), k.reshape(2, sk, d), v.reshape(2, sk, d), causal=causal
        )
        np.testing.assert_allclose(
            np.asarray(out).reshape(2, sq, d), np.asarray(ref), rtol=3e-4, atol=3e-5
        )


def gat_bucket(v, v_pad, d, seed=0):
    """A GAT bucket: a hub row at the full ELL width ``d``, every fourth
    real row with one slot, the others 1-3, rows ``v:v_pad`` empty."""
    idx, wts = skewed_ell(v, v_pad, d, d, seed=seed)
    one = jnp.arange(v_pad)[:, None] % 4 == 1
    wts = jnp.where(one & (jnp.arange(d)[None, :] > 0), 0.0, wts)
    return jnp.where(wts != 0, idx, 0), wts


def gat_inputs(v, heads, fh, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(rand(shape, rng=rng).astype(dtype)
                 for shape in ((v, heads * fh), (v, heads), (v, heads)))


class TestGatAgg:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("fh", [3, 8])
    @pytest.mark.parametrize("heads", [1, 8])
    def test_matches_oracle(self, heads, fh, dtype):
        """Both walk the same inputs (a bfloat16 table is held exactly in
        float32 by both), so only the softmax's summation order differs."""
        v, v_pad = 50, 80
        idx, wts = gat_bucket(v, v_pad, 24, seed=heads * fh)
        z, s, t = gat_inputs(v, heads, fh, dtype, seed=fh)
        out = gat_agg(idx, wts, z, s, t, block_v=16)
        ref = gat_agg_ref(idx, wts, z, s, t)
        assert out.shape == (v_pad, heads * fh) and out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        assert np.isfinite(np.asarray(out)).all()
        # pad rows have no slot: 0, not NaN
        np.testing.assert_array_equal(np.asarray(out[v:]), 0.0)

    def test_one_slot_rows_copy_their_neighbour(self):
        """A single slot has weight 1 whatever its score."""
        idx, wts = gat_bucket(50, 64, 16, seed=3)
        z, s, t = gat_inputs(50, 8, 8, jnp.float32, seed=4)
        out = np.asarray(gat_agg(idx, wts, z, s, t, block_v=16))
        ones = np.flatnonzero(np.asarray(occupied_width(wts))[:50] == 1)
        assert len(ones) > 5
        np.testing.assert_allclose(
            out[ones], np.asarray(z)[np.asarray(idx)[ones, 0]], rtol=1e-6)

    def test_hub_row_is_a_softmax(self):
        """The hub's output per head lies in the convex hull of its
        neighbours' rows: with z = 1 everywhere it is exactly 1."""
        idx, wts = gat_bucket(50, 64, 40, seed=5)
        _, s, t = gat_inputs(50, 8, 3, jnp.float32, seed=6)
        out = gat_agg(idx, wts, jnp.ones((50, 24)), 10 * s, 10 * t,
                      block_v=16)
        np.testing.assert_allclose(np.asarray(out[:50]), 1.0, rtol=1e-5)

    def test_occupied_walk_equals_padded_walk(self, monkeypatch):
        """Skipped slots are masked, so stopping at the occupied width
        gives the padded walk's result bit for bit."""
        import repro.kernels.gat_agg.ops as gat_ops

        idx, wts = gat_bucket(50, 80, 24, seed=7)
        z, s, t = gat_inputs(50, 8, 8, jnp.float32, seed=8)
        out = gat_agg(idx, wts, z, s, t, block_v=16)
        monkeypatch.setattr(gat_ops, "occupied_width", full_width)
        padded = jax.jit(gat_ops._gat_kernel, static_argnums=(5,))(
            idx, wts, z, s, t, 16)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(padded))

    def test_gradient_is_the_oracles(self):
        """The custom VJP is the jnp oracle's: the gradients agree to the
        forward passes' own difference."""
        idx, wts = gat_bucket(40, 64, 16, seed=9)
        z, s, t = gat_inputs(40, 8, 3, jnp.float32, seed=10)
        g = rand((64, 24), rng=np.random.default_rng(11))

        def loss(fn):
            return lambda zz, ss, tt: (fn(idx, wts, zz, ss, tt) * g).sum()

        got = jax.grad(loss(lambda *a: gat_agg(*a, block_v=16)),
                       argnums=(0, 1, 2))(z, s, t)
        want = jax.grad(loss(gat_agg_ref), argnums=(0, 1, 2))(z, s, t)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_table_over_vmem_is_refused(self):
        """The resident table must fit the kernel's VMEM limit."""
        import repro.kernels.gat_agg.ops as gat_ops

        v = gat_ops.MAX_SCOPED_VMEM // (4 * 128) + 8
        assert gat_ops.vmem_need(v, 64, 128) > gat_ops.MAX_SCOPED_VMEM
        idx = jax.ShapeDtypeStruct((v, 8), jnp.int32)
        f = jax.ShapeDtypeStruct((v, 64), jnp.float32)
        sc = jax.ShapeDtypeStruct((v, 8), jnp.float32)
        with pytest.raises(ValueError, match="resident in VMEM"):
            jax.eval_shape(lambda i, w, z, s, t: gat_agg(i, w, z, s, t),
                           idx, jax.ShapeDtypeStruct((v, 8), jnp.float32),
                           f, sc, sc)
