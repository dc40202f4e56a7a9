"""Tests for the `repro.compile()` front-end: Program execution parity
with the pre-redesign string-policy path, the save/load artifact
round-trip, kernel-registry dispatch, the unified objective registry, and
the deprecation shim."""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import (
    GNNLayerWorkload,
    objective_names,
    parse_dataflow,
    register_objective,
    search_dataflows,
    search_model,
    unregister_objective,
)
from repro.core.mapper import MappingResult
from repro.core.schedule import ExecSpec
from repro.core.simulator import BatchStats, ModelStats, RunStats
from repro.gnn import EllAdjacency, GNNConfig, gnn_forward, init_gnn
from repro.gnn import model as gnn_model
from repro.gnn.layers import LAYER_FNS, POLICIES, multiphase_matmul
from repro.graphs import load_dataset


@pytest.fixture(scope="module")
def graph():
    g, spec = load_dataset("mutag")
    return g, spec


@pytest.fixture(scope="module")
def workloads(graph):
    g, spec = graph
    return [
        GNNLayerWorkload(g.nnz, spec.n_features, 16, name="layer0"),
        GNNLayerWorkload(g.nnz, 16, 4, name="layer1"),
    ]


@pytest.fixture(scope="module")
def program(graph, workloads):
    g, _ = graph
    return repro.compile(workloads, graph=g, objective="cycles")


def _x(graph, f):
    g, _ = graph
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=(g.n_nodes, f)).astype(np.float32))


# ---------------------------------------------------------------------------
# compile() + Program basics
# ---------------------------------------------------------------------------


class TestCompile:
    def test_returns_bound_program_with_stats(self, program, workloads):
        assert isinstance(program, repro.Program)
        assert program.n_layers == 2
        assert program.stats is not None and program.stats.cycles > 0
        assert program.schedule.stats is program.stats
        assert program.dims == [(wl.f_in, wl.g_out) for wl in workloads]
        assert program.fingerprint["v"] == workloads[0].v

    def test_run_executes_searched_schedule(self, program, graph, workloads):
        params = program.init(jax.random.PRNGKey(0))
        out = program.run(params, _x(graph, workloads[0].f_in))
        assert out.shape == (graph[0].n_nodes, 4)
        assert np.isfinite(np.asarray(out)).all()

    def test_loss_is_finite_and_differentiable(self, program, graph, workloads):
        g, _ = graph
        params = program.init(jax.random.PRNGKey(1))
        x = _x(graph, workloads[0].f_in)
        rng = np.random.default_rng(3)
        labels = jnp.asarray(rng.integers(0, 4, g.n_nodes).astype(np.int32))
        mask = jnp.asarray((rng.random(g.n_nodes) < 0.3).astype(np.float32))
        loss, grads = jax.value_and_grad(
            lambda p: program.loss(p, x, labels, mask)
        )(params)
        assert np.isfinite(float(loss))
        for leaf in jax.tree_util.tree_leaves(grads):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_compile_from_gnn_config(self, graph):
        g, spec = graph
        cfg = GNNConfig(kind="sage", f_in=spec.n_features, hidden=8,
                        n_classes=4)
        prog = repro.compile(cfg, graph=g)
        assert prog.kind == "sage"
        assert prog.dims == cfg.dims
        out = prog.run(prog.init(jax.random.PRNGKey(0)),
                       _x(graph, spec.n_features))
        assert out.shape == (g.n_nodes, 4)

    def test_config_without_graph_rejected(self):
        with pytest.raises(ValueError, match="graph"):
            repro.compile(GNNConfig())

    def test_unbound_program_refuses_to_run(self, workloads):
        prog = repro.compile(workloads)
        with pytest.raises(ValueError, match="bind"):
            prog.run([], jnp.zeros((1, 1)))

    def test_explicit_schedule_skips_search_and_is_priced(
        self, graph, workloads
    ):
        g, _ = graph
        cfg = GNNConfig(f_in=workloads[0].f_in, hidden=16, n_classes=4,
                        policy="seq")
        sched = cfg.default_schedule()
        assert sched.stats is None
        prog = repro.compile(workloads, graph=g, schedule=sched)
        assert prog.stats is not None and prog.stats.cycles > 0

    def test_mismatched_schedule_shapes_rejected(self, graph, workloads):
        g, _ = graph
        bad = GNNConfig(f_in=7, hidden=5, n_classes=3).default_schedule()
        with pytest.raises(ValueError, match="shapes"):
            repro.compile(workloads, graph=g, schedule=bad)


# ---------------------------------------------------------------------------
# Numerics: Program.run == the pre-redesign string-policy gnn_forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", sorted(LAYER_FNS))
@pytest.mark.parametrize("order", ["AC", "CA"])
@pytest.mark.parametrize("policy", POLICIES)
def test_program_matches_string_policy_forward(graph, kind, order, policy):
    """The full policy x order x kind matrix: a Program built from the
    policy's default schedule reproduces the string-configured forward
    pass (itself pinned to the dense reference in test_layers_numerics).
    A gat layer has no AC and no pp path: compiling one raises."""
    g, spec = graph
    cfg = GNNConfig(kind=kind, f_in=spec.n_features, hidden=8, n_classes=4,
                    policy=policy, order=order, band_size=32)
    if kind == "gat" and (order == "AC" or policy == "pp"):
        with pytest.raises(ValueError, match="CA and not PP"):
            repro.compile(cfg, graph=g, schedule=cfg.default_schedule())
        return
    prog = repro.compile(cfg, graph=g, schedule=cfg.default_schedule())
    params = init_gnn(cfg, jax.random.PRNGKey(7))
    x = _x(graph, spec.n_features)
    ref = gnn_forward(cfg, params, prog.adj, x,
                      schedule=cfg.default_schedule())
    out = prog.run(params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5,
        err_msg=f"{kind}/{policy}/{order}",
    )


# ---------------------------------------------------------------------------
# Artifact round-trip
# ---------------------------------------------------------------------------


class TestArtifact:
    def test_save_load_round_trip(self, program, graph, tmp_path):
        g, _ = graph
        path = program.save(tmp_path / "model.program.json")
        loaded = repro.Program.load(path, graph=g)
        assert loaded.schedule == program.schedule
        assert loaded.hw == program.hw
        assert loaded.stats == program.stats  # predicted ModelStats intact
        assert loaded.fingerprint == program.fingerprint
        assert loaded.objective == program.objective

    def test_round_trip_is_byte_stable(self, program, tmp_path):
        first = program.save(tmp_path / "a.json").read_bytes()
        again = repro.Program.load(tmp_path / "a.json").save(
            tmp_path / "b.json"
        ).read_bytes()
        assert first == again

    def test_loaded_program_runs_identically(self, program, graph, workloads,
                                             tmp_path):
        g, _ = graph
        path = program.save(tmp_path / "p.json")
        loaded = repro.Program.load(path, graph=g)
        params = program.init(jax.random.PRNGKey(2))
        x = _x(graph, workloads[0].f_in)
        np.testing.assert_array_equal(
            np.asarray(program.run(params, x)),
            np.asarray(loaded.run(params, x)),
        )

    def test_fingerprint_mismatch_rejected(self, program, tmp_path):
        other, _ = load_dataset("cora")
        path = program.save(tmp_path / "p.json")
        with pytest.raises(ValueError, match="fingerprint"):
            repro.Program.load(path, graph=other)

    def test_not_a_program_artifact_rejected(self, tmp_path):
        p = tmp_path / "junk.json"
        p.write_text(json.dumps({"format": "something/else"}))
        with pytest.raises(ValueError, match="artifact"):
            repro.Program.load(p)


# ---------------------------------------------------------------------------
# Kernel registry dispatch + ExecSpec/kwargs conflicts
# ---------------------------------------------------------------------------


class TestDispatch:
    @pytest.fixture(scope="class")
    def operands(self, graph):
        g, spec = graph
        adj = EllAdjacency.from_csr(g)
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(g.n_nodes, spec.n_features))
                        .astype(np.float32))
        w = jnp.asarray(rng.normal(size=(spec.n_features, 8))
                        .astype(np.float32))
        return adj, x, w

    def test_conflicting_spec_kwargs_raise(self, operands):
        adj, x, w = operands
        spec = ExecSpec(policy="sp_opt", order="AC", band_size=64)
        for bad in (dict(policy="seq"), dict(order="CA"),
                    dict(band_size=128), dict(use_pallas=True)):
            with pytest.raises(ValueError, match="conflicting"):
                multiphase_matmul(adj, x, w, spec=spec, **bad)

    def test_matching_spec_kwargs_allowed(self, operands):
        adj, x, w = operands
        spec = ExecSpec(policy="sp_opt", order="AC", band_size=64)
        out = multiphase_matmul(adj, x, w, spec=spec, policy="sp_opt",
                                band_size=64)
        ref = multiphase_matmul(adj, x, w, spec=spec)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_unknown_policy_and_order_raise(self, operands):
        adj, x, w = operands
        with pytest.raises(ValueError, match="policy"):
            multiphase_matmul(adj, x, w, policy="bogus")
        with pytest.raises(ValueError, match="order"):
            multiphase_matmul(adj, x, w, policy="seq", order="ZZ")

    def test_no_policy_string_dispatch_left_in_layers(self):
        """The acceptance criterion: dispatch is registry-driven."""
        import inspect
        import repro.gnn.layers as layers

        src = inspect.getsource(layers.multiphase_matmul)
        assert "if policy ==" not in src and 'policy == "' not in src


# ---------------------------------------------------------------------------
# Objective registry: one consistent error everywhere, extensible
# ---------------------------------------------------------------------------


def _run_stats(cycles=2.0, energy=3.0):
    return RunStats(
        dataflow="x", cycles=cycles, energy_pj=energy, energy_breakdown={},
        gb_accesses={}, rf_accesses=0.0, buffering_elems=0.0, macs=0.0,
        pe_utilization=1.0, stall_factor=1.0, agg_cycles=1.0, cmb_cycles=1.0,
    )


class TestObjectives:
    def test_unknown_objective_error_is_consistent(self, workloads):
        df = parse_dataflow("Seq_AC(VsFtNt, VsGtFt)")
        mapping = MappingResult(df, _run_stats())
        batch = BatchStats(
            cycles=np.ones(2), energy_pj=np.ones(2),
            legal=np.ones(2, dtype=bool), agg_cycles=np.ones(2),
            cmb_cycles=np.ones(2), macs=np.ones(2),
        )
        model = ModelStats([_run_stats()], [])
        for fail in (
            lambda: mapping.objective("bogus"),
            lambda: batch.objective("bogus"),
            lambda: model.objective("bogus"),
            lambda: search_dataflows(workloads[0], objective="bogus"),
        ):
            with pytest.raises(ValueError, match="valid objectives") as e:
                fail()
            for name in ("cycles", "energy", "edp"):
                assert name in str(e.value)

    def test_model_search_rejects_non_additive(self, workloads):
        with pytest.raises(ValueError, match="additive"):
            search_model(workloads, objective="edp")

    def test_known_objectives_agree_with_closed_forms(self):
        model = ModelStats([_run_stats(cycles=2.0, energy=3.0)], [])
        assert model.objective("cycles") == 2.0
        assert model.objective("energy") == 3.0
        assert model.objective("edp") == 6.0

    def test_registered_objective_usable_everywhere(self):
        register_objective(
            "test_sum", lambda c, e: c + e, additive=True,
            description="test-only",
        )
        try:
            assert "test_sum" in objective_names(additive_only=True)
            model = ModelStats([_run_stats(cycles=2.0, energy=3.0)], [])
            assert model.objective("test_sum") == 5.0
            mapping = MappingResult(
                parse_dataflow("Seq_AC(VsFtNt, VsGtFt)"), _run_stats()
            )
            assert mapping.objective("test_sum") == 5.0
        finally:
            unregister_objective("test_sum")
        with pytest.raises(ValueError, match="valid objectives"):
            model.objective("test_sum")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_objective("cycles", lambda c, e: c)


# ---------------------------------------------------------------------------
# Deprecation shim
# ---------------------------------------------------------------------------


def test_string_policy_shim_warns_once(graph, monkeypatch):
    g, spec = graph
    monkeypatch.setattr(gnn_model, "_POLICY_SHIM_WARNED", False)
    cfg = GNNConfig(kind="gcn", f_in=spec.n_features, n_classes=4)
    adj = EllAdjacency.from_csr(g)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    x = _x(graph, spec.n_features)
    with pytest.warns(DeprecationWarning, match="repro.compile"):
        gnn_forward(cfg, params, adj, x)
    with warnings.catch_warnings():
        # a second shim warning would raise
        warnings.simplefilter("error", DeprecationWarning)
        gnn_forward(cfg, params, adj, x)


def test_schedule_path_does_not_warn(graph, monkeypatch):
    g, spec = graph
    monkeypatch.setattr(gnn_model, "_POLICY_SHIM_WARNED", False)
    cfg = GNNConfig(kind="gcn", f_in=spec.n_features, n_classes=4)
    adj = EllAdjacency.from_csr(g)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    x = _x(graph, spec.n_features)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        gnn_forward(cfg, params, adj, x, schedule=cfg.default_schedule())
