"""Persistent program-store tests: atomic artifact saves survive injected
failures, corrupt artifacts degrade to counted misses (never exceptions),
traffic profiles round-trip, and a revived engine — fresh process state,
same store — serves bit-identical outputs with zero mapper searches and,
after precompile(), zero new XLA traces on its first request."""
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import GNNLayerWorkload
from repro.core.schedule import ModelSchedule
from repro.graphs import BucketPolicy, TrafficProfile, from_edges
from repro.runtime import (
    ProgramStore,
    enable_persistent_compilation_cache,
    key_digest,
    store_key,
)
from repro.runtime.engine import InferenceEngine, Request

DIMS = [(12, 16), (16, 4)]
SCHEDULE = ModelSchedule.from_policies("sp_opt", "AC", DIMS)
POLICY = BucketPolicy(min_nodes=16, min_degree=4, max_graphs=4)


def ring_graph(n: int, seed: int = 0):
    src = np.arange(n)
    dst = (src + 1) % n
    return from_edges(n, np.concatenate([src, dst]), np.concatenate([dst, src]))


def make_request(n: int, seed: int, rid: int = 0) -> Request:
    g = ring_graph(n, seed=seed)
    x = np.random.default_rng(seed).normal(size=(n, DIMS[0][0])).astype(np.float32)
    return Request(graph=g, x=x, rid=rid)


def compiled(graph, schedule=SCHEDULE):
    wls = [GNNLayerWorkload(graph.nnz, fi, fo) for fi, fo in DIMS]
    return repro.compile(wls, graph=graph, schedule=schedule)


@pytest.fixture(scope="module")
def prog():
    return compiled(ring_graph(16))


@pytest.fixture(scope="module")
def params(prog):
    return prog.init(jax.random.PRNGKey(0))


def a_key(bucket=(16, 4), v_total=16, **kw):
    kw.setdefault("kind", "gcn")
    kw.setdefault("objective", "cycles")
    kw.setdefault("use_pallas", False)
    return store_key(DIMS, bucket, v_total, **kw)


class TestAtomicSave:
    def test_injected_failure_leaves_previous_artifact_intact(
        self, tmp_path, prog, monkeypatch
    ):
        target = tmp_path / "prog.json"
        prog.save(target)
        before = target.read_text()

        def boom(src, dst):
            raise OSError("injected: disk full")

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError, match="injected"):
            prog.save(target)
        monkeypatch.undo()
        # the reader's view: previous complete artifact, no temp strays
        assert target.read_text() == before
        assert not list(tmp_path.glob("*.tmp.*"))

    def test_injected_failure_on_first_write_leaves_nothing(
        self, tmp_path, prog, monkeypatch
    ):
        target = tmp_path / "fresh.json"

        def boom(src, dst):
            raise OSError("injected")

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError):
            prog.save(target)
        monkeypatch.undo()
        assert not target.exists()
        assert not list(tmp_path.glob("*.tmp.*"))

    def test_save_bytes_stable_across_round_trip(self, tmp_path, prog):
        p1 = tmp_path / "a.json"
        p2 = tmp_path / "b.json"
        prog.save(p1)
        type(prog).from_json(p1.read_text()).save(p2)
        assert p1.read_text() == p2.read_text()


class TestProgramStore:
    def test_round_trip_serves_bit_identical(self, tmp_path, prog, params):
        store = ProgramStore(tmp_path)
        key = a_key()
        store.put(key, prog)
        # a fresh store (new process, same directory) must hit
        revived = ProgramStore(tmp_path)
        loaded = revived.get(key)
        assert loaded is not None and revived.hits == 1
        g = ring_graph(16)
        x = jnp.ones((16, DIMS[0][0]), jnp.float32)
        want = np.asarray(prog.run(params, x))
        got = np.asarray(
            loaded.bind(g, pad_degree=g.max_degree).run(params, x)
        )
        assert np.array_equal(want, got)

    def test_absent_key_is_plain_miss(self, tmp_path):
        store = ProgramStore(tmp_path)
        assert store.get(a_key(bucket=(32, 4), v_total=32)) is None
        assert store.misses == 1 and store.corrupt == 0

    @pytest.mark.parametrize("mangle", ["garbage", "truncated", "format"])
    def test_bad_artifact_is_counted_miss_never_raises(
        self, tmp_path, prog, mangle
    ):
        store = ProgramStore(tmp_path)
        key = a_key()
        path = store.put(key, prog)
        text = path.read_text()
        if mangle == "garbage":
            path.write_text("{ not json at all")
        elif mangle == "truncated":
            path.write_text(text[: len(text) // 2])
        else:  # a PROGRAM_FORMAT bump invalidates old stores gracefully
            d = json.loads(text)
            d["format"] = "repro.program/v0"
            path.write_text(json.dumps(d))
        assert store.get(key) is None
        assert store.corrupt == 1 and store.misses == 1
        # put repairs the entry and get recovers
        store.put(key, prog)
        assert store.get(key) is not None

    def test_corrupt_index_is_cosmetic(self, tmp_path, prog):
        store = ProgramStore(tmp_path)
        k1, k2 = a_key(), a_key(bucket=(16, 4), v_total=32)
        store.put(k1, prog)
        store.put(k2, prog)
        (tmp_path / "index.json").write_text("not an index {{{")
        # paths derive from key digests, so artifacts still resolve
        revived = ProgramStore(tmp_path)
        assert len(revived) == 2
        assert revived.get(k1) is not None and revived.get(k2) is not None
        # the next put rewrites a valid index
        revived.put(k1, prog)
        d = json.loads((tmp_path / "index.json").read_text())
        assert d["format"] == "repro.store/v1"

    def test_key_digest_is_order_insensitive_and_distinct(self):
        k = a_key()
        assert key_digest(k) == key_digest(dict(reversed(list(k.items()))))
        assert key_digest(k) != key_digest(a_key(use_pallas=True))
        assert key_digest(k) != key_digest(a_key(v_total=32))


class TestTrafficProfile:
    def test_record_merge_and_heat_order(self):
        p = TrafficProfile()
        p.record_request((16, 4), n=10)
        p.record_request((32, 4), n=2)
        p.record_batch((16, 4), slots=4)
        p.record_batch((16, 4), slots=1)
        p.record_batch((32, 4), slots=2)
        assert p.n_requests == 12
        shapes = p.hot_shapes()
        # the hotter bucket's shapes come first, then the cold bucket's
        assert [b for b, _ in shapes] == [(16, 4), (16, 4), (32, 4)]
        q = TrafficProfile()
        q.record_request((16, 4), n=5)
        q.record_batch((16, 4), slots=4)
        merged = p.merge(q)
        assert merged.n_requests == 17
        assert merged.batches[(16, 4, 4)] == 2

    def test_save_load_round_trip(self, tmp_path):
        p = TrafficProfile()
        p.record_request((16, 4), n=3)
        p.record_batch((16, 4), slots=2)
        path = p.save(tmp_path / "traffic.json")
        q = TrafficProfile.load(path)
        assert q.requests == p.requests and q.batches == p.batches

    def test_store_tolerates_garbage_profile(self, tmp_path):
        store = ProgramStore(tmp_path)
        store.profile_path.write_text("}} nope")
        assert store.load_profile() is None
        assert store.corrupt == 1
        assert ProgramStore(tmp_path).load_profile() is None  # still no raise


class TestRestartParity:
    @pytest.mark.parametrize("kind", ["gcn", "sage"])
    def test_revived_engine_is_bit_identical_and_search_free(
        self, tmp_path, kind
    ):
        reqs = [make_request(12, seed=i, rid=i) for i in range(4)]
        cold = InferenceEngine(
            DIMS, kind=kind, policy=POLICY, readout="mean",
            store=ProgramStore(tmp_path),
        )
        params = cold.init(jax.random.PRNGKey(0))
        got_cold = cold.submit(reqs)
        assert cold.stats().n_searches >= 1  # the search actually ran once
        revived = InferenceEngine(
            DIMS, params, kind=kind, policy=POLICY, readout="mean",
            store=ProgramStore(tmp_path),
        )
        got = revived.submit(reqs)
        stats = revived.stats()
        assert stats.n_searches == 0, "a warm store must preempt the mapper"
        assert stats.store_hits >= 1
        for a, b in zip(got_cold, got):
            assert a.ok and b.ok
            assert np.array_equal(a.output, b.output)

    def test_pallas_tier_round_trips_through_store(self, tmp_path):
        reqs = [make_request(12, seed=i, rid=i) for i in range(2)]
        cold = InferenceEngine(
            DIMS, use_pallas=True, policy=POLICY, readout="mean",
            store=ProgramStore(tmp_path),
        )
        params = cold.init(jax.random.PRNGKey(0))
        got_cold = cold.submit(reqs)
        revived = InferenceEngine(
            DIMS, params, use_pallas=True, policy=POLICY, readout="mean",
            store=ProgramStore(tmp_path),
        )
        got = revived.submit(reqs)
        assert revived.stats().n_searches == 0
        for a, b in zip(got_cold, got):
            assert a.ok and b.ok
            assert np.array_equal(a.output, b.output)

    def test_degraded_twin_of_loaded_program_is_bit_identical(
        self, tmp_path, prog, params
    ):
        store = ProgramStore(tmp_path)
        key = a_key(use_pallas=True)
        store.put(key, prog)
        loaded = ProgramStore(tmp_path).get(key)
        g = ring_graph(16)
        x = jnp.ones((16, DIMS[0][0]), jnp.float32)
        want = np.asarray(prog.degraded(use_pallas=False).run(params, x))
        twin = loaded.bind(g, pad_degree=g.max_degree).degraded(
            use_pallas=False
        )
        assert np.array_equal(want, np.asarray(twin.run(params, x)))


class TestPrecompile:
    def test_first_request_after_precompile_is_trace_free(self, tmp_path):
        reqs = [make_request(12, seed=i, rid=i) for i in range(5)]
        cold = InferenceEngine(
            DIMS, policy=POLICY, readout="mean",
            store=ProgramStore(tmp_path),
        )
        params = cold.init(jax.random.PRNGKey(0))
        # solo first arrival + bulk: the traffic profile records both the
        # slots=1 and the packed micro-batch shapes
        cold.submit(reqs[:1])
        cold.submit(reqs[1:])
        revived = InferenceEngine(
            DIMS, params, policy=POLICY, readout="mean",
            store=ProgramStore(tmp_path),
        )
        rep = revived.precompile()
        assert rep.n_shapes >= 2
        assert rep.n_store_hits == rep.n_shapes
        assert rep.n_searches == 0 and rep.n_compiled == 0
        assert rep.n_traces >= 1  # the traces happened here, at startup...
        before = repro.trace_count()
        got = revived.submit(reqs[:1])
        assert repro.trace_count() == before  # ...not on the request path
        assert revived.stats().n_searches == 0
        assert got[0].ok

    def test_precompile_without_params_rejected(self, tmp_path):
        engine = InferenceEngine(DIMS, store=ProgramStore(tmp_path))
        with pytest.raises(ValueError, match="params"):
            engine.precompile()

    def test_precompile_max_shapes_bounds_startup_work(self, tmp_path):
        profile = TrafficProfile()
        profile.record_request((16, 4), n=9)
        profile.record_batch((16, 4), slots=1)
        profile.record_batch((16, 4), slots=2)
        engine = InferenceEngine(DIMS, policy=POLICY, readout="mean",
                                 store=ProgramStore(tmp_path))
        engine.init(jax.random.PRNGKey(0))
        rep = engine.precompile(profile, max_shapes=1)
        assert rep.n_shapes == 1


class TestStatsSplit:
    def test_compile_time_splits_into_search_and_trace(self, tmp_path):
        engine = InferenceEngine(
            DIMS, policy=POLICY, readout="mean",
            store=ProgramStore(tmp_path),
        )
        engine.init(jax.random.PRNGKey(0))
        engine.submit([make_request(12, seed=i, rid=i) for i in range(3)])
        stats = engine.stats()
        assert stats.search_s > 0.0, "a cold engine ran the mapper"
        assert stats.trace_s > 0.0, "a cold engine took XLA traces"
        assert stats.n_searches >= 1


class TestCompilationCachePath:
    """The XLA compile cache is placed from outside, or at one fixed path
    in the checkout — never under a store root or a temporary name."""

    @pytest.fixture
    def restore_jax_cache(self):
        from jax.experimental.compilation_cache import compilation_cache

        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs")
        saved = {k: getattr(jax.config, k) for k in keys}
        yield
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()

    def test_env_dir_is_left_to_jax(self, tmp_path, monkeypatch,
                                    restore_jax_cache):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        before = jax.config.jax_compilation_cache_dir
        assert enable_persistent_compilation_cache() == tmp_path / "cc"
        ProgramStore(tmp_path / "store", jax_cache=True)
        assert jax.config.jax_compilation_cache_dir == before
        assert sorted(p.name for p in (tmp_path / "store").iterdir()) == []

    def test_default_is_fixed_path_in_checkout(self, tmp_path, monkeypatch,
                                               restore_jax_cache):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = Path(repro.__file__).resolve().parents[2] / ".jax_cache"
        assert enable_persistent_compilation_cache() == want
        ProgramStore(tmp_path / "store", jax_cache=True)
        assert jax.config.jax_compilation_cache_dir == str(want)
        assert sorted(p.name for p in (tmp_path / "store").iterdir()) == []
