"""Serving-layer tests: the Program jit-executable cache (zero re-tracing
on same-shape inputs), segment-aware readout parity (batched == per-graph
to 1e-6), and the bucketized InferenceEngine end to end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import GNNLayerWorkload
from repro.core.schedule import ModelSchedule
from repro.gnn.layers import EllAdjacency, segment_readout
from repro.graphs import BucketPolicy, assemble, from_edges
from repro.runtime.engine import InferenceEngine, ProgramCache, Request

DIMS = [(12, 16), (16, 4)]
SCHEDULE = ModelSchedule.from_policies("sp_opt", "AC", DIMS)


def ring_graph(n: int, chords: int = 0, seed: int = 0):
    rng = np.random.default_rng(seed)
    src = np.arange(n)
    dst = (src + 1) % n
    if chords:
        es = rng.integers(0, n, size=chords)
        ed = rng.integers(0, n, size=chords)
        src, dst = np.concatenate([src, es]), np.concatenate([dst, ed])
    return from_edges(n, np.concatenate([src, dst]), np.concatenate([dst, src]))


def make_request(n: int, seed: int, rid: int = 0, chords: int = 0) -> Request:
    """chords=0 keeps max degree at 3 (ring + self loop), so every
    same-size request routes to one deterministic bucket."""
    g = ring_graph(n, chords=chords, seed=seed)
    x = np.random.default_rng(seed).normal(size=(n, DIMS[0][0])).astype(np.float32)
    return Request(graph=g, x=x, rid=rid)


def compiled(graph, schedule=SCHEDULE):
    wls = [GNNLayerWorkload(graph.nnz, fi, fo) for fi, fo in DIMS]
    return repro.compile(wls, graph=graph, schedule=schedule)


@pytest.fixture(scope="module")
def params():
    prog = compiled(ring_graph(16))
    return prog.init(jax.random.PRNGKey(0))


class TestExecutableCache:
    def test_second_run_takes_zero_traces(self, params):
        g = ring_graph(24, chords=6)
        prog = compiled(g)
        x = jnp.ones((g.n_nodes, DIMS[0][0]), jnp.float32)
        prog.run(params, x)
        before = repro.trace_count()
        out = prog.run(params, x)
        assert repro.trace_count() == before, "same-shape run re-traced"
        assert out.shape == (g.n_nodes, DIMS[-1][1])

    def test_same_shape_rebind_takes_zero_traces(self, params):
        """The serving case: a new graph with identical padded shapes must
        reuse the compiled executable through bind()."""
        a = ring_graph(24, chords=6, seed=1)
        b = ring_graph(24, chords=6, seed=2)
        d = max(a.max_degree, b.max_degree)
        prog = compiled(a)
        bound_a = prog.bind(a, pad_degree=d)
        bound_b = prog.bind(b, pad_degree=d)
        x = jnp.ones((24, DIMS[0][0]), jnp.float32)
        bound_a.run(params, x)
        before = repro.trace_count()
        out_a = bound_a.run(params, x)
        out_b = bound_b.run(params, x)
        assert repro.trace_count() == before, "same-shape rebind re-traced"
        # different adjacency, same executable: results must differ
        assert not np.allclose(np.asarray(out_a), np.asarray(out_b))

    def test_new_shape_traces_once(self, params):
        g1, g2 = ring_graph(16), ring_graph(32)
        x1 = jnp.ones((16, DIMS[0][0]), jnp.float32)
        x2 = jnp.ones((32, DIMS[0][0]), jnp.float32)
        prog = compiled(g1)
        prog.run(params, x1)
        before = repro.trace_count()
        prog.bind(g2, pad_degree=g1.max_degree).run(params, x2)
        assert repro.trace_count() == before + 1

    def test_pad_degree_narrower_than_max_degree_rejected(self):
        g = ring_graph(16, chords=8)
        with pytest.raises(ValueError, match="narrower"):
            compiled(g).bind(g, pad_degree=1)


class TestKernelFamilyDefault:
    """Pallas or jnp follows the backend unless the caller says."""

    def test_engine_default_follows_backend(self):
        from repro.runtime import default_ladder

        on_tpu = jax.default_backend() == "tpu"
        eng = InferenceEngine(DIMS)
        assert eng.use_pallas is on_tpu
        assert eng.ladder == default_ladder(on_tpu)

    @pytest.mark.parametrize("flag", [True, False])
    def test_explicit_flag_is_honoured(self, flag):
        assert InferenceEngine(DIMS, use_pallas=flag).use_pallas is flag
        wls = [GNNLayerWorkload(ring_graph(16).nnz, fi, fo) for fi, fo in DIMS]
        prog = repro.compile(wls, schedule=SCHEDULE, use_pallas=flag)
        assert prog.use_pallas is flag

    def test_compile_default_follows_backend(self):
        prog = compiled(ring_graph(16))
        assert prog.use_pallas is (jax.default_backend() == "tpu")

    @pytest.mark.parametrize(
        "policy,order,want",
        [("seq", "CA", ("seq", "CA", True)),
         ("sp_opt", "AC", ("sp_opt", "AC", True)),
         ("sp_opt", "CA", ("sp_opt", "CA", False)),
         ("sp_generic", "AC", ("sp_generic", "AC", False))],
    )
    def test_resolve_kernel_key_names_the_jnp_fallback(self, policy, order, want):
        from repro.core import resolve_kernel_key

        assert resolve_kernel_key(policy, order, True) == want
        assert resolve_kernel_key(policy, order, False) == (policy, order, False)

    def test_lowered_is_the_executable_run_calls(self, params):
        g = ring_graph(24, chords=6)
        prog = compiled(g)
        x = jnp.ones((g.n_nodes, DIMS[0][0]), jnp.float32)
        out = prog.lowered(params, x).compile()(
            params, prog.adj.indices, prog.adj.weights, x,
            jnp.zeros(0, jnp.int32),
        )
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(prog.run(params, x)))


class TestSegmentReadout:
    def test_readout_reduces_known_values(self):
        h = jnp.asarray([[1.0], [3.0], [10.0], [99.0]])
        ids = jnp.asarray([0, 0, 1, 2])  # id 2 is out of range: pad row
        mean = segment_readout(h, ids, 2, reduce="mean")
        np.testing.assert_allclose(np.asarray(mean), [[2.0], [10.0]])
        total = segment_readout(h, ids, 2, reduce="sum")
        np.testing.assert_allclose(np.asarray(total), [[4.0], [10.0]])
        mx = segment_readout(h, ids, 2, reduce="max")
        np.testing.assert_allclose(np.asarray(mx), [[3.0], [10.0]])

    def test_invalid_reduce_rejected(self):
        with pytest.raises(ValueError, match="reduce"):
            segment_readout(jnp.zeros((2, 1)), jnp.zeros(2, jnp.int32), 1,
                            reduce="median")

    def test_batched_outputs_match_single_graph_runs(self, params):
        """Acceptance: per-graph outputs from a batched run match
        single-graph runs to 1e-6 — node logits and every readout."""
        graphs = [ring_graph(10, 3, seed=s) for s in range(3)]
        pol = BucketPolicy(min_nodes=16, min_degree=16, max_graphs=4)
        batch = assemble(graphs, pol)
        xs = [
            np.random.default_rng(s).normal(
                size=(g.n_nodes, DIMS[0][0])
            ).astype(np.float32)
            for s, g in enumerate(graphs)
        ]
        prog = compiled(batch.graph).bind(batch.graph, pad_degree=batch.d_bucket)
        x = jnp.asarray(batch.batch_features(xs))
        seg = jnp.asarray(batch.segment_ids)

        # node-level parity through split_nodes
        nodes = batch.split_nodes(np.asarray(prog.run(params, x)))
        singles = [
            np.asarray(compiled(g).run(params, jnp.asarray(xg)))
            for g, xg in zip(graphs, xs)
        ]
        for got, want in zip(nodes, singles):
            np.testing.assert_allclose(got, want, atol=1e-6)

        # per-graph readout parity
        for reduce, ref in (
            ("mean", [s.mean(axis=0) for s in singles]),
            ("sum", [s.sum(axis=0) for s in singles]),
            ("max", [s.max(axis=0) for s in singles]),
        ):
            out = prog.run(
                params, x, segment_ids=seg,
                num_segments=batch.n_graphs, readout=reduce,
            )
            assert out.shape == (batch.n_graphs, DIMS[-1][1])
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=1e-6,
                err_msg=f"readout={reduce}",
            )

    def test_segment_ids_require_num_segments(self, params):
        g = ring_graph(16)
        prog = compiled(g)
        with pytest.raises(ValueError, match="num_segments"):
            prog.run(params, jnp.ones((16, DIMS[0][0])),
                     segment_ids=jnp.zeros(16, jnp.int32))
        with pytest.raises(ValueError, match="segment_ids"):
            prog.run(params, jnp.ones((16, DIMS[0][0])), num_segments=3)
        with pytest.raises(ValueError, match="segment_ids"):
            prog.run(params, jnp.ones((16, DIMS[0][0])), readout="max")


class TestProgramCache:
    def test_lru_eviction(self):
        cache = ProgramCache(capacity=2)
        progs = {k: compiled(ring_graph(8 + k)) for k in range(3)}
        cache.put(("a",), progs[0])
        cache.put(("b",), progs[1])
        assert cache.get(("a",)) is progs[0]  # refresh a
        cache.put(("c",), progs[2])  # evicts b, the least recent
        assert cache.get(("b",)) is None
        assert cache.get(("a",)) is progs[0]
        assert cache.get(("c",)) is progs[2]
        assert cache.evictions == 1
        assert (cache.hits, cache.misses) == (3, 1)

    def test_capacity_validated(self):
        with pytest.raises(ValueError, match="capacity"):
            ProgramCache(capacity=0)


class TestInferenceEngine:
    POL = BucketPolicy(min_nodes=16, min_degree=4, max_graphs=4)

    def engine(self, **kw):
        eng = InferenceEngine(DIMS, policy=self.POL, schedule=SCHEDULE, **kw)
        eng.init(jax.random.PRNGKey(0))
        return eng

    def test_stream_end_to_end(self):
        eng = self.engine()
        reqs = [make_request(8 + (i % 3) * 9, seed=i, rid=100 + i)
                for i in range(10)]
        results = eng.submit(reqs)
        assert [r.rid for r in results] == [100 + i for i in range(10)]
        assert all(r.output.shape == (DIMS[-1][1],) for r in results)
        stats = eng.stats()
        assert stats.n_requests == 10
        assert stats.n_buckets >= 2  # 8-node and 17/26-node graphs differ
        assert stats.p99_ms >= stats.p50_ms > 0

    def test_warm_stream_is_trace_free_and_hits_cache(self):
        eng = self.engine()
        reqs = [make_request(12, seed=i, rid=i) for i in range(6)]
        cold = eng.submit(reqs)
        misses = eng.cache.misses
        before = repro.trace_count()
        warm = eng.submit([make_request(12, seed=i + 50, rid=i) for i in range(6)])
        assert repro.trace_count() == before, "warm same-bucket stream re-traced"
        assert eng.cache.misses == misses  # all hits
        # different graphs/features through the same executable: new outputs
        assert not np.allclose(cold[0].output, warm[0].output)

    def test_engine_matches_per_graph_serving(self):
        """The whole point: batched serving computes the same answers."""
        eng = self.engine()
        reqs = [make_request(11, seed=i, rid=i) for i in range(5)]
        results = eng.submit(reqs)
        for req, res in zip(reqs, results):
            single = compiled(req.graph).run(eng.params, jnp.asarray(req.x))
            np.testing.assert_allclose(
                res.output, np.asarray(single).mean(axis=0), atol=1e-6
            )

    def test_node_level_readout_none(self):
        eng = self.engine(readout=None)
        reqs = [make_request(9, seed=i, rid=i) for i in range(3)]
        results = eng.submit(reqs)
        for req, res in zip(reqs, results):
            assert res.output.shape == (req.graph.n_nodes, DIMS[-1][1])

    def test_feature_shape_validated(self):
        """Per-request causes no longer raise out of submit(): a bad shape
        comes back as a typed rejected Result naming the request id."""
        eng = self.engine()
        g = ring_graph(9)
        bad = Request(graph=g, x=np.zeros((9, 3), np.float32), rid=7)
        (res,) = eng.submit([bad])
        assert res.status == "rejected"
        assert res.error_type == "invalid_request"
        assert res.output is None
        assert "request 7" in res.error

    def test_params_required(self):
        eng = InferenceEngine(DIMS, policy=self.POL, schedule=SCHEDULE)
        with pytest.raises(ValueError, match="params"):
            eng.submit([make_request(9, seed=0)])

    def test_tail_fill_levels_share_the_executable(self):
        """Readout runs over the padded slot count, so fill levels that
        round to the same slot shape (3 and 4 graphs -> 4 slots) reuse one
        executable: no new traces after the slot shape is warm."""
        eng = self.engine()
        eng.submit([make_request(12, seed=i, rid=i) for i in range(3)])
        before = repro.trace_count()
        for fill in (4, 3):
            res = eng.submit(
                [make_request(12, seed=10 * fill + i, rid=i)
                 for i in range(fill)]
            )
            assert len(res) == fill
            assert all(r.output.shape == (DIMS[-1][1],) for r in res)
        assert repro.trace_count() == before, (
            "tail batches with different fill levels re-traced"
        )

    def test_colliding_v_totals_keep_distinct_programs(self):
        """Buckets whose v_bucket * slots products coincide (16x2 vs 32x1
        padded nodes) must not share a cache entry: each bucket gets its
        own Program (and, unpinned, its own mapper search)."""
        eng = self.engine()
        eng.submit([make_request(12, seed=0, rid=0),
                    make_request(12, seed=1, rid=1)])  # (16,4) x 2 slots
        misses = eng.cache.misses
        eng.submit([make_request(20, seed=2, rid=2)])  # (32,4) x 1 slot
        assert eng.cache.misses == misses + 1, (
            "a (32,4)-bucket batch reused the (16,4)x2 Program"
        )

    def test_ell_slot_counters_read_the_bound_batch(self):
        """``ell_slots_used / ell_slots`` is the batch's real nonzeros over
        the bound padded ELL's V_pad x D: three 12-node rings (36 nonzeros
        each with their self-loops) in a (16, 4) bucket of 4 slots, rows
        grouped to the schedule's ELL block.  Pad rows' zero-weight
        self-loops are not counted."""
        eng = self.engine()
        reqs = [make_request(12, seed=i, rid=i) for i in range(3)]
        eng.submit(reqs)
        batch = assemble([r.graph for r in reqs], self.POL)
        v_pad, d = EllAdjacency.from_schedule(
            batch.graph, SCHEDULE, pad_to=batch.d_bucket
        ).indices.shape
        assert batch.n_pad == 64 - 36 and d == 4
        st = eng.stats()
        assert (st.ell_slots, st.ell_slots_used) == (v_pad * d, 3 * 36)
        eng.submit(reqs[:1])  # a 1-slot tail batch of its own shape
        st1 = eng.stats()
        assert st1.ell_slots_used == st.ell_slots_used + 36
        assert st1.ell_slots > st.ell_slots

    def test_mapper_search_runs_once_per_bucket(self):
        """Without a pinned schedule, the engine searches on a bucket's
        first batch and reuses the schedule for later slot variants."""
        eng = InferenceEngine(DIMS, policy=self.POL)
        eng.init(jax.random.PRNGKey(0))
        reqs = [make_request(12, seed=i, rid=i) for i in range(5)]
        eng.submit(reqs)  # 4-slot batch + 1-slot tail: two cache keys
        assert eng.cache.misses == 2
        assert len(eng._schedules) == 1  # but one mapper search


def test_async_stats_sum_ell_slots_over_workers():
    """Two workers (on one device) serve three buckets, one request each,
    so every batch is the sync engine's whatever the windows do; the front
    end's counters are the sum of the workers' and equal the sync
    engine's."""
    from repro.runtime import AsyncEngine

    pol = TestInferenceEngine.POL
    reqs = [make_request(12, seed=1, rid=1),
            make_request(20, seed=9, rid=9, chords=4),
            make_request(40, seed=5, rid=10)]
    sync = InferenceEngine(DIMS, policy=pol, schedule=SCHEDULE)
    params = sync.init(jax.random.PRNGKey(0))
    sync.submit(reqs)
    dev = jax.devices()[0]
    with AsyncEngine(DIMS, params, devices=[dev, dev], window_ms=5.0,
                     policy=pol, schedule=SCHEDULE) as a:
        assert all(r.status == "ok" for r in a.submit(reqs))
    per = [w.engine.stats() for w in a.workers]
    assert all(s.ell_slots_used > 0 for s in per), "a worker served nothing"
    st, want = a.stats(), sync.stats()
    assert st.ell_slots == sum(s.ell_slots for s in per) == want.ell_slots
    assert (st.ell_slots_used == sum(s.ell_slots_used for s in per)
            == want.ell_slots_used)


class TestPartitionAwareAdmission:
    """Oversized requests charge ``n_partitions`` units against
    ``max_inflight_graphs``, not one batch slot."""

    POL = BucketPolicy(min_nodes=16, min_degree=4, max_nodes=64)

    def engine(self, cap: int):
        eng = InferenceEngine(
            DIMS, policy=self.POL, partition_oversized=True,
            max_inflight_graphs=cap,
        )
        eng.init(jax.random.PRNGKey(0))
        return eng

    def giant(self, rid: int = 100) -> Request:
        return make_request(200, seed=7, rid=rid)

    def test_giant_charges_partition_units(self):
        eng = self.engine(cap=4)
        smalls = [make_request(24, seed=i + 1, rid=i) for i in range(3)]
        res = eng.submit([self.giant()] + smalls)
        g = res[0]
        assert g.ok and g.n_partitions >= 2
        # the giant's fan-out filled the budget its partitions consume
        slots_left = max(0, 4 - g.n_partitions)
        n_shed = sum(r.status == "rejected" for r in res[1:])
        assert n_shed == max(0, len(smalls) - slots_left)
        shed = [r for r in res[1:] if r.status == "rejected"]
        assert all(r.error_type == "engine_overloaded" for r in shed)
        assert all(r.retry_after_s > 0 for r in shed)

    def test_giant_behind_full_batch_is_shed_with_unit_hint(self):
        eng = self.engine(cap=4)
        smalls = [make_request(24, seed=i + 1, rid=i) for i in range(4)]
        res = eng.submit(smalls + [self.giant()])
        assert all(r.ok for r in res[:-1])
        g = res[-1]
        assert g.status == "rejected"
        assert g.error_type == "engine_overloaded"
        assert g.retry_after_s is not None and g.retry_after_s > 0
        assert "partition units" in g.error  # unit-aware shed path

    def test_empty_engine_always_admits_one_giant(self):
        # its units exceed the cap outright, but an empty engine must
        # make progress rather than starve the giant forever
        eng = self.engine(cap=2)
        res = eng.submit([self.giant(rid=1)])
        assert res[0].ok and res[0].n_partitions > 2


class TestMeasuredRerank:
    """Warm batches log measured walls; rerank_topk swaps off-path."""

    POL = BucketPolicy(min_nodes=16, min_degree=4, max_graphs=4)

    def engine(self, **kw):
        eng = InferenceEngine(DIMS, policy=self.POL, **kw)
        eng.init(jax.random.PRNGKey(0))
        return eng

    def test_warm_submit_records_wall_observations(self):
        eng = self.engine()
        reqs = [make_request(12, seed=i, rid=i) for i in range(4)]
        eng.submit(reqs)  # cold: traces, no observation
        assert not eng.profile.observed
        eng.submit(reqs)  # warm: one observation per micro-batch
        assert eng.profile.observed
        (v, d, slots, digest), (n, tot) = next(iter(eng.profile.observed.items()))
        assert (v, d) in eng._buckets_seen and n >= 1 and tot > 0
        assert eng.profile.mean_wall((v, d), slots, digest) > 0

    def test_compile_without_a_trace_is_cold(self, monkeypatch):
        """A backend compile that takes no new trace (an executable
        evicted, or an input placed differently) is counted, charged to
        trace_s and never logged as a warm wall."""
        eng = self.engine()
        reqs = [make_request(12, seed=i, rid=i) for i in range(4)]
        eng.submit(reqs)  # cold
        eng.submit(reqs)  # warm: one observation
        observed = dict(eng.profile.observed)
        st0 = eng.stats()
        run = repro.Program.run

        def compiling_run(self, *a, **k):
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", 1e-3
            )
            return run(self, *a, **k)

        monkeypatch.setattr(repro.Program, "run", compiling_run)
        traces = repro.trace_count()
        assert all(r.ok for r in eng.submit(reqs))
        st = eng.stats()
        assert repro.trace_count() == traces
        assert st.n_compiles - st0.n_compiles == st.n_batches - st0.n_batches
        assert st.trace_s > st0.trace_s
        assert eng.profile.observed == observed

    def test_compile_count_is_per_thread(self):
        import threading

        def compile_on_thread():
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", 1e-3
            )

        before = repro.compile_count()
        t = threading.Thread(target=compile_on_thread)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert repro.compile_count() == before
        compile_on_thread()
        assert repro.compile_count() == before + 1

    def test_rerank_is_trace_free_on_request_path(self):
        eng = self.engine()
        reqs = [make_request(12, seed=i, rid=i) for i in range(4)]
        eng.submit(reqs)
        eng.submit(reqs)
        rep = eng.rerank_topk(top_k=2, iters=2, warmup=1)
        assert rep.n_buckets >= 1
        assert rep.n_candidates >= 1
        before = repro.trace_count()
        res = eng.submit(reqs)
        assert all(r.ok for r in res)
        assert repro.trace_count() == before, (
            "rerank_topk leaked XLA traces onto the request path"
        )
