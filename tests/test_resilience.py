"""Serving-resilience tests: engine-boundary validation, admission
control, the degradation ladder, solo-retry quarantine, deterministic
fault injection — and the chaos acceptance run (1000-request stream, 20%
poisoned, submit() never raises, healthy outputs bit-identical to a
fault-free run)."""
import time

import jax
import numpy as np
import pytest

from repro.core.schedule import ModelSchedule
from repro.graphs import BucketPolicy, CSRGraph, from_edges
from repro.runtime import (
    COMPILE,
    FaultInjector,
    FaultRule,
    InferenceEngine,
    Request,
    RetryPolicy,
    kill_pallas,
    validate_request,
)

DIMS = [(12, 16), (16, 4)]
SCHEDULE = ModelSchedule.from_policies("sp_opt", "AC", DIMS)
POL = BucketPolicy(min_nodes=16, min_degree=4, max_graphs=4)
FAST = RetryPolicy(max_retries=0, backoff_s=0.0)


def ring_graph(n: int, seed: int = 0) -> CSRGraph:
    src = np.arange(n)
    dst = (src + 1) % n
    return from_edges(n, np.concatenate([src, dst]), np.concatenate([dst, src]))


def make_request(n: int, seed: int, rid: int = 0, **kw) -> Request:
    g = ring_graph(n, seed=seed)
    x = np.random.default_rng(seed).normal(size=(n, DIMS[0][0])).astype(np.float32)
    return Request(graph=g, x=x, rid=rid, **kw)


def make_engine(params, **kw) -> InferenceEngine:
    kw.setdefault("policy", POL)
    kw.setdefault("schedule", SCHEDULE)
    kw.setdefault("retry", FAST)
    return InferenceEngine(DIMS, params, **kw)


@pytest.fixture(scope="module")
def params():
    eng = InferenceEngine(DIMS, policy=POL, schedule=SCHEDULE)
    return eng.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Engine-boundary validation
# ---------------------------------------------------------------------------


class TestValidation:
    def _reject(self, params, req):
        eng = make_engine(params)
        (res,) = eng.submit([req])
        assert res.status == "rejected"
        assert res.error_type == "invalid_request"
        assert res.output is None
        assert f"request {req.rid}" in res.error
        return res

    def test_nan_features_rejected(self, params):
        req = make_request(16, seed=0, rid=7)
        req.x[3, 2] = np.nan
        res = self._reject(params, req)
        assert "non-finite" in res.error

    def test_float64_features_rejected(self, params):
        good = make_request(16, seed=0, rid=9)
        req = Request(graph=good.graph, x=good.x.astype(np.float64), rid=9)
        res = self._reject(params, req)
        assert "float32" in res.error

    def test_wrong_shape_rejected(self, params):
        good = make_request(16, seed=0, rid=11)
        req = Request(graph=good.graph, x=good.x[:, :-1].copy(), rid=11)
        self._reject(params, req)

    def test_out_of_range_col_idx_rejected(self, params):
        good = make_request(16, seed=0, rid=13)
        g = good.graph
        ci = np.array(g.col_idx, copy=True)
        ci[0] = g.n_nodes + 5  # dangling edge target
        bad = CSRGraph(row_ptr=g.row_ptr, col_idx=ci, values=g.values,
                       n_nodes=g.n_nodes)
        res = self._reject(params, Request(graph=bad, x=good.x, rid=13))
        assert "out of range" in res.error

    def test_csr_invariants_direct(self):
        """Each CSR invariant raises a typed InvalidRequest naming the rid."""
        from repro.runtime import InvalidRequest

        good = make_request(16, seed=0, rid=21)
        g = good.graph

        def expect(graph, match):
            with pytest.raises(InvalidRequest, match=match) as e:
                validate_request(Request(graph=graph, x=good.x, rid=21),
                                 DIMS[0][0])
            assert "request 21" in str(e.value)

        expect(
            CSRGraph(g.row_ptr[:-1], g.col_idx, g.values, g.n_nodes),
            "row_ptr has length",
        )
        rp = np.array(g.row_ptr, copy=True)
        rp[3], rp[4] = rp[4], rp[3] + 2  # break monotonicity
        expect(CSRGraph(rp, g.col_idx, g.values, g.n_nodes), "monoton")
        expect(
            CSRGraph(g.row_ptr, g.col_idx, g.values[:-1], g.n_nodes),
            "lengths",
        )
        vals = np.array(g.values, copy=True)
        vals[0] = np.inf
        expect(CSRGraph(g.row_ptr, g.col_idx, vals, g.n_nodes), "non-finite")

    def test_healthy_neighbors_unaffected(self, params):
        """One malformed request in a submit slice: it is rejected at the
        boundary and the rest of the slice is served normally."""
        reqs = [make_request(16, seed=s, rid=s) for s in range(4)]
        reqs[2].x[0, 0] = np.nan
        eng = make_engine(params)
        results = eng.submit(reqs)
        assert [r.status for r in results] == ["ok", "ok", "rejected", "ok"]
        assert all(r.output is not None for i, r in enumerate(results) if i != 2)


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


class TestAdmissionControl:
    def test_overload_sheds_with_retry_after(self, params):
        eng = make_engine(params, max_inflight_graphs=2)
        results = eng.submit([make_request(16, seed=s, rid=s) for s in range(5)])
        shed = [r for r in results if r.status == "rejected"]
        served = [r for r in results if r.ok]
        assert len(served) == 2 and len(shed) == 3
        for r in shed:
            assert r.error_type == "engine_overloaded"
            assert r.retry_after_s is not None and r.retry_after_s > 0
        assert eng.stats().n_rejected == 3
        assert eng.stats().errors == {"engine_overloaded": 3}

    def test_oversized_graph_rejected(self, params):
        eng = make_engine(
            params,
            policy=BucketPolicy(min_nodes=16, min_degree=4, max_graphs=4,
                                max_nodes=32),
        )
        ok_req = make_request(16, seed=0, rid=0)
        big = make_request(40, seed=1, rid=1)
        res_ok, res_big = eng.submit([ok_req, big])
        assert res_ok.ok
        assert res_big.status == "rejected"
        assert res_big.error_type == "oversized_graph"
        assert "max_nodes=32" in res_big.error

    def test_expired_deadline_fails_at_assembly(self, params):
        eng = make_engine(params)
        healthy = make_request(16, seed=0, rid=0)
        expired = make_request(16, seed=1, rid=1, deadline_s=0.0)
        res_h, res_e = eng.submit([healthy, expired])
        assert res_h.ok
        assert res_e.status == "failed"
        assert res_e.error_type == "deadline_exceeded"
        assert "deadline" in res_e.error
        # the expired request freed its batch slot; the healthy one ran
        assert eng.stats().n_failed == 1 and eng.stats().n_ok == 1

    def test_generous_deadline_served(self, params):
        eng = make_engine(params)
        (res,) = eng.submit([make_request(16, seed=0, rid=0, deadline_s=60.0)])
        assert res.ok


# ---------------------------------------------------------------------------
# Fault isolation: solo-retry quarantine + typed failures
# ---------------------------------------------------------------------------


class TestFaultIsolation:
    def test_poisoned_request_fails_alone_neighbors_bit_identical(self, params):
        """The core isolation property: a sticky per-rid kernel fault takes
        down its whole micro-batch at every tier, the engine quarantines by
        re-running members solo, and only the poisoned rid fails — with the
        healthy neighbors' outputs bit-identical to a fault-free run."""
        reqs = [make_request(16, seed=s, rid=s) for s in range(4)]
        clean = make_engine(params).submit(reqs)

        inj = FaultInjector(rules=[FaultRule(kind="exception", rid=2)])
        eng = make_engine(params, fault_injector=inj)
        chaos = eng.submit(reqs)

        assert chaos[2].status == "failed"
        assert chaos[2].error_type == "kernel_fault"
        assert chaos[2].output is None
        for i in (0, 1, 3):
            assert chaos[i].status == "ok"
            assert np.array_equal(chaos[i].output, clean[i].output), (
                f"rid {i}: quarantined solo output differs from the "
                f"fault-free batched output"
            )
        stats = eng.stats()
        assert stats.n_solo_retries == 4  # every member re-ran alone
        assert stats.n_failed == 1 and stats.n_ok == 3
        assert stats.errors.get("kernel_fault", 0) >= 1

    def test_transient_fault_retried_to_ok(self, params):
        inj = FaultInjector(
            rules=[FaultRule(kind="exception", rid=0, max_fires=1)]
        )
        eng = make_engine(
            params, fault_injector=inj, retry=RetryPolicy(max_retries=1)
        )
        (res,) = eng.submit([make_request(16, seed=0, rid=0)])
        assert res.status == "ok"
        assert res.n_retries >= 1
        assert eng.stats().n_retries >= 1

    def test_persistent_nan_fails_with_numerical_fault(self, params):
        inj = FaultInjector(rules=[FaultRule(kind="nan", rid=1)])
        eng = make_engine(params, fault_injector=inj)
        res0, res1 = eng.submit(
            [make_request(16, seed=0, rid=0), make_request(16, seed=1, rid=1)]
        )
        assert res0.status == "ok"
        assert res1.status == "failed"
        assert res1.error_type == "numerical_fault"
        assert "non-finite" in res1.error

    def test_transient_nan_clears_on_retry(self, params):
        inj = FaultInjector(rules=[FaultRule(kind="nan", rid=0, max_fires=1)])
        eng = make_engine(
            params, fault_injector=inj, retry=RetryPolicy(max_retries=1)
        )
        (res,) = eng.submit([make_request(16, seed=0, rid=0)])
        assert res.status == "ok"
        assert np.isfinite(res.output).all()
        assert res.n_retries >= 1

    def test_check_numerics_off_returns_nans_silently(self, params):
        """The knob documents the tradeoff: with check_numerics=False the
        corrupted output escapes (status ok, NaNs inside)."""
        inj = FaultInjector(rules=[FaultRule(kind="nan", rid=0)])
        eng = make_engine(params, fault_injector=inj, check_numerics=False)
        (res,) = eng.submit([make_request(16, seed=0, rid=0)])
        assert res.status == "ok"
        assert np.isnan(res.output).any()

    def test_compile_boundary_fault_retried(self, params):
        """A transient compile fault on a cold bucket clears on retry."""
        inj = FaultInjector(
            rules=[
                FaultRule(kind="exception", bucket=(16, 4),
                          batch_index=COMPILE, max_fires=1)
            ]
        )
        eng = make_engine(
            params, fault_injector=inj, retry=RetryPolicy(max_retries=1)
        )
        (res,) = eng.submit([make_request(16, seed=0, rid=0)])
        assert res.status == "ok"
        assert res.n_retries >= 1
        assert any(ev.boundary == "compile" for ev in inj.log)

    def test_latency_spike_flags_straggler_but_serves(self, params):
        """An injected latency spike is flagged by the straggler monitor;
        the request itself still completes ok."""
        inj = FaultInjector(
            rules=[FaultRule(kind="latency", batch_index=10, latency_s=0.3)]
        )
        eng = make_engine(params, fault_injector=inj)
        results = []
        for i in range(12):  # one single-request micro-batch per submit
            results += eng.submit([make_request(16, seed=i, rid=i)])
        assert all(r.status == "ok" for r in results)
        stats = eng.stats()
        assert stats.n_stragglers >= 1, (
            "the 0.3s injected spike should dwarf the warm-batch median"
        )
        assert any(ev.kind == "latency" for ev in inj.log)


# ---------------------------------------------------------------------------
# Graceful degradation
# ---------------------------------------------------------------------------


class TestDegradation:
    def test_pallas_outage_mid_stream(self, params):
        """kill_pallas models a live backend outage: buckets whose
        executables are already traced keep serving on the pallas tier;
        cold buckets degrade to jnp+searched with a recorded downgrade."""
        eng = make_engine(params, use_pallas=True)
        warm = eng.submit([make_request(16, seed=0, rid=0),
                           make_request(16, seed=1, rid=1)])
        assert [r.status for r in warm] == ["ok", "ok"]
        assert all(r.tier == "pallas+searched" for r in warm)

        with kill_pallas():
            # same bucket, same slot count -> warm executable still serves
            still_warm = eng.submit([make_request(16, seed=2, rid=2),
                                     make_request(16, seed=3, rid=3)])
            # new bucket -> pallas cannot trace -> degrade down the ladder
            cold = eng.submit([make_request(32, seed=4, rid=4)])

        assert [r.status for r in still_warm] == ["ok", "ok"]
        assert all(r.tier == "pallas+searched" for r in still_warm)
        assert cold[0].status == "degraded"
        assert cold[0].ok  # degraded results are served answers
        assert cold[0].tier == "jnp+searched"
        stats = eng.stats()
        assert stats.n_downgrades == 1 and stats.n_degraded == 1

    @pytest.mark.parametrize("lane", ["batched", "partitioned"])
    def test_pallas_step_down_logged_once_with_cause(
        self, params, caplog, lane
    ):
        """A Pallas tier the backend refuses must not degrade in silence:
        the first step down is logged with its cause, every one counted.
        An oversized request served through a partition plan walks the
        same ladder as a micro-batch."""
        if lane == "batched":
            eng = make_engine(params, use_pallas=True)
            sizes = (16, 32)
        else:
            eng = make_engine(
                params, use_pallas=True, partition_oversized=True,
                policy=BucketPolicy(min_nodes=16, min_degree=4, max_nodes=64),
            )
            sizes = (200, 200)
        with kill_pallas(), caplog.at_level("WARNING", logger="repro.runtime"):
            a = eng.submit([make_request(sizes[0], seed=0, rid=0)])
            b = eng.submit([make_request(sizes[1], seed=1, rid=1)])
        assert [r.tier for r in a + b] == ["jnp+searched"] * 2
        assert [r.status for r in a + b] == ["degraded"] * 2
        if lane == "partitioned":
            assert all(r.plan == "row_stream" and r.n_partitions > 1
                       for r in a + b)
        assert eng.stats().n_downgrades == 2
        assert eng.stats().n_degraded == 2
        logged = [r for r in caplog.records if "stepping down" in r.getMessage()]
        assert len(logged) == 1
        assert "pallas+searched" in logged[0].getMessage()
        assert "KernelFault" in logged[0].getMessage()

    @pytest.mark.parametrize("policy", ["seq", "sp_generic", "sp_opt"])
    @pytest.mark.parametrize("order", ["AC", "CA"])
    def test_degraded_numerics_match_reference(self, params, policy, order):
        """Satellite acceptance: for every (policy, order), the jnp
        fallback the ladder lands on when the Pallas backend dies
        mid-stream matches a pure-jnp reference engine to 1e-6."""
        sched = ModelSchedule.from_policies(policy, order, DIMS)
        reqs = [make_request(16, seed=s, rid=s) for s in range(3)]

        ref_eng = make_engine(params, schedule=sched, use_pallas=False)
        ref = ref_eng.submit(reqs)
        assert all(r.status == "ok" for r in ref)

        eng = make_engine(params, schedule=sched, use_pallas=True)
        with kill_pallas():
            res = eng.submit(reqs)

        for r, rr in zip(res, ref):
            assert r.status == "degraded" and r.tier == "jnp+searched"
            np.testing.assert_allclose(
                r.output, rr.output, atol=1e-6, rtol=0,
                err_msg=f"({policy}, {order}) degraded path diverged",
            )


# ---------------------------------------------------------------------------
# Injector determinism
# ---------------------------------------------------------------------------


class TestInjectorDeterminism:
    def test_same_seed_same_faults(self, params):
        reqs = [make_request(16, seed=s, rid=s) for s in range(24)]

        def run(seed):
            inj = FaultInjector(seed, p_exception=0.5)
            eng = make_engine(params, fault_injector=inj)
            results = eng.submit(reqs)
            return [(r.rid, r.status, r.error_type) for r in results], inj.log

        a_res, a_log = run(seed=7)
        b_res, b_log = run(seed=7)
        assert a_res == b_res, "same seed must reproduce the same statuses"
        assert a_log == b_log, "same seed must reproduce the same injections"
        assert a_log, "p_exception=0.5 over the stream must inject something"

    def test_rule_max_fires_bounds_injection(self):
        rule = FaultRule(kind="exception", rid=5, max_fires=2)
        inj = FaultInjector(rules=[rule])
        fired = 0
        for _ in range(5):
            try:
                inj.on_run((16, 4), 0, [5], "jnp+searched")
            except Exception:
                fired += 1
        assert fired == 2 and rule.fires == 2

    def test_rule_targeting_fields(self):
        rule = FaultRule(kind="nan", bucket=(32, 8), tier="pallas+searched")
        assert rule.matches((32, 8), 3, [1, 2], "pallas+searched")
        assert not rule.matches((16, 4), 3, [1, 2], "pallas+searched")
        assert not rule.matches((32, 8), 3, [1, 2], "jnp+default")

    def test_invalid_config_rejected(self):
        with pytest.raises(ValueError, match="kind"):
            FaultRule(kind="segfault")
        with pytest.raises(ValueError, match="p_exception"):
            FaultInjector(p_exception=1.5)
        with pytest.raises(ValueError, match="sum"):
            FaultInjector(p_exception=0.6, p_nan=0.6)

    def test_corrupt_output_fraction(self):
        inj = FaultInjector(nan_fraction=0.25)
        out = inj.corrupt_output(np.zeros((8, 8), np.float32))
        frac = float(np.isnan(out).mean())
        assert frac == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# Chaos acceptance: the headline isolation proof
# ---------------------------------------------------------------------------


class TestChaosAcceptance:
    def test_1000_request_stream_20pct_poisoned(self, params):
        """ISSUE acceptance: a 1000-request stream with 20% poisoned
        requests completes with submit() never raising, every non-ok
        result typed, EngineStats counters matching the per-result tally,
        and healthy outputs bit-identical to a fault-free run."""
        n_total = 1000
        policy = BucketPolicy(min_nodes=16, min_degree=4, max_graphs=4,
                              max_nodes=64)
        kernel_rids = []
        reqs = []
        for rid in range(n_total):
            if rid % 5 == 0:  # 200 poisoned, 40 per class
                cls = (rid // 5) % 5
                if cls == 0:  # NaN features
                    r = make_request(16, seed=rid, rid=rid)
                    r.x[0, 0] = np.nan
                elif cls == 1:  # float64 features
                    g = make_request(16, seed=rid, rid=rid)
                    r = Request(graph=g.graph, x=g.x.astype(np.float64),
                                rid=rid)
                elif cls == 2:  # broken CSR
                    g = make_request(16, seed=rid, rid=rid)
                    ci = np.array(g.graph.col_idx, copy=True)
                    ci[0] = 999
                    r = Request(
                        graph=CSRGraph(g.graph.row_ptr, ci, g.graph.values,
                                       g.graph.n_nodes),
                        x=g.x, rid=rid,
                    )
                elif cls == 3:  # oversized
                    r = make_request(100, seed=rid, rid=rid)
                else:  # sticky per-rid kernel fault
                    r = make_request(16, seed=rid, rid=rid)
                    kernel_rids.append(rid)
            else:
                r = make_request(16, seed=rid, rid=rid)
            reqs.append(r)

        inj = FaultInjector(
            rules=[FaultRule(kind="exception", rid=rid) for rid in kernel_rids]
        )
        eng = make_engine(params, policy=policy, fault_injector=inj)
        results = eng.submit(reqs)  # must never raise

        assert len(results) == n_total
        by_status: dict[str, int] = {}
        for req, res in zip(reqs, results):
            assert res.rid == req.rid
            by_status[res.status] = by_status.get(res.status, 0) + 1
            if res.ok:
                assert res.output is not None
                assert np.isfinite(res.output).all()
                assert res.error is None and res.error_type is None
            else:
                assert res.output is None
                assert res.error_type is not None, (
                    f"rid {res.rid}: non-ok result must carry a typed cause"
                )
                assert f"request {res.rid}" in res.error or res.error

        assert by_status.get("ok", 0) == 800
        assert by_status.get("rejected", 0) == 160  # nan/f64/csr/oversized
        assert by_status.get("failed", 0) == 40  # the kernel-fault rids
        failed_rids = {r.rid for r in results if r.status == "failed"}
        assert failed_rids == set(kernel_rids), (
            "exactly the poisoned rids fail; quarantine must not take "
            "healthy neighbors down"
        )

        stats = eng.stats()
        assert stats.n_requests == n_total
        assert stats.n_ok == 800
        assert stats.n_rejected == 160
        assert stats.n_failed == 40
        assert stats.n_ok + stats.n_rejected + stats.n_failed \
            + stats.n_degraded == n_total
        assert stats.n_solo_retries > 0  # quarantine actually ran
        assert stats.errors.get("invalid_request", 0) == 120
        assert stats.errors.get("oversized_graph", 0) == 40
        assert stats.errors.get("kernel_fault", 0) == 40

        # healthy outputs are bit-identical to a fault-free run of the
        # same requests (block-diagonal batching computes each graph
        # independently, so batch composition cannot change the answer)
        healthy = [r for r in reqs if r.rid % 5 != 0]
        ref_eng = make_engine(params, policy=policy)
        ref = {res.rid: res for res in ref_eng.submit(healthy)}
        for res in results:
            if res.status == "ok":
                assert np.array_equal(res.output, ref[res.rid].output), (
                    f"rid {res.rid}: chaos output differs from fault-free run"
                )
