"""Async front-end + multi-device bucket placement tests.

The in-process lane covers the :class:`~repro.runtime.scheduler.BucketPlacer`
policy, the profile heat/subset helpers, the backlog-proportional
``retry_after_s`` hint, and the single-device ``AsyncEngine`` contract
(admission before queueing, window flushes, per-request futures).

The multi-device lane runs in a subprocess under
``--xla_force_host_platform_device_count=4`` (so the override cannot
pollute this process's jax) and asserts the three placement properties
the ISSUE names: (a) distinct buckets land on distinct devices,
(b) outputs are bit-identical to the single-device sync engine, and
(c) a faulted request on one device never perturbs results on another.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.graphs import TABLE4, BucketPolicy
from repro.graphs.batching import TrafficProfile
from repro.graphs.datasets import make_graph
from repro.runtime import (
    AsyncEngine,
    BucketPlacer,
    InferenceEngine,
    Request,
)
from repro.runtime.resilience import backlog_retry_after


# ---------------------------------------------------------------------------
# BucketPlacer policy
# ---------------------------------------------------------------------------


def test_placer_distinct_buckets_distinct_devices():
    p = BucketPlacer(4)
    for i, b in enumerate([(32, 8), (64, 8), (128, 16), (256, 16)]):
        p.record(b, 10)
    homes = [p.assignment[b][0] for b in p.assignment]
    assert sorted(homes) == [0, 1, 2, 3]


def test_placer_hot_bucket_gets_replica():
    p = BucketPlacer(4, replicas=2)
    p.record((32, 8), 1)
    p.record((64, 8), 1)
    # (32, 8) becomes far hotter than a fair 1/4 share -> second device
    p.record((32, 8), 100)
    assert len(p.assignment[(32, 8)]) == 2
    assert len(set(p.assignment[(32, 8)])) == 2
    # the cold bucket stays single-homed
    assert len(p.assignment[(64, 8)]) == 1


def test_placer_replicas_capped_by_knob_and_devices():
    p = BucketPlacer(2, replicas=8)  # knob beyond the mesh clamps
    assert p.replicas == 2
    p.record((32, 8), 1000)
    p.record((32, 8), 1000)
    assert len(p.assignment[(32, 8)]) <= 2


def test_placer_pick_prefers_least_outstanding_replica():
    p = BucketPlacer(2, replicas=2)
    p.record((32, 8), 100)
    p.record((32, 8), 100)  # hot -> both devices
    assert len(p.assignment[(32, 8)]) == 2
    d0 = p.pick((32, 8), 10)
    d1 = p.pick((32, 8), 1)  # first pick is busier now
    assert d1 != d0
    p.done(d0, 10)
    p.done(d1, 1)
    assert p.outstanding == [0, 0]


def test_placer_buckets_for_covers_assignment():
    p = BucketPlacer(2)
    p.record((32, 8), 1)
    p.record((64, 8), 1)
    all_buckets = set()
    for d in range(2):
        all_buckets |= p.buckets_for(d)
    assert all_buckets == {(32, 8), (64, 8)}


# ---------------------------------------------------------------------------
# Satellite: backlog-proportional retry_after + profile helpers
# ---------------------------------------------------------------------------


def test_backlog_retry_after_scales_with_queue_depth():
    shallow = backlog_retry_after(10, 0.02, 64)
    deep = backlog_retry_after(640, 0.02, 64)
    assert shallow == pytest.approx(0.02)  # one batch drains it
    assert deep == pytest.approx(0.2)  # ten batches
    assert backlog_retry_after(0, 0.02, 64) == pytest.approx(0.02)  # floor


def test_profile_heat_orders_hottest_first():
    prof = TrafficProfile()
    prof.record_request((32, 8), 5)
    prof.record_request((64, 8), 50)
    assert prof.heat()[0] == ((64, 8), 50)


def test_profile_subset_filters_both_ledgers():
    prof = TrafficProfile()
    prof.record_request((32, 8), 5)
    prof.record_request((64, 8), 7)
    prof.record_batch((32, 8), 4)
    prof.record_batch((64, 8), 8)
    sub = prof.subset({(32, 8)})
    assert sub.requests == {(32, 8): 5}
    assert sub.batches == {(32, 8, 4): 1}
    # the original is untouched
    assert prof.requests[(64, 8)] == 7


# ---------------------------------------------------------------------------
# AsyncEngine, single device (in-process)
# ---------------------------------------------------------------------------

DIMS = [(16, 8)]


def _stream(n, f_in=16, seed=0, names=("mutag", "imdb-bin")):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        g = make_graph(TABLE4[names[i % len(names)]], rng)
        x = rng.normal(size=(g.n_nodes, f_in)).astype(np.float32)
        reqs.append(Request(graph=g, x=x, rid=i))
    return reqs


@pytest.fixture(scope="module")
def params():
    return InferenceEngine(DIMS).init(jax.random.PRNGKey(0))


def test_async_single_device_matches_sync(params):
    reqs = _stream(8)
    sync = InferenceEngine(DIMS, params)
    sync_res = sync.submit(reqs)
    with AsyncEngine(DIMS, params, window_ms=5.0) as a:
        res = a.submit(reqs)
    for r, s in zip(res, sync_res):
        assert r.status == s.status == "ok"
        np.testing.assert_array_equal(r.output, s.output)
    st = a.stats()
    assert st.n_requests == 8
    assert st.n_ok == 8
    assert st.p99_ms >= st.p50_ms > 0


def test_async_admission_before_queueing(params):
    """Malformed and oversized requests resolve immediately as rejected —
    they never occupy a window slot or reach a device."""
    from repro.graphs import from_edges

    policy = BucketPolicy(max_nodes=64)
    good = _stream(1, names=("mutag",))[0]
    n_big = 100  # deterministic chain over the 64-node cap
    big = from_edges(
        n_big, np.arange(n_big - 1), np.arange(1, n_big)
    )
    oversized = Request(
        graph=big,
        x=np.zeros((big.n_nodes, 16), np.float32),
        rid=100,
    )
    bad_x = Request(graph=good.graph, x=np.zeros((3, 16), np.float32), rid=101)
    with AsyncEngine(DIMS, params, window_ms=5.0, policy=policy) as a:
        f_bad = a.submit_async(bad_x)
        f_big = a.submit_async(oversized)
        assert f_bad.result(timeout=1).status == "rejected"
        assert f_big.result(timeout=1).status == "rejected"
        ok = a.submit_async(good).result(timeout=60)
        assert ok.status == "ok"
    st = a.stats()
    assert st.n_rejected == 2
    assert st.errors.get("invalid_request") == 1
    assert st.errors.get("oversized_graph") == 1


def test_async_queue_cap_sheds_with_backlog_hint(params):
    reqs = _stream(6, names=("mutag",))
    with AsyncEngine(
        DIMS, params, window_ms=200.0, max_queue_graphs=4
    ) as a:
        futs = [a.submit_async(r) for r in reqs]
        shed = [f.result(timeout=120) for f in futs[4:]]
        served = [f.result(timeout=120) for f in futs[:4]]
    assert all(r.status == "rejected" for r in shed)
    assert all(r.error_type == "engine_overloaded" for r in shed)
    assert all(r.retry_after_s is not None and r.retry_after_s > 0
               for r in shed)
    assert all(r.status == "ok" for r in served)


def test_async_window_flushes_on_fill_before_deadline(params):
    """A window that reaches max_graphs flushes immediately — a huge
    window_ms must not delay a full batch."""
    policy = BucketPolicy(max_graphs=4)
    reqs = _stream(4, names=("mutag",))
    with AsyncEngine(
        DIMS, params, window_ms=60_000.0, policy=policy
    ) as a:
        res = a.submit(reqs)  # would hang for a minute if fill didn't flush
    assert all(r.status == "ok" for r in res)
    assert a.stats().n_flushes_full >= 1


def test_async_deadline_enforced_at_window(params):
    """A request whose deadline expires while parked in the window fails
    typed at the flush boundary (PR 6 contract), not silently late."""
    req = _stream(1, names=("mutag",))[0]
    expired = Request(graph=req.graph, x=req.x, rid=0, deadline_s=1e-9)
    with AsyncEngine(DIMS, params, window_ms=30.0) as a:
        r = a.submit_async(expired).result(timeout=60)
    assert r.status == "failed"
    assert r.error_type == "deadline_exceeded"


def test_async_per_request_latency_includes_queue_wait(params):
    """Per-request latency is enqueue -> result: a request parked for the
    whole window must be charged at least the window it waited."""
    req = _stream(1, names=("mutag",))[0]
    with AsyncEngine(DIMS, params, window_ms=80.0) as a:
        a.submit([req])  # warm the bucket (compile off the clock)
        r = a.submit_async(
            Request(graph=req.graph, x=req.x, rid=1)
        ).result(timeout=60)
    assert r.status == "ok"
    # lone request -> deadline flush -> waited ~the full 80 ms window
    assert r.latency_s >= 0.05


def test_async_window_and_inbox_waits_are_counted(params):
    """A lone request waits out its whole window, and its flushed group is
    counted once on its way through the worker's inbox."""
    req = _stream(1, names=("mutag",))[0]
    window_ms = 40.0
    with AsyncEngine(DIMS, params, window_ms=window_ms) as a:
        a.submit([req])  # warm the bucket
        st0 = a.stats()
        r = a.submit_async(
            Request(graph=req.graph, x=req.x, rid=1)
        ).result(timeout=60)
        st = a.stats()
    assert r.status == "ok"
    assert st.n_window_waits - st0.n_window_waits == 1
    assert st.window_wait_s - st0.window_wait_s >= 0.9 * window_ms / 1e3
    assert st.n_groups - st0.n_groups == 1
    assert 0.0 <= st.inbox_wait_s - st0.inbox_wait_s < r.latency_s


@pytest.mark.parametrize("readout", [None, "mean"])
def test_async_precompile_warms_assigned_buckets(tmp_path, params, readout):
    """precompile() on a revived engine loads from the shared store and
    leaves the first real request trace-free (zero cold start) — in both
    call shapes a served batch takes: node logits and per-graph readout."""
    from repro.api import trace_count
    from repro.runtime import ProgramStore

    reqs = _stream(6)
    with AsyncEngine(
        DIMS, params, window_ms=5.0, store=ProgramStore(tmp_path),
        readout=readout,
    ) as a:
        assert all(r.ok for r in a.submit(reqs))
    # revive: fresh engine on the same store
    with AsyncEngine(
        DIMS, params, window_ms=5.0, store=ProgramStore(tmp_path),
        readout=readout,
    ) as b:
        rep = b.precompile()
        assert rep.n_shapes > 0
        assert rep.n_searches == 0  # every program came from the store
        before = trace_count()
        compiles = sum(w.engine.stats().n_compiles for w in b.workers)
        res = b.submit(reqs)
        assert all(r.ok for r in res)
        assert trace_count() == before  # warm path: zero new traces
        # ... and no backend compile either: the primed executable is the
        # very one the served batch asks for
        assert sum(w.engine.stats().n_compiles for w in b.workers) == compiles
    for r, q in zip(res, reqs):
        want = (q.graph.n_nodes, DIMS[-1][1]) if readout is None else (
            DIMS[-1][1],)
        assert np.shape(r.output) == want


# ---------------------------------------------------------------------------
# Multi-device lane (subprocess, 4 forced host devices)
# ---------------------------------------------------------------------------

MULTI_DEVICE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from repro.graphs import TABLE4
    from repro.graphs.datasets import make_graph
    from repro.runtime import (
        AsyncEngine, FaultInjector, FaultRule, InferenceEngine, Request,
    )

    assert jax.device_count() == 4, jax.devices()
    DIMS = [(16, 8)]
    rng = np.random.default_rng(0)
    names = ["mutag", "imdb-bin", "collab"]
    reqs = []
    for i in range(24):
        g = make_graph(TABLE4[names[i % 3]], rng)
        x = rng.normal(size=(g.n_nodes, 16)).astype(np.float32)
        reqs.append(Request(graph=g, x=x, rid=i))

    sync = InferenceEngine(DIMS)
    params = sync.init(jax.random.PRNGKey(0))
    sync_res = sync.submit(reqs)

    # (a) + (b): distinct buckets -> distinct devices, outputs bit-identical
    with AsyncEngine(DIMS, params, window_ms=10.0) as a:
        res = a.submit(reqs)
    placement = a.placement()
    homes = [devs[0] for devs in placement.values()]
    assert len(placement) >= 3, placement
    # distinct buckets spread one per device while free devices remain
    assert len(set(homes)) == min(len(homes), 4), (
        "distinct buckets must land on distinct devices: %r" % placement)
    for r, s in zip(res, sync_res):
        assert r.status == s.status == "ok", (r.rid, r.status, r.error)
        assert np.array_equal(r.output, s.output), r.rid
    assert len({r.device for r in res}) >= 3, {r.device for r in res}
    print("PLACEMENT-OK")

    # (c) fault isolation across devices: a sticky injected fault pinned to
    # one bucket (hence one device) fails those requests typed, while every
    # request on the other devices stays bit-identical to the fault-free run
    target = sorted(
        set((r.bucket for r in res)), key=lambda b: (b[0], b[1]))[0]
    inj = FaultInjector(rules=[
        FaultRule(kind="exception", bucket=tuple(target), max_fires=None),
    ])
    with AsyncEngine(
        DIMS, params, window_ms=10.0, fault_injector=inj,
        check_numerics=True,
    ) as c:
        chaos = c.submit(reqs)
    n_failed = 0
    for r, clean in zip(chaos, res):
        if clean.bucket == target:
            assert r.status == "failed", (r.rid, r.status)
            assert r.error_type == "kernel_fault", r.error_type
            n_failed += 1
        else:
            assert r.status == "ok", (r.rid, r.status, r.error)
            assert r.device == clean.device, (r.device, clean.device)
            assert np.array_equal(r.output, clean.output), r.rid
    assert n_failed > 0
    print("FAULT-ISOLATION-OK")
    """
)


def test_multi_device_placement_identity_and_isolation():
    """ISSUE satellite: under 4 forced host devices — (a) distinct buckets
    on distinct devices, (b) bit-identical to the sync single-device
    engine, (c) faults on one device never perturb another."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", MULTI_DEVICE_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "PLACEMENT-OK" in r.stdout, (r.stdout[-2000:], r.stderr[-2000:])
    assert "FAULT-ISOLATION-OK" in r.stdout, (
        r.stdout[-2000:], r.stderr[-2000:])
