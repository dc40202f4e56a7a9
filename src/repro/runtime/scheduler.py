"""Async continuous-batching front-end with multi-device bucket placement.

:class:`~repro.runtime.engine.InferenceEngine.submit` is synchronous and
single-device: requests only batch within one call, every bucket executes
serially on one device, and a request's latency is set by whoever it
happened to arrive with.  The paper's core claim is that spatial
accelerators win by running distinct phase dataflows *concurrently* on
partitioned compute; for a serving workload the analogous axis is
graph-level parallelism across independent inputs — distinct padding
buckets are independent compiled programs, so they can run on distinct
devices of a mesh at the same time.  This module is that front-end:

* :class:`AsyncEngine` — an arrival queue with a **batching window** per
  bucket: a window flushes when it holds ``policy.max_graphs`` graphs or
  when ``window_ms`` expires, whichever comes first.  ``submit_async``
  returns a :class:`concurrent.futures.Future` per request, so latency is
  measured per request (enqueue -> result), not per submit-chunk.
* :class:`BucketPlacer` — schedules buckets over the devices of a
  :class:`jax.sharding.Mesh` (or an explicit device list): distinct
  buckets land on distinct devices while devices remain (least-loaded by
  recorded heat), and buckets hotter than a fair device share get up to
  ``replicas`` replicas, driven by the same
  :class:`~repro.graphs.batching.TrafficProfile` heat the engine already
  records.
* **Overlapped transfers** — the flush path assembles the block-diagonal
  batch and stages its feature block onto the target device with
  :func:`jax.device_put` *before* the group reaches the device worker, so
  the host->device copy overlaps the previous batch's compute.

Contracts carried over:

* PR 6 (resilience): admission runs **before** queueing — a malformed,
  oversized or shed request resolves its future immediately with a typed
  ``rejected`` :class:`~repro.runtime.engine.Result` and never occupies a
  window slot.  Per-request deadlines are enforced at the batching window
  (:meth:`InferenceEngine.serve_group`), and the per-device engines keep
  the full ladder + solo-retry quarantine, so a poisoned request still
  fails alone with a typed status.  No code path raises for a per-request
  cause.
* PR 7 (zero cold start): every per-device engine's LRU sits on the one
  shared :class:`~repro.runtime.store.ProgramStore` (artifacts compiled on
  any device serve all of them — they are keyed by shape, not device),
  and :meth:`AsyncEngine.precompile` warms **each device's assigned
  buckets** on that device's own worker thread.

Execution model: one worker thread per device.  JAX traces/compiles hold
the GIL, but ``block_until_ready`` releases it during device execution,
so on a multi-core host the per-device streams overlap; on a single-core
container the win is continuous batching itself (requests arriving while
a batch runs form the next batch instead of serializing per call).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Sequence

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from ..gnn.pp import mesh_devices
from ..graphs.batching import TrafficProfile, assemble
from .engine import (
    EngineStats,
    InferenceEngine,
    PrecompileReport,
    Request,
    Result,
    next_batch_id,
)
from .resilience import (
    STATUS_DEGRADED,
    STATUS_FAILED,
    STATUS_OK,
    STATUS_REJECTED,
    EngineOverloaded,
    OversizedGraph,
    ServingError,
    backlog_retry_after,
    validate_request,
)


@dataclass
class AsyncEngineStats:
    """The async front-end's serving report.

    ``p50_ms`` / ``p99_ms`` are per-request enqueue -> result wall times
    across every device (front-end rejections included), so they are
    directly comparable to the sync engine's.  ``per_device`` holds each
    worker engine's own :class:`~repro.runtime.engine.EngineStats`;
    ``placement`` records which devices each bucket was assigned to.
    """

    n_requests: int = 0
    n_devices: int = 0
    wall_s: float = 0.0
    graphs_per_sec: float = 0.0
    p50_ms: float = 0.0
    p99_ms: float = 0.0
    n_ok: int = 0
    n_rejected: int = 0
    n_failed: int = 0
    n_degraded: int = 0
    n_flushes_full: int = 0  # windows flushed because they filled
    n_flushes_deadline: int = 0  # windows flushed by the window_ms clock
    max_inflight: int = 0  # high-water mark of queued+running graphs
    #: Σ (flush - arrival) over the requests of every flushed window
    window_wait_s: float = 0.0
    n_window_waits: int = 0
    #: Σ (worker pops the group - dispatch) over every dispatched group
    inbox_wait_s: float = 0.0
    n_groups: int = 0
    #: Σ over workers of EngineStats.ell_slots / ell_slots_used
    ell_slots: int = 0
    ell_slots_used: int = 0
    #: Σ over workers of EngineStats.attn_edge_heads
    attn_edge_heads: int = 0
    errors: dict = field(default_factory=dict)
    placement: dict = field(default_factory=dict)  # "VxD" -> [device labels]
    per_device: dict = field(default_factory=dict)  # label -> EngineStats dict

    def as_dict(self) -> dict:
        from dataclasses import asdict

        return asdict(self)


@dataclass
class AsyncPrecompileReport:
    """Per-device precompile roll-up: each worker warmed its *assigned*
    buckets (placer plan over the persisted profile) on its own thread."""

    n_shapes: int = 0
    n_store_hits: int = 0
    n_compiled: int = 0
    n_searches: int = 0
    n_traces: int = 0
    wall_s: float = 0.0
    per_device: dict = field(default_factory=dict)  # label -> PrecompileReport

    def as_dict(self) -> dict:
        from dataclasses import asdict

        return asdict(self)


class BucketPlacer:
    """Bucket -> device assignment over a mesh, driven by traffic heat.

    Distinct buckets go to distinct devices while free devices remain:
    a new bucket is assigned to the device carrying the least cumulative
    heat (request count), so the first ``n_devices`` buckets spread one
    per device.  A bucket whose heat share exceeds a fair device share
    (``1 / n_devices``) is *hot* and gets additional replicas — up to
    ``replicas`` — on the least-loaded devices that don't already serve
    it.  Dispatch picks the assigned replica with the fewest outstanding
    graphs.

    The placer is deliberately greedy and incremental: assignments only
    grow (a bucket never migrates), so per-device executable caches stay
    warm and placement is deterministic for a given arrival order.  Not
    thread-safe by itself — the :class:`AsyncEngine` serializes calls
    under its own lock.
    """

    def __init__(
        self, n_devices: int, *, replicas: int = 1, min_heat: int = 32
    ):
        if n_devices < 1:
            raise ValueError(f"need at least one device, got {n_devices}")
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        self.n_devices = n_devices
        self.replicas = min(replicas, n_devices)
        #: minimum absolute heat before a bucket can widen — a bucket's
        #: first few arrivals dominate any share computation, so expansion
        #: waits for a statistically meaningful sample
        self.min_heat = min_heat
        #: bucket -> ordered device indices serving it (first = home)
        self.assignment: dict[tuple[int, int], list[int]] = {}
        #: cumulative request heat per bucket / per device
        self.heat: dict[tuple[int, int], int] = {}
        self.device_heat: list[int] = [0] * n_devices
        #: outstanding (queued or running) graphs per device
        self.outstanding: list[int] = [0] * n_devices

    def _least_loaded(self, exclude: Sequence[int] = ()) -> int:
        """Device with the least heat (ties -> lowest index) not excluded."""
        best = None
        for d in range(self.n_devices):
            if d in exclude:
                continue
            if best is None or self.device_heat[d] < self.device_heat[best]:
                best = d
        assert best is not None
        return best

    def record(self, bucket: tuple[int, int], n: int = 1) -> None:
        """Account ``n`` arrivals to ``bucket``: assign it on first sight,
        and widen hot buckets up to ``replicas`` devices."""
        self.heat[bucket] = self.heat.get(bucket, 0) + n
        homes = self.assignment.get(bucket)
        if homes is None:
            homes = [self._least_loaded()]
            self.assignment[bucket] = homes
        self.device_heat[homes[0]] += n
        if (
            self.replicas > 1
            and len(homes) < self.replicas
            and self.heat[bucket] >= self.min_heat
        ):
            total = sum(self.heat.values())
            if total > 0 and self.heat[bucket] / total > 1.0 / self.n_devices:
                extra = self._least_loaded(exclude=homes)
                if extra not in homes:
                    homes.append(extra)

    def plan(self, profile: TrafficProfile) -> None:
        """Seed the assignment from a recorded profile, hottest bucket
        first — the startup twin of :meth:`record`, so ``precompile`` can
        warm each device's buckets before traffic arrives."""
        for bucket, n in profile.heat():
            self.record(bucket, n)

    def pick(self, bucket: tuple[int, int], n_graphs: int) -> int:
        """The device index to dispatch this flush to: the bucket's
        assigned replica with the fewest outstanding graphs.  Registers
        the ``n_graphs`` as outstanding (release with :meth:`done`)."""
        homes = self.assignment.get(bucket)
        if homes is None:  # dispatch before record (defensive)
            self.record(bucket, 0)
            homes = self.assignment[bucket]
        d = min(homes, key=lambda i: (self.outstanding[i], homes.index(i)))
        self.outstanding[d] += n_graphs
        return d

    def done(self, device: int, n_graphs: int) -> None:
        self.outstanding[device] = max(0, self.outstanding[device] - n_graphs)

    def buckets_for(self, device: int) -> set[tuple[int, int]]:
        """Every bucket assigned (home or replica) to ``device``."""
        return {b for b, homes in self.assignment.items() if device in homes}


class _Window:
    """One open batching window: same-bucket requests waiting to flush."""

    __slots__ = ("bucket", "requests", "arrivals", "futures", "deadline",
                 "batch_id")

    def __init__(self, bucket: tuple[int, int], deadline: float):
        self.bucket = bucket
        self.requests: list[Request] = []
        self.arrivals: list[float] = []
        self.futures: list[Future] = []
        self.deadline = deadline  # perf_counter time to force-flush
        self.batch_id: int | None = None  # drawn when the window flushes


class _DeviceWorker(threading.Thread):
    """One device's serving loop: owns a per-device
    :class:`InferenceEngine` (its own LRU + executable caches, the shared
    store underneath) and drains dispatched groups in FIFO order under
    ``jax.default_device`` so every trace, transfer and execution lands on
    its device."""

    def __init__(self, index: int, device, engine: InferenceEngine, owner):
        super().__init__(name=f"repro-worker-{index}", daemon=True)
        self.index = index
        self.device = device
        self.engine = engine
        self.owner = owner
        self.inbox: "list" = []  # (dispatch time, item)
        self.cv = threading.Condition()

    def dispatch(self, item) -> None:
        with self.cv:
            self.inbox.append((time.perf_counter(), item))
            self.cv.notify()

    def run(self) -> None:
        with jax.default_device(self.device):
            if self.engine.params is not None:
                # commit the params once; every batch then reads them
                # device-locally instead of re-transferring
                self.engine.params = jax.device_put(
                    self.engine.params, self.device
                )
            while True:
                with self.cv:
                    while not self.inbox:
                        self.cv.wait()
                    t_dispatch, item = self.inbox.pop(0)
                if item is None:
                    return
                kind, payload, fut = item
                try:
                    if kind == "group":
                        self.owner._note_inbox_wait(
                            time.perf_counter() - t_dispatch
                        )
                        reqs, arrivals, pre, batch_id = payload
                        out = self.engine.serve_group(
                            reqs, arrivals, pre=pre, batch_id=batch_id
                        )
                    else:  # "call": run an arbitrary thunk on this device
                        out = payload()
                    fut.set_result(out)
                except BaseException as e:  # noqa: BLE001 — worker survives
                    fut.set_exception(e)


class AsyncEngine:
    """Continuous-batching serving front-end over a device mesh.

    ::

        engine = AsyncEngine(dims, params, mesh=mesh, window_ms=10)
        engine.start()
        futs = [engine.submit_async(r) for r in requests]
        results = [f.result() for f in futs]
        engine.close()

    ``submit_async`` admits the request (PR 6 boundary checks + a
    ``max_queue_graphs`` backlog cap with a queue-depth-proportional
    ``retry_after_s``), then parks it in its bucket's batching window.
    The window flushes to a device when it fills to ``policy.max_graphs``
    or its ``window_ms`` deadline expires — so under load p99 tracks the
    window, not the batch that happened to contain the request.

    Every per-device engine is an :class:`InferenceEngine` built from the
    same kwargs (the shared ``store`` included) and labelled with its
    device; ``max_inflight_graphs`` is dropped, because admission is the
    front-end's.
    """

    def __init__(
        self,
        dims: Sequence[tuple[int, int]],
        params=None,
        *,
        mesh: "jax.sharding.Mesh | None" = None,
        devices: Sequence | None = None,
        window_ms: float = 10.0,
        replicas: int = 1,
        max_queue_graphs: int | None = None,
        **engine_kwargs,
    ):
        self.devices = mesh_devices(mesh, list(devices) if devices else None)
        if not self.devices:
            raise ValueError("no devices to place buckets on")
        self.window_s = float(window_ms) / 1e3
        self.max_queue_graphs = max_queue_graphs
        # admission is the front-end's job — per-engine shedding would
        # double-count a stream that is already capped at the queue
        engine_kwargs.pop("max_inflight_graphs", None)
        self.workers: list[_DeviceWorker] = []
        for i, dev in enumerate(self.devices):
            eng = InferenceEngine(
                dims, params, device_label=str(dev), **engine_kwargs
            )
            self.workers.append(_DeviceWorker(i, dev, eng, self))
        e0 = self.workers[0].engine
        self.policy = e0.policy
        self.f_in = e0.f_in
        self.store = e0.store
        self.placer = BucketPlacer(len(self.devices), replicas=replicas)
        #: merged bucket heat across devices (persisted to the store on
        #: close; worker engines never save their partial profiles)
        self.profile: TrafficProfile = e0.profile
        for w in self.workers[1:]:
            w.engine.profile = TrafficProfile()  # don't double-seed heat
        self._lock = threading.Lock()
        self._windows: dict[tuple[int, int], _Window] = {}
        self._inflight = 0  # graphs admitted but not yet resolved
        self._max_inflight = 0
        self._rid = 0
        self._n_requests = 0
        self._n_flushes_full = 0
        self._n_flushes_deadline = 0
        self._window_wait_s = 0.0
        self._n_window_waits = 0
        self._inbox_wait_s = 0.0
        self._n_groups = 0
        self._fe_latencies: list[float] = []  # front-end rejections
        self._fe_status = {s: 0 for s in
                           (STATUS_OK, STATUS_REJECTED, STATUS_FAILED,
                            STATUS_DEGRADED)}
        self._fe_errors: dict[str, int] = {}
        self._wall_t0: float | None = None
        self._wall_t1: float = 0.0
        self._started = False
        self._closed = False
        self._flusher = threading.Thread(
            target=self._flush_loop, name="repro-flusher", daemon=True
        )
        self._flush_cv = threading.Condition(self._lock)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "AsyncEngine":
        if self._started:
            return self
        self._started = True
        for w in self.workers:
            w.start()
        self._flusher.start()
        return self

    def close(self) -> None:
        """Flush every open window, drain the workers, persist the merged
        traffic profile.  Idempotent."""
        if self._closed or not self._started:
            self._closed = True
            return
        self._closed = True
        final: list[tuple[int, list]] = []
        with self._lock:
            for bucket in list(self._windows):
                flushed = self._flush_locked(bucket, "deadline")
                if flushed is not None:
                    final.append(flushed)
            self._flush_cv.notify_all()
        for widx, wins in final:
            self._stage_and_dispatch(widx, wins)
        self._flusher.join(timeout=10.0)
        # sentinel after all groups: workers drain FIFO then exit
        for w in self.workers:
            w.dispatch(None)
        for w in self.workers:
            w.join(timeout=30.0)
        self._persist_profile()

    def __enter__(self) -> "AsyncEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def _persist_profile(self) -> None:
        if self.store is not None:
            merged = self.profile
            for w in self.workers[1:]:
                merged = merged.merge(w.engine.profile)
            self.profile = merged
            for w in self.workers[1:]:
                w.engine.profile = TrafficProfile()
            self.store.save_profile(merged)

    # -- admission (PR 6: before queueing) -----------------------------------
    def _admission_error(self, req: Request) -> ServingError | None:
        try:
            validate_request(req, self.f_in)
            reason = self.workers[0].engine.oversized_reason(req.graph)
            if reason is not None:
                raise OversizedGraph(f"request {req.rid}: {reason}")
            if (
                self.max_queue_graphs is not None
                and self._inflight >= self.max_queue_graphs
            ):
                hint = backlog_retry_after(
                    self._inflight,
                    self._median_batch_wall(),
                    self.policy.max_graphs,
                )
                raise EngineOverloaded(
                    f"request {req.rid}: queue at max_queue_graphs="
                    f"{self.max_queue_graphs}; retry after {hint:.3f}s",
                    retry_after_s=hint,
                )
        except ServingError as e:
            return e
        return None

    def _median_batch_wall(self) -> float:
        walls: list[float] = []
        for w in self.workers:
            walls.extend(w.engine._batch_walls[-50:])
        if not walls:
            return 0.05
        return float(np.median(walls))

    # -- enqueue -------------------------------------------------------------
    def submit_async(self, req: Request) -> "Future[Result]":
        """Admit ``req`` and park it in its bucket's batching window.

        Returns a future resolving to this request's
        :class:`~repro.runtime.engine.Result`.  Admission failures resolve
        immediately (typed ``rejected`` result, never an exception) —
        nothing inadmissible ever occupies a window slot.
        """
        if not self._started or self._closed:
            raise RuntimeError("AsyncEngine is not running (call start())")
        fut: "Future[Result]" = Future()
        t_arrival = time.perf_counter()
        flush_now: tuple[int, list] | None = None
        part_widx: int | None = None
        with self._lock:
            if self._wall_t0 is None:
                self._wall_t0 = t_arrival
            self._n_requests += 1
            with TraceAnnotation("repro.admit", rid=req.rid):
                err = self._admission_error(req)
            if (
                err is not None
                and isinstance(err, OversizedGraph)
                and self.workers[0].engine.partition_oversized
            ):
                # beyond-capacity single graph: route to the partitioned
                # lane on the least-loaded device instead of rejecting
                res = None
                self._inflight += 1
                self._max_inflight = max(self._max_inflight, self._inflight)
                part_widx = min(
                    range(len(self.workers)),
                    key=lambda i: self.placer.outstanding[i],
                )
                self.placer.outstanding[part_widx] += 1
            elif err is not None:
                lat = time.perf_counter() - t_arrival
                res = Result.from_error(req.rid, err, lat)
                self._fe_status[err.status] += 1
                self._fe_errors[err.code] = self._fe_errors.get(err.code, 0) + 1
                self._fe_latencies.append(lat)
                self._wall_t1 = time.perf_counter()
            else:
                res = None
                bucket = self.policy.bucket_of(req.graph)
                self.placer.record(bucket)
                self._inflight += 1
                self._max_inflight = max(self._max_inflight, self._inflight)
                win = self._windows.get(bucket)
                if win is None:
                    win = _Window(bucket, t_arrival + self.window_s)
                    self._windows[bucket] = win
                    self._flush_cv.notify()  # new earliest deadline maybe
                win.requests.append(req)
                win.arrivals.append(t_arrival)
                win.futures.append(fut)
                if len(win.requests) >= self.policy.max_graphs:
                    flush_now = self._flush_locked(bucket, "full")
        if res is not None:
            fut.set_result(res)  # outside the lock
        elif part_widx is not None:
            worker = self.workers[part_widx]
            done: "Future[Result]" = Future()
            done.add_done_callback(
                self._make_partition_resolver(part_widx, fut)
            )
            worker.dispatch((
                "call",
                lambda e=worker.engine, r=req, t=t_arrival:
                    e.serve_partitioned(r, t),
                done,
            ))
        elif flush_now is not None:
            self._stage_and_dispatch(*flush_now)
        return fut

    def submit(self, requests: Sequence[Request]) -> list[Result]:
        """Synchronous convenience: enqueue everything, wait for all."""
        futs = [self.submit_async(r) for r in requests]
        return [f.result() for f in futs]

    def make_request(self, graph, x, **kw) -> Request:
        """A :class:`Request` with a fresh front-end-assigned rid."""
        with self._lock:
            rid = self._rid
            self._rid += 1
        return Request(graph=graph, x=x, rid=rid, **kw)

    # -- flush ---------------------------------------------------------------
    def _flush_locked(self, bucket: tuple[int, int], reason: str):
        """Pop the bucket's window (lock held) and pick its device; the
        caller stages + dispatches outside the lock."""
        win = self._windows.pop(bucket, None)
        if win is None or not win.requests:
            return None
        widx = self.placer.pick(bucket, len(win.requests))
        win.batch_id = next_batch_id()
        now = time.perf_counter()
        self._window_wait_s += sum(now - t for t in win.arrivals)
        self._n_window_waits += len(win.arrivals)
        if reason == "full":
            self._n_flushes_full += 1
        else:
            self._n_flushes_deadline += 1
        return widx, [win]

    def _stage_and_dispatch(self, widx: int, wins: list) -> None:
        """Assemble + stage each flushed window onto its device, then hand
        it to the worker.  Runs on the enqueueing/flusher thread so the
        host->device transfer overlaps the device's current batch."""
        worker = self.workers[widx]
        for win in wins:
            try:
                with TraceAnnotation("repro.assemble", batch=win.batch_id):
                    batch = assemble(
                        [r.graph for r in win.requests], self.policy
                    )
                    x_np = batch.batch_features([r.x for r in win.requests])
                # place (don't commit) the feature block on the target
                # device: committed-ness is part of the jit dispatch key,
                # and precompile's prime warms the uncommitted variant — a
                # committed device_put here would pay a fresh XLA compile
                # per shape despite the warm cache
                with (
                    TraceAnnotation("repro.stage", batch=win.batch_id),
                    jax.default_device(worker.device),
                ):
                    pre = (batch, jax.numpy.asarray(x_np))
            except Exception:
                pre = None  # fall back to in-engine assembly
            done: "Future[list[Result]]" = Future()
            done.add_done_callback(
                self._make_resolver(widx, win.futures, len(win.requests))
            )
            worker.dispatch((
                "group",
                (win.requests, win.arrivals, pre, win.batch_id),
                done,
            ))

    def _note_inbox_wait(self, wait_s: float) -> None:
        with self._lock:
            self._inbox_wait_s += wait_s
            self._n_groups += 1

    def _make_resolver(self, widx: int, futures: list, n: int):
        def _resolve(done: "Future") -> None:
            exc = done.exception()
            results = None if exc is not None else done.result()
            with self._lock:
                self._inflight -= n
                self.placer.done(widx, n)
                self._wall_t1 = time.perf_counter()
            if exc is not None:
                # engine misconfiguration (serve_group's only raise path);
                # surface it on every waiting future
                for f in futures:
                    f.set_exception(exc)
                return
            for f, r in zip(futures, results):
                f.set_result(r)

        return _resolve

    def _make_partition_resolver(self, widx: int, fut: "Future"):
        def _resolve(done: "Future") -> None:
            exc = done.exception()
            with self._lock:
                self._inflight -= 1
                self.placer.done(widx, 1)
                self._wall_t1 = time.perf_counter()
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(done.result())

        return _resolve

    def _flush_loop(self) -> None:
        """Deadline clock: sleep until the earliest open window expires,
        flush everything due, repeat."""
        while True:
            with self._lock:
                if self._closed and not self._windows:
                    return
                now = time.perf_counter()
                due: list[tuple[int, list]] = []
                next_deadline = None
                for bucket in list(self._windows):
                    win = self._windows[bucket]
                    if win.deadline <= now:
                        flushed = self._flush_locked(bucket, "deadline")
                        if flushed is not None:
                            due.append(flushed)
                    elif (
                        next_deadline is None or win.deadline < next_deadline
                    ):
                        next_deadline = win.deadline
                if not due:
                    timeout = (
                        None if next_deadline is None
                        else max(0.0, next_deadline - now)
                    )
                    self._flush_cv.wait(timeout=timeout)
                    continue
            for widx, wins in due:
                self._stage_and_dispatch(widx, wins)

    # -- startup warmth (PR 7) -----------------------------------------------
    def precompile(
        self,
        profile: TrafficProfile | None = None,
        *,
        max_shapes: int | None = None,
    ) -> AsyncPrecompileReport:
        """Warm each device's *assigned* buckets on its own worker thread.

        The placer is seeded from the (persisted) profile, then every
        worker precompiles the profile subset its device was assigned —
        so a revived multi-device engine takes all of its XLA traces off
        the request path, and no device wastes startup warming a bucket
        it will never be handed.
        """
        if not self._started:
            raise RuntimeError("call start() before precompile()")
        if profile is None and self.store is not None:
            profile = self.store.load_profile()
        if profile is None:
            profile = self.profile
        with self._lock:
            self.placer.plan(profile)
            subsets = [
                profile.subset(self.placer.buckets_for(i))
                for i in range(len(self.workers))
            ]
        t0 = time.perf_counter()
        futs: list[Future] = []
        for w, sub in zip(self.workers, subsets):
            fut: Future = Future()
            futs.append(fut)
            w.dispatch((
                "call",
                (lambda e=w.engine, s=sub: e.precompile(
                    s, max_shapes=max_shapes
                )),
                fut,
            ))
        rep = AsyncPrecompileReport()
        for w, fut in zip(self.workers, futs):
            r: PrecompileReport = fut.result()
            rep.n_shapes += r.n_shapes
            rep.n_store_hits += r.n_store_hits
            rep.n_compiled += r.n_compiled
            rep.n_searches += r.n_searches
            rep.n_traces += r.n_traces
            rep.per_device[str(w.device)] = r.as_dict()
        rep.wall_s = time.perf_counter() - t0
        return rep

    # -- reporting -----------------------------------------------------------
    def placement(self) -> dict[str, list[str]]:
        """Bucket -> device labels, for inspection and tests."""
        with self._lock:
            return {
                f"{v}x{d}": [str(self.devices[i]) for i in homes]
                for (v, d), homes in sorted(self.placer.assignment.items())
            }

    def stats(self) -> AsyncEngineStats:
        """Merged per-request report across every device worker."""
        with self._lock:
            lat = list(self._fe_latencies)
            status = dict(self._fe_status)
            errors = dict(self._fe_errors)
            n_requests = self._n_requests
            wall = (
                (self._wall_t1 - self._wall_t0)
                if self._wall_t0 is not None else 0.0
            )
            n_full = self._n_flushes_full
            n_deadline = self._n_flushes_deadline
            max_inflight = self._max_inflight
            waits = dict(
                window_wait_s=self._window_wait_s,
                n_window_waits=self._n_window_waits,
                inbox_wait_s=self._inbox_wait_s,
                n_groups=self._n_groups,
            )
        per_device: dict[str, EngineStats] = {}
        n_served = ell_slots = ell_slots_used = attn_edge_heads = 0
        for w in self.workers:
            s = w.engine.stats()
            per_device[str(w.device)] = s
            lat.extend(w.engine._latencies)
            status[STATUS_OK] += s.n_ok
            status[STATUS_REJECTED] += s.n_rejected
            status[STATUS_FAILED] += s.n_failed
            status[STATUS_DEGRADED] += s.n_degraded
            n_served += s.n_ok + s.n_degraded
            ell_slots += s.ell_slots
            ell_slots_used += s.ell_slots_used
            attn_edge_heads += s.attn_edge_heads
            for code, n in s.errors.items():
                errors[code] = errors.get(code, 0) + n
        lat_ms = np.asarray(lat, dtype=np.float64) * 1e3
        return AsyncEngineStats(
            n_requests=n_requests,
            n_devices=len(self.devices),
            wall_s=wall,
            graphs_per_sec=n_served / wall if wall > 0 else 0.0,
            p50_ms=float(np.percentile(lat_ms, 50)) if len(lat_ms) else 0.0,
            p99_ms=float(np.percentile(lat_ms, 99)) if len(lat_ms) else 0.0,
            n_ok=status[STATUS_OK],
            n_rejected=status[STATUS_REJECTED],
            n_failed=status[STATUS_FAILED],
            n_degraded=status[STATUS_DEGRADED],
            n_flushes_full=n_full,
            n_flushes_deadline=n_deadline,
            max_inflight=max_inflight,
            **waits,
            ell_slots=ell_slots,
            ell_slots_used=ell_slots_used,
            attn_edge_heads=attn_edge_heads,
            errors=errors,
            placement=self.placement(),
            per_device={k: v.as_dict() for k, v in per_device.items()},
        )
