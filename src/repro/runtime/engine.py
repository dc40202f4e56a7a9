"""Serving runtime: a request stream -> bucketized batches -> compiled Programs.

The executable stack below this module is single-graph: ``repro.compile``
searches + lowers one :class:`~repro.api.Program` per graph, and every
distinct input shape costs a fresh XLA compile.  Real GNN serving traffic
is the opposite shape — many small graphs, few distinct sizes (the paper
batches 64/32 graphs per inference, Sec. 5.1.2).  The
:class:`InferenceEngine` turns the stream into batched device work:

1. **Admit**: every request is validated at the boundary
   (:func:`repro.runtime.resilience.validate_request` — CSR invariants,
   float32 features) and checked against the policy's oversized-graph caps
   and the ``max_inflight_graphs`` load-shedding limit.  A request that
   fails admission returns a typed ``rejected`` :class:`Result`; it never
   joins a batch, so it cannot poison healthy neighbors.
2. **Route**: every admitted request's graph maps to a pow2 padding bucket
   (:class:`repro.graphs.batching.BucketPolicy`).
3. **Assemble**: up to ``max_graphs`` same-bucket graphs become one
   block-diagonal micro-batch with per-graph segment ids
   (:func:`repro.graphs.batching.assemble`), padded so every batch of a
   bucket presents identical device shapes.  Per-request deadlines are
   enforced here: an expired request fails with ``DeadlineExceeded``
   instead of occupying a slot.
4. **Compile-or-load**: one Program per (workload fingerprint, bucket,
   tier, hw) key through an LRU cache — the mapper search and the XLA
   compile are paid once per bucket, not once per request.  With a
   persistent :class:`~repro.runtime.store.ProgramStore` attached they
   are paid once per bucket *ever*: fresh compiles persist to disk,
   restarts load instead of searching, and
   :meth:`InferenceEngine.precompile` replays the recorded
   :class:`~repro.graphs.batching.TrafficProfile` at startup so even the
   XLA traces happen off the request path (zero-cold-start serving).
   Serving, warm-up and re-ranking call a Program with one call shape
   (:meth:`InferenceEngine._batch_kwargs`), so the executable a warm-up
   primes is the one a request asks for.
5. **Execute with fault isolation**: each micro-batch — and each
   oversized request served through a partition plan — walks the
   degradation ladder (:func:`repro.runtime.resilience.default_ladder` —
   searched+Pallas -> searched+jnp -> default schedule) with bounded
   retries per tier, in one loop (:meth:`InferenceEngine._execute_ladder`);
   non-finite outputs raise instead of returning
   silently.  A multi-graph batch that faults at every tier is re-run
   request by request (**solo-retry quarantine**), so one poisoned request
   fails alone with a typed status while its neighbors still return
   bit-identical outputs.  ``submit()`` never raises for a per-request
   cause.

The engine reports graphs/sec, p50/p99 request latency and the full
resilience ledger — per-status counts, retries, downgrades, straggler
batches, and an error-taxonomy histogram (:meth:`InferenceEngine.stats`);
``benchmarks/serve_gnn.py`` holds the throughput evidence (and, under
``--chaos``, the fault-isolation evidence) against naive per-graph
compile+run.
"""
from __future__ import annotations

import itertools
import json
import logging
import time
from collections import OrderedDict
from dataclasses import asdict, dataclass, field, replace as dc_replace
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..api import (
    Program,
    compile as _compile,
    compile_count,
    layer_workloads,
    trace_count,
)
from ..core.hw import AcceleratorConfig, DEFAULT_ACCEL, DEFAULT_LATENCY, LatencyModel
from ..core.schedule import ModelSchedule
from ..gnn.layers import DEFAULT_HEADS, init_layers
from ..kernels.common import measure_wall, resolve_use_pallas
from ..graphs.batching import (
    BucketPolicy,
    GraphBatch,
    TrafficProfile,
    assemble,
    bucketize,
    next_pow2,
)
from ..graphs.csr import CSRGraph, block_diagonal, from_edges
from .fault_tolerance import StragglerMonitor
from .faults import FaultInjector
from .store import ProgramStore, store_key
from .resilience import (
    STATUS_DEGRADED,
    STATUS_FAILED,
    STATUS_OK,
    STATUS_REJECTED,
    DeadlineExceeded,
    EngineOverloaded,
    NumericalFault,
    OversizedGraph,
    RetryPolicy,
    ServingError,
    Tier,
    as_serving_error,
    backlog_retry_after,
    default_ladder,
    validate_request,
)

log = logging.getLogger("repro.runtime")


@dataclass(frozen=True)
class Request:
    """One inference request: a graph and its node features.

    ``deadline_s`` is an optional per-request latency budget, measured
    from ``submit()`` entry; a request whose deadline has already expired
    when its micro-batch assembles fails with ``DeadlineExceeded`` instead
    of occupying batch slots.
    """

    graph: CSRGraph
    x: np.ndarray  # (n_nodes, f_in) float32
    rid: int = 0
    deadline_s: float | None = None


@dataclass(frozen=True)
class Result:
    """Per-request output plus serving metadata.

    ``status`` is the per-request verdict (see
    :mod:`repro.runtime.resilience`): ``ok`` / ``degraded`` carry an
    ``output`` (the ``readout`` vector ``(f_out,)`` — or the
    ``(n_nodes, f_out)`` node logits when the engine runs with
    ``readout=None``); ``rejected`` / ``failed`` carry ``None`` plus the
    typed cause in ``error_type`` (taxonomy code) and ``error`` (message).
    """

    rid: int
    output: np.ndarray | None
    bucket: tuple[int, int] | None
    latency_s: float  # this request's enqueue -> result wall time
    status: str = STATUS_OK
    error: str | None = None
    error_type: str | None = None
    tier: str | None = None  # execution tier that produced the output
    n_retries: int = 0
    retry_after_s: float | None = None  # backpressure hint on shed load
    #: which device served this request (the engine's ``device_label``;
    #: the async front-end sets one per worker).  ``None`` = default.
    device: str | None = None
    #: partitioned-lane telemetry: how many partitions served this
    #: request (0 = the normal batched path), the partitioned wall
    #: clock, and the planner's chosen plan kind
    #: (``row_stream`` / ``feature_chunk`` / ``pp_shard``).
    n_partitions: int = 0
    partition_wall_s: float = 0.0
    plan: str | None = None

    @property
    def ok(self) -> bool:
        """True when ``output`` is a served answer (ok or degraded)."""
        return self.status in (STATUS_OK, STATUS_DEGRADED)

    @classmethod
    def from_error(
        cls,
        rid: int,
        err: ServingError,
        latency_s: float,
        bucket: tuple[int, int] | None = None,
        **extras,
    ) -> "Result":
        """The typed non-``ok`` result of one request that ``err`` ended;
        ``extras`` are further fields (retries, partition telemetry)."""
        return cls(
            rid=rid,
            output=None,
            bucket=bucket,
            latency_s=latency_s,
            status=err.status,
            error=str(err),
            error_type=err.code,
            retry_after_s=getattr(err, "retry_after_s", None),
            **extras,
        )


@dataclass
class EngineStats:
    """Aggregate serving report: throughput, latency percentiles, and the
    resilience ledger (statuses, retries, downgrades, stragglers).

    ``p50_ms`` / ``p99_ms`` are **per-request** enqueue -> result wall
    times (a request that waits behind earlier micro-batches of the same
    ``submit`` call — or in the async front-end's arrival queue — is
    charged that wait), not per-micro-batch wall; ``batch_p50_ms`` is the
    per-micro-batch median for comparison."""

    n_requests: int
    n_batches: int
    n_buckets: int
    wall_s: float
    graphs_per_sec: float
    p50_ms: float
    p99_ms: float
    search_s: float  # mapper search + Program packaging (cold buckets)
    trace_s: float  # wall of executions that took new XLA traces/compiles
    cache_hits: int
    cache_misses: int
    cache_evictions: int
    n_searches: int = 0  # mapper searches actually run (store hits skip them)
    store_hits: int = 0  # programs loaded from the persistent store
    store_misses: int = 0
    store_corrupt: int = 0  # artifacts that existed but failed to load
    n_ok: int = 0
    n_rejected: int = 0
    n_failed: int = 0
    n_degraded: int = 0
    n_retries: int = 0  # execution attempts repeated after a fault
    n_downgrades: int = 0  # micro-batches that left their preferred tier
    n_solo_retries: int = 0  # quarantine re-runs of single requests
    n_stragglers: int = 0  # micro-batches flagged by the StragglerMonitor
    errors: dict = field(default_factory=dict)  # taxonomy code -> count
    batch_p50_ms: float = 0.0  # median micro-batch wall (drain-rate probe)
    n_partitioned: int = 0  # oversized requests served via a partition plan
    partition_wall_s: float = 0.0  # wall spent inside the partitioned lane
    partition_plans: dict = field(default_factory=dict)  # plan kind -> count
    n_compiles: int = 0  # backend compiles taken by executions and primes
    #: Σ V_pad x D of the padded ELL over bound micro-batches, and Σ of
    #: their real nonzeros: ``ell_slots_used / ell_slots`` is the share of
    #: slots the aggregation kernels walk (they skip each row's padding)
    ell_slots: int = 0
    ell_slots_used: int = 0
    #: Σ over bound micro-batches of real nonzeros x heads x layers: the
    #: (edge, head) pairs a gat model scores (0 for other kinds)
    attn_edge_heads: int = 0

    def as_dict(self) -> dict:
        return asdict(self)


@dataclass
class PrecompileReport:
    """What :meth:`InferenceEngine.precompile` did at startup: how many
    bucket shapes it warmed, how many Programs came from the persistent
    store vs fresh compiles (and how many of those ran the mapper), how
    many XLA traces it took off the request path, and the wall clock."""

    n_shapes: int = 0
    n_store_hits: int = 0
    n_compiled: int = 0  # store misses compiled in-process
    n_searches: int = 0  # mapper searches among the compiles
    n_traces: int = 0  # XLA traces taken while warming
    wall_s: float = 0.0

    def as_dict(self) -> dict:
        return asdict(self)


@dataclass
class RerankReport:
    """What :meth:`InferenceEngine.rerank_topk` did: how many hot buckets
    it re-ranked, how many candidate schedules it measured, which buckets
    swapped to a measured-faster schedule (``swaps`` maps ``"VxD"`` to the
    incumbent/winner digests and walls), and how many XLA traces the whole
    pass took — all off the request path."""

    n_buckets: int = 0
    n_candidates: int = 0  # candidate schedules compiled and measured
    n_swapped: int = 0  # buckets whose pinned schedule changed
    n_traces: int = 0  # XLA traces taken while measuring + re-priming
    wall_s: float = 0.0
    swaps: dict = field(default_factory=dict)  # "VxD" -> swap detail

    def as_dict(self) -> dict:
        return asdict(self)


class ProgramCache:
    """LRU over compiled Programs, keyed by (fingerprint, bucket, hw)."""

    def __init__(self, capacity: int = 32):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._programs: OrderedDict[tuple, Program] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._programs)

    def get(self, key: tuple) -> Program | None:
        prog = self._programs.get(key)
        if prog is None:
            self.misses += 1
            return None
        self._programs.move_to_end(key)
        self.hits += 1
        return prog

    def peek(self, key: tuple) -> Program | None:
        """Non-counting lookup (used to derive tier twins)."""
        return self._programs.get(key)

    def items(self) -> list[tuple[tuple, Program]]:
        """Non-counting listing, least recently used first."""
        return list(self._programs.items())

    def put(self, key: tuple, prog: Program) -> None:
        self._programs[key] = prog
        self._programs.move_to_end(key)
        while len(self._programs) > self.capacity:
            self._programs.popitem(last=False)
            self.evictions += 1


def _chunks(seq: list, size: int):
    for i in range(0, len(seq), size):
        yield seq[i : i + size]


#: process-wide micro-batch ids: the ``batch`` of the ``repro.assemble``,
#: ``repro.stage``, ``repro.bind`` and ``repro.execute`` profiler spans
next_batch_id = itertools.count().__next__


class InferenceEngine:
    """Bucketized multi-graph serving over an LRU of compiled Programs.

    One engine serves one model (``dims`` layer shapes + ``params``) under
    one objective on one accelerator config.  ``schedule`` pins an
    explicit :class:`~repro.core.schedule.ModelSchedule` for every bucket;
    by default each bucket's first micro-batch runs the model-level mapper
    search once and the LRU amortizes it over the stream.

    ``readout`` is the per-graph reduction (``"mean"``/``"sum"``/``"max"``)
    — or ``None`` to return per-graph node logits instead.

    Resilience knobs:

    * ``retry`` — bounded backoff per ladder tier
      (:class:`~repro.runtime.resilience.RetryPolicy`);
    * ``use_pallas`` — whether the preferred tier runs the Pallas kernels
      (``None``: exactly when JAX's default backend is the TPU);
    * ``ladder`` — explicit degradation tiers (default:
      :func:`~repro.runtime.resilience.default_ladder` of ``use_pallas``);
      the first step down from a Pallas tier is logged with its cause;
    * ``max_inflight_graphs`` — admission-control cap per ``submit`` call;
      excess requests are shed with ``rejected`` + ``retry_after_s``;
    * ``fault_injector`` — a
      :class:`~repro.runtime.faults.FaultInjector` consulted at the
      compile and run boundaries (chaos testing);
    * ``check_numerics`` — treat non-finite outputs as faults (retried,
      then ``failed``) instead of returning them silently;
    * ``monitor`` — per-micro-batch latency
      :class:`~repro.runtime.fault_tolerance.StragglerMonitor`;
    * ``store`` — a persistent
      :class:`~repro.runtime.store.ProgramStore` backing the LRU:
      compiled Programs and the traffic profile survive the process, and
      :meth:`precompile` warms the recorded bucket grid at startup.
    """

    def __init__(
        self,
        dims: Sequence[tuple[int, int]],
        params=None,
        *,
        kind: str = "gcn",
        heads: int = DEFAULT_HEADS,
        objective: str = "cycles",
        hw: AcceleratorConfig = DEFAULT_ACCEL,
        policy: BucketPolicy = BucketPolicy(),
        schedule: ModelSchedule | None = None,
        cache_capacity: int = 32,
        use_pallas: bool | None = None,
        readout: str | None = "mean",
        retry: RetryPolicy = RetryPolicy(max_retries=2, backoff_s=0.0),
        ladder: Sequence[Tier] | None = None,
        max_inflight_graphs: int | None = None,
        fault_injector: FaultInjector | None = None,
        check_numerics: bool = True,
        monitor: StragglerMonitor | None = None,
        store: ProgramStore | None = None,
        device_label: str | None = None,
        partition_oversized: bool = False,
        max_partitions: int = 256,
    ):
        self.dims = [(int(fi), int(fo)) for fi, fo in dims]
        if not self.dims:
            raise ValueError("engine needs at least one layer shape")
        self.params = params
        self.kind = kind
        #: attention heads of every layer of a ``gat`` model
        self.heads = int(heads)
        self.objective = objective
        self.hw = hw
        self.policy = policy
        self.schedule = schedule
        self.use_pallas = resolve_use_pallas(use_pallas)
        self.readout = readout
        self.retry = retry
        self.ladder = (
            tuple(ladder) if ladder is not None
            else default_ladder(self.use_pallas)
        )
        if not self.ladder:
            raise ValueError("the degradation ladder needs at least one tier")
        self.max_inflight_graphs = max_inflight_graphs
        self.injector = fault_injector
        self.check_numerics = check_numerics
        #: stamped on every Result this engine produces (the async
        #: front-end labels each per-device engine with its jax device).
        self.device_label = device_label
        #: serve oversized admissions through a planner-chosen partition
        #: (:func:`repro.graphs.partition.plan_partition`) instead of a
        #: typed rejection.  Off by default: the PR 6 rejection contract
        #: stays intact unless a deployment opts in.
        if partition_oversized and kind == "gat":
            raise ValueError("the partitioned lane has no gat layer")
        self.partition_oversized = partition_oversized
        self.max_partitions = max_partitions
        self.monitor = monitor if monitor is not None else StragglerMonitor()
        self.cache = ProgramCache(cache_capacity)
        #: optional persistent backing for the program cache: a miss here
        #: consults the store before compiling, and every fresh compile is
        #: persisted, so a restarted engine loads instead of searching.
        self.store = store
        #: recorded bucket traffic.  Seeded from the store's persisted
        #: profile (bucket heat survives the process) and re-persisted
        #: after every ``submit``; ``precompile()`` replays it at startup.
        self.profile: TrafficProfile = TrafficProfile()
        if store is not None:
            prior = store.load_profile()
            if prior is not None:
                self.profile = prior
        # a fitted latency model calibrates every schedule this engine
        # searches.  When the caller left ``hw.latency`` at the identity
        # default, resolve one: the ``REPRO_LATENCY_MODEL`` env override
        # first, then the store's fitted model for the running jax
        # backend (written by ``repro.core.calibrate.calibrate``).  An
        # explicit non-default ``hw.latency`` always wins.
        if self.hw.latency == DEFAULT_LATENCY:
            lm = LatencyModel.from_env()
            if lm is None and store is not None:
                from ..core.calibrate import backend_fingerprint

                lm = store.load_latency_model(backend_fingerprint())
            if lm is not None:
                self.hw = dc_replace(self.hw, latency=lm)
        #: searched schedules keyed by (v_bucket, d_bucket): the mapper
        #: runs once per bucket; slot-count variants of the bucket (partial
        #: tail batches) reuse the schedule and only pay their XLA compile.
        self._schedules: dict[tuple[int, int], ModelSchedule] = {}
        # accumulators behind stats()
        self._latencies: list[float] = []  # per-request enqueue -> result
        self._batch_walls: list[float] = []  # per-micro-batch wall times
        self._buckets_seen: set[tuple[int, int]] = set()
        self._n_requests = 0
        self._n_batches = 0
        self._wall_s = 0.0
        self._search_s = 0.0  # mapper search + Program packaging
        self._trace_s = 0.0  # wall of executions that took new XLA traces
        self._n_compiles = 0  # backend compiles taken by those executions
        self._ell_slots = 0  # padded-ELL slots of bound micro-batches
        self._ell_slots_used = 0  # their real nonzeros
        self._attn_edge_heads = 0  # (edge, head) pairs scored (gat)
        self._n_searches = 0  # mapper searches actually run
        self._status_counts = {s: 0 for s in
                               (STATUS_OK, STATUS_REJECTED, STATUS_FAILED,
                                STATUS_DEGRADED)}
        self._errors: dict[str, int] = {}
        self._n_retries = 0
        self._n_downgrades = 0
        self._pallas_step_down_logged = False
        self._n_solo_retries = 0
        #: per-bucket micro-batch sequence numbers (fault-injection plans
        #: target (bucket, batch_index); solo-retry batches get their own)
        self._batch_seq: dict[tuple[int, int], int] = {}
        #: partition plans keyed by the graph's nominal bucket — planning
        #: (a few mapper searches) is paid once per oversized shape class
        self._plans: dict[tuple[int, int], "PartitionPlan"] = {}
        self._n_partitioned = 0
        self._partition_wall_s = 0.0
        self._partition_plans: dict[str, int] = {}

    @property
    def f_in(self) -> int:
        return self.dims[0][0]

    def init(self, rng: jax.Array):
        """Initialize (and adopt) model parameters for the served dims."""
        self.params = init_layers(self.kind, rng, self.dims, heads=self.heads)
        return self.params

    # -- program cache -------------------------------------------------------
    def _shape_key(
        self, v_bucket: int, v_total: int, d_bucket: int, tier: Tier
    ) -> tuple:
        return (
            tuple(self.dims),
            self.kind,
            self.objective,
            (tier.use_pallas, tier.searched),
            # v_bucket AND v_total: buckets whose v_bucket * slots products
            # coincide (e.g. 32x2 and 64x1) must not share a Program
            (v_bucket, v_total, d_bucket),
            # canonical JSON string: asdict(hw) nests the latency-model
            # mapping, which is not hashable as a tuple of items
            json.dumps(asdict(self.hw), sort_keys=True),
        )

    def _cache_key(self, batch: GraphBatch, tier: Tier) -> tuple:
        return self._shape_key(
            batch.v_bucket, batch.v_total, batch.d_bucket, tier
        )

    def _store_key(self, batch: GraphBatch, tier: Tier) -> dict:
        """The persistent twin of :meth:`_cache_key` (see
        :func:`repro.runtime.store.store_key`)."""
        return store_key(
            self.dims,
            (batch.v_bucket, batch.d_bucket),
            batch.v_total,
            kind=self.kind,
            objective=self.objective,
            use_pallas=tier.use_pallas,
            searched=tier.searched,
            hw=self.hw,
            heads=self.heads if self.kind == "gat" else None,
        )

    def _default_schedule(self) -> ModelSchedule:
        """The ladder's last rung: a fixed sp_opt/AC schedule (seq/CA for
        ``gat``, which runs no other order) that needs no mapper search
        and no Pallas toolchain."""
        if self.kind == "gat":
            return ModelSchedule.from_policies("seq", "CA", self.dims)
        return ModelSchedule.from_policies("sp_opt", "AC", self.dims)

    def _workloads(self, graph: CSRGraph) -> list:
        return layer_workloads(graph.nnz, self.dims, kind=self.kind,
                               heads=self.heads)

    def _program_for(self, batch: GraphBatch, tier: Tier) -> Program:
        """Compile — or load — the bucket's Program for one ladder tier.

        Resolution order on a memory-cache miss: the persistent
        :class:`~repro.runtime.store.ProgramStore` (a restarted engine
        loads the searched schedule instead of re-running the mapper; a
        corrupt artifact is a counted miss, never a crash), then the
        cached Pallas twin via :meth:`Program.degraded`, then a fresh
        compile — which is persisted back to the store atomically.  The
        mapper searches on the bucket's first micro-batch; later batches
        of the bucket reuse the schedule *and* the jitted executables
        (the Program's exec cache is shared across ``bind``).
        """
        key = self._cache_key(batch, tier)
        prog = self.cache.get(key)
        if prog is None:
            if self.injector is not None:
                self.injector.on_compile((batch.v_bucket, batch.d_bucket))
            bucket = (batch.v_bucket, batch.d_bucket)
            skey = None
            if self.store is not None:
                skey = self._store_key(batch, tier)
                prog = self.store.get(skey)
            if prog is None:
                t0 = time.perf_counter()
                twin = None
                if tier.searched and not tier.use_pallas:
                    pallas_tier = Tier("pallas+searched", True, True)
                    twin = self.cache.peek(
                        self._cache_key(batch, pallas_tier)
                    )
                if twin is not None:
                    prog = twin.degraded(use_pallas=False)
                else:
                    wls = self._workloads(batch.graph)
                    if tier.searched:
                        sched = self.schedule or self._schedules.get(bucket)
                    else:
                        sched = self._default_schedule()
                    if tier.searched and sched is None:
                        self._n_searches += 1
                    prog = _compile(
                        wls,
                        hw=self.hw,
                        objective=self.objective,
                        schedule=sched,
                        kind=self.kind,
                        use_pallas=tier.use_pallas,
                    )
                self._search_s += time.perf_counter() - t0
                if skey is not None:
                    self.store.put(skey, prog)
            if tier.searched:
                self._schedules.setdefault(bucket, prog.schedule)
            self.cache.put(key, prog)
        return prog

    def programs(self) -> list[tuple[tuple[int, int, int], Program]]:
        """The cached Programs, least recently used first, each with the
        ``(v_bucket, v_total, d_bucket)`` shape it was compiled for."""
        return [(key[4], prog) for key, prog in self.cache.items()]

    def _batch_kwargs(self, batch: GraphBatch) -> dict:
        """The batching arguments of :meth:`Program.run
        <repro.api.Program.run>` / :meth:`~repro.api.Program.prime` for one
        micro-batch: none for node logits (``readout=None``), else the
        per-graph readout over the padded slot count, not ``n_graphs`` —
        the executable's shape then depends only on the bucket, so tail
        batches at any fill level reuse it (pad segments are sliced off
        after the run).  Every run and prime of the engine goes through
        this, so a primed executable is the one a request asks for."""
        if self.readout is None:
            return {}
        return dict(
            segment_ids=jnp.asarray(batch.segment_ids),
            num_segments=batch.slots,
            readout=self.readout,
        )

    # -- ahead-of-time warmup ------------------------------------------------
    def _synthetic_batch(
        self, v_bucket: int, d_bucket: int, slots: int
    ) -> GraphBatch:
        """A stand-in micro-batch with the bucket's exact device shapes:
        ``slots`` member graphs of ``v_bucket`` nodes each (rings, or
        isolated self-loops when the degree bucket is too narrow for a
        ring), so binding at ``pad_degree=d_bucket`` and reading out over
        ``slots`` segments warms precisely the executable a real batch of
        this shape will request.  Only shapes matter here — the adjacency
        values never reach a served answer."""
        if d_bucket >= 3 and v_bucket >= 3:
            src = np.arange(v_bucket)
            dst = (src + 1) % v_bucket
            member = from_edges(
                v_bucket, np.concatenate([src, dst]), np.concatenate([dst, src])
            )
        else:
            member = from_edges(
                v_bucket, np.zeros(0, np.int64), np.zeros(0, np.int64)
            )
        batched = block_diagonal([member] * slots)
        segment_ids = np.repeat(
            np.arange(slots, dtype=np.int32), v_bucket
        )
        return GraphBatch(
            graph=batched,
            segment_ids=segment_ids,
            sizes=np.full(slots, v_bucket, dtype=np.int64),
            v_bucket=v_bucket,
            d_bucket=d_bucket,
        )

    def precompile(
        self,
        profile: TrafficProfile | None = None,
        *,
        max_shapes: int | None = None,
    ) -> PrecompileReport:
        """Warm the expected bucket grid ahead of traffic, hottest first.

        For every ``((v_bucket, d_bucket), slots)`` shape the
        :class:`~repro.graphs.batching.TrafficProfile` recorded (argument,
        else the store's persisted profile, else this engine's own), the
        preferred ladder tier's Program is compiled-or-loaded through the
        store-backed cache and its executable traced on a synthetic batch
        via :meth:`Program.prime <repro.api.Program.prime>` — so a revived
        engine pays mapper search *zero* times (store hits) and takes
        every XLA trace here, off the request path: the first real request
        of a warm shape re-traces nothing (``repro.trace_count()`` delta
        of 0) and runs at warm-path latency.  ``max_shapes`` bounds
        startup work to the hottest shapes.
        """
        if self.params is None:
            raise ValueError(
                "engine has no params; pass params= or call engine.init(rng)"
            )
        if profile is None and self.store is not None:
            profile = self.store.load_profile()
        if profile is None:
            profile = self.profile
        rep = PrecompileReport()
        t0 = time.perf_counter()
        shapes = profile.hot_shapes()
        if max_shapes is not None:
            shapes = shapes[:max_shapes]
        tier = self.ladder[0]
        hits0 = self.store.hits if self.store is not None else 0
        searches0 = self._n_searches
        misses0 = self.cache.misses
        for (v_bucket, d_bucket), slots in shapes:
            batch = self._synthetic_batch(v_bucket, d_bucket, slots)
            self._buckets_seen.add((v_bucket, d_bucket))
            prog = self._program_for(batch, tier)
            bound = prog.bind(batch.graph, pad_degree=batch.d_bucket)
            compiles0 = compile_count()
            t_run = time.perf_counter()
            n_new = bound.prime(self.params, **self._batch_kwargs(batch))
            n_compiles = compile_count() - compiles0
            self._n_compiles += n_compiles
            if n_new or n_compiles:
                self._trace_s += time.perf_counter() - t_run
            rep.n_shapes += 1
            rep.n_traces += n_new
        rep.n_store_hits = (
            (self.store.hits - hits0) if self.store is not None else 0
        )
        rep.n_searches = self._n_searches - searches0
        # shapes already warm in the memory cache cost neither a store
        # load nor a compile, so count compiles off the cache-miss delta
        rep.n_compiled = (self.cache.misses - misses0) - rep.n_store_hits
        rep.wall_s = time.perf_counter() - t0
        return rep

    # -- measured re-ranking -------------------------------------------------
    def rerank_topk(
        self,
        *,
        top_k: int = 4,
        max_shapes: int | None = None,
        min_improvement: float = 0.03,
        warmup: int = 1,
        iters: int = 5,
    ) -> RerankReport:
        """Re-rank every hot bucket's schedule by *measured* wall time.

        The mapper search behind each bucket minimizes the analytic cost
        model; a calibrated :class:`~repro.core.hw.LatencyModel` narrows
        the model<->hardware gap but cannot close it per schedule.  This
        pass closes the loop with actual measurements, entirely off the
        request path:

        1. for each hot bucket (hottest first, bounded by ``max_shapes``),
           take the mapper's analytic top-k
           (:func:`~repro.core.mapper.search_model_topk`) plus the
           incumbent schedule;
        2. compile each candidate with a *pinned* schedule (no search)
           and measure it on a synthetic batch of the bucket's hottest
           slot count via :func:`~repro.kernels.common.measure_wall`
           (the serving call shape, :meth:`_batch_kwargs`); every
           measurement lands in the profile's observation
           ledger (:meth:`TrafficProfile.record_wall
           <repro.graphs.batching.TrafficProfile.record_wall>`);
        3. when the best candidate beats the incumbent by more than
           ``min_improvement`` (hysteresis against timer noise), hot-swap
           the bucket: pin the winner in the per-bucket schedule map,
           overwrite the memory-cache entry *and* the store artifact for
           every recorded slot variant, and re-prime the serving
           executables in the same call shape — so the
           next real request of the bucket re-traces nothing
           (``repro.trace_count()`` delta of 0 on the request path).
        """
        if self.params is None:
            raise ValueError(
                "engine has no params; pass params= or call engine.init(rng)"
            )
        from ..core.mapper import search_model_topk

        rep = RerankReport()
        t0 = time.perf_counter()
        traces0 = trace_count()
        tier = self.ladder[0]
        shapes = self.profile.hot_shapes()
        if max_shapes is not None:
            shapes = shapes[:max_shapes]
        # the hottest slot variant of each bucket carries the measurement
        # (hot_shapes is hottest-first); the other variants only get
        # re-primed when the bucket swaps
        hot_slots: dict[tuple[int, int], int] = {}
        variants: dict[tuple[int, int], list[int]] = {}
        for bucket, slots in shapes:
            hot_slots.setdefault(bucket, slots)
            variants.setdefault(bucket, []).append(slots)

        for bucket, slots in hot_slots.items():
            rep.n_buckets += 1
            v_bucket, d_bucket = bucket
            batch = self._synthetic_batch(v_bucket, d_bucket, slots)
            incumbent = self._program_for(batch, tier)
            wls = self._workloads(batch.graph)
            x = jnp.zeros((batch.graph.n_nodes, self.f_in), jnp.float32)
            kw = self._batch_kwargs(batch)

            def measure(prog: Program) -> float:
                bound = prog.bind(batch.graph, pad_degree=batch.d_bucket)
                wall = measure_wall(
                    lambda: bound.run(self.params, x, **kw),
                    warmup=warmup, iters=iters,
                )
                self.profile.record_wall(
                    bucket, batch.slots, prog.schedule_digest, wall
                )
                return wall

            walls: dict[str, tuple[float, Program]] = {
                incumbent.schedule_digest: (measure(incumbent), incumbent)
            }
            for cand in search_model_topk(
                wls, hw=self.hw, objective=self.objective, top_k=top_k
            ):
                dig = cand.digest()
                if dig in walls:
                    continue
                prog = _compile(
                    wls,
                    hw=self.hw,
                    objective=self.objective,
                    schedule=cand,
                    kind=self.kind,
                    use_pallas=tier.use_pallas,
                )
                rep.n_candidates += 1
                walls[dig] = (measure(prog), prog)
            best_dig, (best_wall, best_prog) = min(
                walls.items(), key=lambda kv: kv[1][0]
            )
            inc_wall = walls[incumbent.schedule_digest][0]
            if (
                best_dig == incumbent.schedule_digest
                or best_wall >= inc_wall * (1.0 - min_improvement)
            ):
                continue
            rep.n_swapped += 1
            self._schedules[bucket] = best_prog.schedule
            rep.swaps[f"{v_bucket}x{d_bucket}"] = {
                "from": incumbent.schedule_digest,
                "to": best_dig,
                "incumbent_wall_s": inc_wall,
                "winner_wall_s": best_wall,
                "improvement": 1.0 - best_wall / inc_wall,
            }
            for sv in variants[bucket]:
                vb = self._synthetic_batch(v_bucket, d_bucket, sv)
                self.cache.put(self._cache_key(vb, tier), best_prog)
                if self.store is not None:
                    self.store.put(self._store_key(vb, tier), best_prog)
                bound = best_prog.bind(vb.graph, pad_degree=vb.d_bucket)
                bound.prime(self.params, **self._batch_kwargs(vb))
        if self.store is not None:
            self.store.save_profile(self.profile)
        rep.n_traces = trace_count() - traces0
        rep.wall_s = time.perf_counter() - t0
        return rep

    # -- admission -----------------------------------------------------------
    def median_batch_wall(self) -> float:
        """Recent median micro-batch wall time (the engine's drain rate);
        a conservative 50 ms before the first batch completes."""
        if not self._batch_walls:
            return 0.05
        return float(np.median(self._batch_walls[-50:]))

    def _retry_after_hint(self, queue_depth: int) -> float:
        """Backpressure hint for shed load, proportional to the backlog:
        the number of micro-batches the queued graphs represent times the
        recent median batch wall — not just one request's latency — so
        shed clients back off long enough for the queue to actually drain."""
        return backlog_retry_after(
            queue_depth, self.median_batch_wall(), self.policy.max_graphs
        )

    def oversized_reason(self, graph: CSRGraph) -> str | None:
        """Why ``graph`` exceeds this engine's admission limits, or
        ``None`` — the policy caps plus the simulator's footprint check
        against ``hw.gb_capacity_bytes`` (the widest served layer sets
        the staged-intermediate width)."""
        f_max = max(max(fi, fo) for fi, fo in self.dims)
        return self.policy.oversized_reason(graph, f=f_max, hw=self.hw)

    def _admission_error(
        self, req: Request, inflight_units: int
    ) -> ServingError | None:
        """Validity, size and load checks for one request.
        ``inflight_units`` is the work already admitted this call in
        batch-slot units (a partitioned giant counts ``n_partitions``)."""
        try:
            validate_request(req, self.f_in)
            reason = self.oversized_reason(req.graph)
            if reason is not None:
                raise OversizedGraph(f"request {req.rid}: {reason}")
            if (
                self.max_inflight_graphs is not None
                and inflight_units >= self.max_inflight_graphs
            ):
                hint = self._retry_after_hint(inflight_units)
                raise EngineOverloaded(
                    f"request {req.rid}: engine at max_inflight_graphs="
                    f"{self.max_inflight_graphs}; retry after {hint:.3f}s",
                    retry_after_s=hint,
                )
        except ServingError as e:
            return e
        return None

    # -- bookkeeping ---------------------------------------------------------
    def _record(self, results: list, pos: int, res: Result) -> None:
        """Store one request's Result and count its status and, for a
        typed failure (:meth:`Result.from_error`), its taxonomy code."""
        if self.device_label is not None and res.device is None:
            res = dc_replace(res, device=self.device_label)
        results[pos] = res
        self._status_counts[res.status] += 1
        if res.error_type is not None:
            code = res.error_type
            self._errors[code] = self._errors.get(code, 0) + 1

    # -- serving -------------------------------------------------------------
    def submit(self, requests: Sequence[Request]) -> list[Result]:
        """Serve a slice of the stream: admit -> route -> assemble -> run.

        Requests are grouped by bucket and chunked into
        ``policy.max_graphs``-sized micro-batches; every request's latency
        is its own enqueue -> result wall time (bucket-cold compiles and
        time spent waiting behind earlier micro-batches of this call
        included, so the p99 reflects what the *request* experienced, not
        what its micro-batch cost).

        Never raises for a per-request cause: malformed, oversized, shed,
        expired or faulted requests come back as typed non-``ok``
        :class:`Result`\\ s while their healthy neighbors are served
        normally.  (A missing ``params`` is an engine misconfiguration and
        still raises.)
        """
        if self.params is None:
            raise ValueError(
                "engine has no params; pass params= or call engine.init(rng)"
            )
        t_submit = time.perf_counter()
        t_arrival = [t_submit] * len(requests)
        self._n_requests += len(requests)
        results: list[Result | None] = [None] * len(requests)

        admitted: list[int] = []
        partitioned: list[int] = []
        # admission charges *work units*, not request count: a normal
        # request is one batch slot, but an oversized request fans out
        # into plan.n_partitions device launches — charging only 1 would
        # let one giant blow straight through max_inflight_graphs
        inflight_units = 0
        for pos, req in enumerate(requests):
            err = self._admission_error(req, inflight_units)
            if err is None:
                admitted.append(pos)
                inflight_units += 1
                continue
            if self.partition_oversized and isinstance(err, OversizedGraph):
                try:
                    units = self._plan_for(req.graph).n_partitions
                except ValueError:
                    # unplannable: admit with one unit; the partitioned
                    # lane fails it with the typed OversizedGraph cause
                    units = 1
                if (
                    self.max_inflight_graphs is not None
                    and inflight_units > 0
                    and inflight_units + units > self.max_inflight_graphs
                ):
                    # over the cap *and* not first in line — shed it with
                    # a hint sized to its real backlog contribution.  An
                    # empty engine always admits one giant (units may
                    # exceed the cap outright; progress beats starvation).
                    hint = self._retry_after_hint(inflight_units + units)
                    err = EngineOverloaded(
                        f"request {req.rid}: {units} partition units would "
                        f"exceed max_inflight_graphs="
                        f"{self.max_inflight_graphs} "
                        f"({inflight_units} units in flight); "
                        f"retry after {hint:.3f}s",
                        retry_after_s=hint,
                    )
                else:
                    partitioned.append(pos)
                    inflight_units += units
                    continue
            self._record(results, pos, Result.from_error(
                req.rid, err, time.perf_counter() - t_submit
            ))

        if admitted:
            routed = bucketize(
                [requests[i].graph for i in admitted], self.policy
            )
            for bucket_key, local_idxs in routed.items():
                self._buckets_seen.add(bucket_key)
                self.profile.record_request(bucket_key, len(local_idxs))
                idxs = [admitted[j] for j in local_idxs]
                for chunk in _chunks(idxs, self.policy.max_graphs):
                    live = self._enforce_deadlines(
                        requests, chunk, bucket_key, t_arrival, results
                    )
                    if live:
                        self._serve_batch(
                            requests, live, bucket_key, results,
                            t_arrival=t_arrival,
                        )
        for pos in partitioned:
            self._serve_partitioned(requests, pos, results, t_arrival[pos])
        self._wall_s += time.perf_counter() - t_submit
        if self.store is not None:
            self.store.save_profile(self.profile)
        return results  # type: ignore[return-value]

    def serve_group(
        self,
        requests: Sequence[Request],
        t_arrival: Sequence[float] | None = None,
        *,
        pre: tuple[GraphBatch, "jax.Array"] | None = None,
        batch_id: int | None = None,
    ) -> list[Result]:
        """Serve one *pre-admitted*, same-bucket group of requests — the
        async front-end's batching-window flush path — as one micro-batch.
        A window flushes as soon as it holds ``policy.max_graphs``
        requests, so a group is never larger than one batch.

        The caller owns admission (the PR 6 contract puts it **before**
        queueing, so nothing malformed, oversized or shed ever reaches a
        window); this path re-checks nothing.  Per-request deadlines are
        enforced here, at the window, against each request's own
        ``t_arrival`` (its enqueue time, ``time.perf_counter()`` clock) —
        as are the reported latencies, so a request's latency is its
        queue wait plus its micro-batch.

        ``pre`` is an optionally pre-assembled ``(GraphBatch, features)``
        pair whose features the front-end already staged onto this
        engine's device (``jax.device_put`` ahead of dispatch, so the
        host->device transfer overlaps queueing).  It is used only when
        every request in the group is still live — a deadline drop
        changes the batch composition and falls back to re-assembly.
        ``batch_id`` is the micro-batch id the front-end gave the group
        when it flushed the window (:data:`next_batch_id`).

        Same fault contract as :meth:`submit`: never raises for a
        per-request cause.
        """
        if self.params is None:
            raise ValueError(
                "engine has no params; pass params= or call engine.init(rng)"
            )
        if not requests:
            return []
        t0 = time.perf_counter()
        if t_arrival is None:
            t_arrival = [t0] * len(requests)
        bucket_key = self.policy.bucket_of(requests[0].graph)
        self._n_requests += len(requests)
        self._buckets_seen.add(bucket_key)
        self.profile.record_request(bucket_key, len(requests))
        results: list[Result | None] = [None] * len(requests)
        idxs = list(range(len(requests)))
        live = self._enforce_deadlines(
            requests, idxs, bucket_key, t_arrival, results
        )
        if live:
            self._serve_batch(
                requests, live, bucket_key, results,
                t_arrival=t_arrival,
                pre=pre if live == idxs else None,
                batch_id=batch_id,
            )
        self._wall_s += time.perf_counter() - t0
        return results  # type: ignore[return-value]

    # -- partitioned lane ----------------------------------------------------
    def serve_partitioned(
        self, req: Request, t_arrival: float | None = None
    ) -> Result:
        """Serve one oversized request through the partitioned lane.

        The async front-end dispatches these as standalone worker items
        (they never join a batching window); same fault contract as
        :meth:`submit` — a planning or execution failure comes back as a
        typed non-``ok`` :class:`Result`, never an exception.
        """
        if self.params is None:
            raise ValueError(
                "engine has no params; pass params= or call engine.init(rng)"
            )
        t0 = time.perf_counter()
        results: list[Result | None] = [None]
        self._serve_partitioned(
            [req], 0, results, t_arrival if t_arrival is not None else t0
        )
        self._n_requests += 1
        self._wall_s += time.perf_counter() - t0
        if self.store is not None:
            self.store.save_profile(self.profile)
        return results[0]

    def _plan_for(self, graph: CSRGraph):
        """The cached partition plan for this graph's shape class."""
        key = self.policy.bucket_of(graph)
        plan = self._plans.get(key)
        if plan is None:
            from ..graphs.partition import plan_partition

            # a device-pinned worker engine (async front-end) must not
            # claim the whole mesh for a pp shard
            n_devices = 1 if self.device_label is not None else len(jax.devices())
            t0 = time.perf_counter()
            plan = plan_partition(
                graph,
                self.dims,
                self.hw,
                objective=self.objective,
                n_devices=n_devices,
                allow_monolithic=False,
                max_partitions=self.max_partitions,
                max_block_rows=self.policy.max_nodes,
            )
            self._search_s += time.perf_counter() - t0
            self._plans[key] = plan
        return plan

    def _serve_partitioned(
        self, requests, pos: int, results: list, t_arr: float
    ) -> None:
        """Plan and execute one oversized request; records the Result."""
        req = requests[pos]
        t0 = time.perf_counter()
        dl = req.deadline_s
        if dl is not None and (t0 - t_arr) > dl:
            err = DeadlineExceeded(
                f"request {req.rid}: deadline {dl:.3f}s expired "
                f"({t0 - t_arr:.3f}s elapsed) before partitioned execution"
            )
            self._record(results, pos,
                         Result.from_error(req.rid, err, t0 - t_arr))
            return
        bucket_key = self.policy.bucket_of(req.graph)
        try:
            plan = self._plan_for(req.graph)
        except ValueError as e:
            err = OversizedGraph(f"request {req.rid}: {e}")
            self._record(results, pos, Result.from_error(
                req.rid, err, time.perf_counter() - t_arr, bucket_key
            ))
            return

        done, tier_idx, n_retries, err = self._execute_ladder(
            lambda tier: self._execute_partitioned(req, plan, tier)
        )
        out, n_parts = done if err is None else (None, plan.n_partitions)
        wall = time.perf_counter() - t0
        lat = time.perf_counter() - t_arr
        self._latencies.append(lat)
        self._n_partitioned += 1
        self._partition_wall_s += wall
        self._partition_plans[plan.kind] = (
            self._partition_plans.get(plan.kind, 0) + 1
        )
        if err is not None:
            self._record(results, pos, Result.from_error(
                req.rid, err, lat, bucket_key, n_retries=n_retries,
                n_partitions=n_parts, partition_wall_s=wall, plan=plan.kind,
            ))
            return
        if tier_idx > 0:
            self._n_downgrades += 1
        tier = self.ladder[tier_idx]
        self._record(
            results, pos,
            Result(
                rid=req.rid, output=out, bucket=bucket_key, latency_s=lat,
                status=STATUS_DEGRADED if tier_idx > 0 else STATUS_OK,
                tier=tier.name, n_retries=n_retries,
                n_partitions=n_parts, partition_wall_s=wall, plan=plan.kind,
            ),
        )

    def _execute_partitioned(self, req: Request, plan, tier: Tier):
        """Execute one oversized request under its plan on one tier.

        ``row_stream`` streams halo closures through store-backed
        Programs: all partitions share one (closure-bucket) Program, each
        is bound and launched without blocking — JAX's async dispatch
        double-buffers the next partition's host-side halo gather against
        the device compute — and the per-partition ``[:n_own]`` node
        slices stitch back bit-identically to the whole-graph forward.
        Returns ``(output, n_partitions)``.
        """
        g = req.graph
        x_full = np.asarray(req.x)
        if plan.kind == "row_stream":
            from ..graphs.partition import extract_row_partitions

            parts = extract_row_partitions(g, plan.block_rows, plan.n_hops)
            d_bucket = self.policy.degree_bucket(g.max_degree)
            v_max = max(p.graph.n_nodes for p in parts)
            sub_policy = BucketPolicy(
                min_nodes=next_pow2(v_max), min_degree=d_bucket, max_graphs=1
            )
            prog = None
            pending = []
            traces_before, compiles_before = trace_count(), compile_count()
            t_run = time.perf_counter()
            for part in parts:
                batch = assemble([part.graph], sub_policy)
                if prog is None:
                    self._buckets_seen.add((batch.v_bucket, batch.d_bucket))
                    self.profile.record_request(
                        (batch.v_bucket, batch.d_bucket), 1
                    )
                    prog = self._program_for(batch, tier)
                self.profile.record_batch(
                    (batch.v_bucket, batch.d_bucket), batch.slots
                )
                bound = prog.bind(batch.graph, pad_degree=batch.d_bucket)
                x_in = jnp.asarray(batch.batch_features([x_full[part.nodes]]))
                # enqueue without blocking: the device crunches this
                # partition while the host gathers the next one's halo
                pending.append((bound.run(self.params, x_in), part.n_own))
            slices = [
                np.asarray(jax.block_until_ready(o))[:n_own]
                for o, n_own in pending
            ]
            n_compiles = compile_count() - compiles_before
            self._n_compiles += n_compiles
            if n_compiles or trace_count() > traces_before:
                self._trace_s += time.perf_counter() - t_run
            h = np.concatenate(slices, axis=0)
            n_parts = len(parts)
        elif plan.kind == "feature_chunk":
            from ..graphs.partition import feature_chunk_forward

            h = feature_chunk_forward(
                g, x_full, self.params, kind=self.kind, chunk_f=plan.chunk_f
            )
            n_parts = plan.n_partitions
        elif plan.kind == "pp_shard":
            from ..graphs.partition import pp_shard_forward

            h = pp_shard_forward(
                g, x_full, self.params, kind=self.kind,
                n_devices=plan.n_partitions,
            )
            n_parts = plan.n_partitions
        else:
            raise ValueError(f"unexpected partition plan kind {plan.kind!r}")
        if self.check_numerics and not np.isfinite(h).all():
            raise NumericalFault(
                f"non-finite values in partitioned output of request "
                f"{req.rid} (plan {plan.kind}, tier {tier.name})"
            )
        if self.readout is None:
            return h, n_parts
        from ..gnn.layers import segment_readout

        seg = jnp.zeros(h.shape[0], dtype=jnp.int32)
        out = np.asarray(
            jax.block_until_ready(
                segment_readout(jnp.asarray(h), seg, 1, reduce=self.readout)
            )
        )
        return out[0], n_parts

    def _enforce_deadlines(
        self, requests, chunk, bucket_key, t_arrival, results
    ) -> list[int]:
        """Deadline check at batch-assembly time: expired requests fail
        with ``DeadlineExceeded`` and free their batch slots."""
        live = []
        for i in chunk:
            dl = requests[i].deadline_s
            elapsed = time.perf_counter() - t_arrival[i]
            if dl is not None and elapsed > dl:
                err = DeadlineExceeded(
                    f"request {requests[i].rid}: deadline {dl:.3f}s expired "
                    f"({elapsed:.3f}s elapsed) before batch assembly"
                )
                self._record(results, i, Result.from_error(
                    requests[i].rid, err, elapsed, bucket_key
                ))
            else:
                live.append(i)
        return live

    def _serve_batch(
        self,
        requests: Sequence[Request],
        idxs: list[int],
        bucket_key: tuple[int, int],
        results: list,
        *,
        t_arrival: Sequence[float],
        solo: bool = False,
        pre: tuple[GraphBatch, "jax.Array"] | None = None,
        batch_id: int | None = None,
    ) -> None:
        """Assemble and execute one micro-batch down the ladder; on a
        whole-batch fault, quarantine by re-running each member solo.

        ``pre`` skips assembly: the front-end already built the batch and
        staged its features on this engine's device (quarantine solo
        re-runs always re-assemble — their composition differs).
        ``batch_id`` tags the batch's spans; a new one is drawn without."""
        t0 = time.perf_counter()
        if batch_id is None:
            batch_id = next_batch_id()
        if pre is not None:
            batch, x_in = pre
        else:
            with TraceAnnotation("repro.assemble", batch=batch_id):
                batch = assemble(
                    [requests[i].graph for i in idxs], self.policy
                )
                x_in = batch.batch_features([requests[i].x for i in idxs])
        self.profile.record_batch(bucket_key, batch.slots)
        rids = [requests[i].rid for i in idxs]
        batch_index = self._batch_seq.get(bucket_key, 0)
        self._batch_seq[bucket_key] = batch_index + 1

        outs, tier_idx, n_retries, err = self._execute_ladder(
            lambda tier: self._attempt(
                batch, x_in, rids, bucket_key, batch_index, batch_id, tier
            )
        )
        dt = time.perf_counter() - t0
        t_done = time.perf_counter()
        self._n_batches += 1
        self._batch_walls.append(dt)
        if solo:
            self._n_solo_retries += 1
        self.monitor.record(self._n_batches, dt)

        if err is not None:
            if len(idxs) > 1:
                # the batch is poisoned but we don't know by whom: re-run
                # every member alone so the poison fails solo and healthy
                # neighbors still get served (bit-identical outputs — the
                # block-diagonal batch computes each graph independently)
                for i in idxs:
                    self._serve_batch(
                        requests, [i], bucket_key, results,
                        t_arrival=t_arrival, solo=True,
                    )
                return
            lat = t_done - t_arrival[idxs[0]]
            self._latencies.append(lat)
            self._record(results, idxs[0], Result.from_error(
                rids[0], err, lat, bucket_key, n_retries=n_retries
            ))
            return

        tier = self.ladder[tier_idx]
        if tier_idx > 0:
            self._n_downgrades += 1
        status = STATUS_DEGRADED if tier_idx > 0 else STATUS_OK
        for i, o in zip(idxs, outs):
            lat = t_done - t_arrival[i]
            self._latencies.append(lat)
            self._record(
                results,
                i,
                Result(
                    rid=requests[i].rid,
                    output=o,
                    bucket=bucket_key,
                    latency_s=lat,
                    status=status,
                    tier=tier.name,
                    n_retries=n_retries,
                ),
            )

    def _execute_ladder(self, run_tier: Callable[[Tier], object]):
        """Walk the degradation ladder with bounded retries per tier.

        ``run_tier(tier)`` is one attempt on one tier: :meth:`_attempt`
        for a micro-batch, :meth:`_execute_partitioned` for an oversized
        request.  Returns ``(output, tier_index, n_retries, error)`` —
        ``output`` is what ``run_tier`` returned and ``error`` is ``None``
        on success; when every tier is exhausted ``output`` is ``None`` and
        ``error`` the (taxonomy-wrapped) last failure.
        """
        last: BaseException | None = None
        n_retries = 0
        for tier_idx, tier in enumerate(self.ladder):
            for attempt in range(self.retry.max_attempts):
                try:
                    return run_tier(tier), tier_idx, n_retries, None
                except Exception as e:  # noqa: BLE001 — isolate any fault
                    last = e
                    if attempt < self.retry.max_retries:
                        n_retries += 1
                        self._n_retries += 1
                        self.retry.sleep_for(attempt)
            # tier exhausted: fall through to the next rung of the ladder
            self._log_step_down(tier_idx, last)
        assert last is not None
        return None, len(self.ladder) - 1, n_retries, as_serving_error(last)

    def _log_step_down(self, tier_idx: int, cause: BaseException) -> None:
        """Log the engine's first step down off a Pallas tier, with its
        cause: a kernel the chip refuses must not hide behind the jnp
        tiers.  Every step down is still counted in ``n_downgrades``."""
        tier = self.ladder[tier_idx]
        if (
            tier.use_pallas
            and tier_idx + 1 < len(self.ladder)
            and not self._pallas_step_down_logged
        ):
            self._pallas_step_down_logged = True
            log.warning(
                "stepping down the ladder from tier %r to %r: %s: %s",
                tier.name, self.ladder[tier_idx + 1].name,
                type(cause).__name__, cause,
            )

    def _attempt(
        self,
        batch: GraphBatch,
        x_in,
        rids: list[int],
        bucket_key: tuple[int, int],
        batch_index: int,
        batch_id: int,
        tier: Tier,
    ) -> list[np.ndarray]:
        """One execution attempt on one tier (the unit of retry).

        ``x_in`` is the assembled feature block: a host ``np.ndarray`` on
        the sync path, or a ``jax.Array`` the front-end already staged on
        this engine's device.  The run leaves it intact, so retries and
        lower ladder tiers reuse it."""
        prog = self._program_for(batch, tier)
        with TraceAnnotation("repro.bind", batch=batch_id):
            bound = prog.bind(batch.graph, pad_degree=batch.d_bucket)
        self._ell_slots += bound.adj.indices.size
        # pad rows hold one zero-weight self-loop each, which no kernel walks
        nnz = np.count_nonzero(batch.graph.values)
        self._ell_slots_used += nnz
        if self.kind == "gat":
            self._attn_edge_heads += nnz * self.heads * len(self.dims)
        corrupt = None
        if self.injector is not None:
            corrupt = self.injector.on_run(
                bucket_key, batch_index, rids, tier.name
            )
        x = jnp.asarray(x_in)  # a staged jax.Array passes through as is
        traces_before, compiles_before = trace_count(), compile_count()
        t_run = time.perf_counter()
        with TraceAnnotation("repro.execute", batch=batch_id):
            out = bound.run(self.params, x, **self._batch_kwargs(batch))
            arr = np.asarray(jax.block_until_ready(out))
        wall = time.perf_counter() - t_run
        n_compiles = compile_count() - compiles_before
        self._n_compiles += n_compiles
        cold = n_compiles > 0 or trace_count() > traces_before
        if cold:
            # this wall is dominated by an XLA trace and/or a backend
            # compile (or a persistent-cache load), so attribute it to
            # trace_s — that is exactly what precompile() and the
            # compilation cache save a revived engine.
            self._trace_s += wall
        if corrupt == "nan":
            arr = self.injector.corrupt_output(arr)
        if self.check_numerics and not np.isfinite(arr).all():
            raise NumericalFault(
                f"non-finite values in the output of bucket {bucket_key} "
                f"batch {batch_index} (tier {tier.name}, rids {rids})"
            )
        if not cold and corrupt is None:
            # clean warm run: fold the measured wall into the traffic
            # profile's observation ledger keyed by the schedule that
            # produced it — the feedback half of the predicted<->measured
            # loop that rerank_topk() re-scores candidates against.
            self.profile.record_wall(
                bucket_key, batch.slots, prog.schedule_digest, wall
            )
        if self.readout is None:
            return batch.split_nodes(arr)
        return list(arr[: batch.n_graphs])

    def stats(self) -> EngineStats:
        """The serving report over everything submitted so far."""
        lat_ms = np.asarray(self._latencies, dtype=np.float64) * 1e3
        n = len(self._latencies)
        return EngineStats(
            n_requests=self._n_requests,
            n_batches=self._n_batches,
            n_buckets=len(self._buckets_seen),
            wall_s=self._wall_s,
            graphs_per_sec=n / self._wall_s if self._wall_s > 0 else 0.0,
            p50_ms=float(np.percentile(lat_ms, 50)) if n else 0.0,
            p99_ms=float(np.percentile(lat_ms, 99)) if n else 0.0,
            batch_p50_ms=(
                float(np.median(self._batch_walls)) * 1e3
                if self._batch_walls else 0.0
            ),
            search_s=self._search_s,
            trace_s=self._trace_s,
            cache_hits=self.cache.hits,
            cache_misses=self.cache.misses,
            cache_evictions=self.cache.evictions,
            n_searches=self._n_searches,
            store_hits=self.store.hits if self.store is not None else 0,
            store_misses=self.store.misses if self.store is not None else 0,
            store_corrupt=self.store.corrupt if self.store is not None else 0,
            n_ok=self._status_counts[STATUS_OK],
            n_rejected=self._status_counts[STATUS_REJECTED],
            n_failed=self._status_counts[STATUS_FAILED],
            n_degraded=self._status_counts[STATUS_DEGRADED],
            n_retries=self._n_retries,
            n_downgrades=self._n_downgrades,
            n_solo_retries=self._n_solo_retries,
            n_stragglers=len(self.monitor.flagged),
            errors=dict(self._errors),
            n_partitioned=self._n_partitioned,
            partition_wall_s=self._partition_wall_s,
            partition_plans=dict(self._partition_plans),
            n_compiles=self._n_compiles,
            ell_slots=self._ell_slots,
            ell_slots_used=self._ell_slots_used,
            attn_edge_heads=self._attn_edge_heads,
        )
