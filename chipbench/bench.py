"""The benchmark harness: set-up, the measured window, metrics, the check.

Everything that belongs to one configuration, traffic mix or metric is
data or a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``chipbench/configs/<config>.json`` — the model and dataset;
* ``chipbench/traffic/<traffic>.json`` — loop, rate, batching policy,
  what each request is, and the limits of the output check;
* ``chipbench/loops/<loop>.py`` — how requests are sent (the traffic's
  ``loop``): due offsets from the seed, and the sending loop;
* ``chipbench/kinds/<kind>.py`` — what one request is (the traffic's
  ``requests.kind``): the graphs made in set-up and the per-seed draw;
* ``chipbench/models/<kind>.py`` — the plain reference of a model kind
  (the config's ``model.kind``) and the work of one of its layers;
* ``chipbench/metrics/<metric>.py`` — a ``read(ctx)`` that returns the
  metric's value, or ``None`` where it finds nothing to read.

The system under test is ``repro.runtime.AsyncEngine``: every request goes
through ``submit_async``.  The harness times requests on its own clock and
takes nothing from the engine's latency statistics.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import time
from concurrent.futures import wait
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import graphs as G
from .stalls import StallWatch
from .stats import percentile

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: how long after the window closes the harness waits for answers
DRAIN_S = 60.0


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


# -- the benchmark's description -------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its config, traffic and
    the metric entries it reports."""
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    (cfg_entry,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    config = _json(root / cfg_entry["file"])
    traffic = _json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def load_part(folder: str, name: str, root: Path = ROOT):
    """The module ``chipbench/<folder>/<name>.py``, found by name."""
    path = root / "chipbench" / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {folder} file for {name!r} at {path}")
    tag = f"chipbench_{folder}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = ROOT):
    """The ``read`` function of ``chipbench/metrics/<metric>.py``."""
    return load_part("metrics", metric, root).read


# -- requests ---------------------------------------------------------------

@dataclass
class Item:
    """One request as the generator prepared it: the reference's edge
    graph, the program's CSR graph and how to get the features.  Items
    with the same ``key`` carry the same graph and features."""

    edge: G.EdgeGraph
    csr: object
    table: int = -1  # feature table index
    nodes: np.ndarray | None = None  # rows of the table (subgraphs)
    x: np.ndarray | None = None  # own features (fresh graphs)
    key: object = None


@dataclass
class Draw:
    """What one seed draws: the items in sending order, the due offsets
    (seconds from the window's start; None where the loop sends on
    completion) and the feature tables."""

    items: list
    offsets: np.ndarray | None
    tables: list = field(default_factory=list)

    def features(self, it: Item) -> np.ndarray:
        if it.x is not None:
            return it.x
        t = self.tables[max(it.table, 0)]
        return t if it.nodes is None else t[it.nodes]


def stream_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


#: items a loop that sends on completion cycles through
CLOSED_SEQUENCE = 4096


class Workload:
    """A cell's traffic: its loop and request kind, found by name, the
    graphs they make in set-up (fixed by the config and traffic files) and
    the per-seed draw."""

    def __init__(self, cell: Cell, from_edges, root: Path = ROOT):
        self.cell = cell
        self.loop = load_part("loops", cell.traffic["loop"], root)
        self.kind = load_part("kinds", cell.traffic["requests"]["kind"], root)
        self.state = self.kind.prepare(cell.config["dataset"],
                                       cell.traffic["requests"], from_edges)

    def draw(self, seed: int, seconds: float) -> Draw:
        t = self.cell.traffic
        offsets = self.loop.offsets(t, seconds, seed)
        n = CLOSED_SEQUENCE if offsets is None else len(offsets)
        items, tables = self.kind.draw(self.state, self.cell.config["dataset"],
                                       t["requests"], seed, n)
        return Draw(items, offsets, tables)


# -- the run ----------------------------------------------------------------

class CompileCounter:
    """Backend compiles (and the functions compiled) and traces to jaxprs
    since registration, with the seconds the traces took."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.names: list[str] = []
        self.traces = 0
        self.trace_s = 0.0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.names.append(str(kw.get("fun_name")))
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1
                self.trace_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)


@dataclass
class Record:
    due: float
    sent: float = 0.0
    submit_s: float = 0.0
    done: float | None = None
    result: object = None
    item: Item | None = None


class Session:
    """One cell's traffic and engine; windows are driven through it.  The
    engine is built by the first :meth:`warm`, which knows the shapes."""

    def __init__(self, cell: Cell, devices, seed: int, root: Path = ROOT):
        from repro.graphs import BucketPolicy, from_edges

        self.cell = cell
        self.root = root
        self.devices = list(devices)
        self.compiles = CompileCounter()
        self.dims = [tuple(d) for d in cell.config["model"]["layers"]]
        self.workload = Workload(cell, from_edges, root)
        self.policy = BucketPolicy(max_graphs=int(cell.traffic["max_graphs"]))
        self.params = self.params_for(seed)
        self.engine = None

    def params_for(self, seed: int):
        import jax

        from repro.runtime import InferenceEngine

        eng = InferenceEngine(self.dims, kind=self.cell.config["model"]["kind"])
        return eng.init(jax.random.PRNGKey(int(seed) % 2**32))

    def reseed(self, seed: int) -> None:
        """New weights for every device's engine (the executables take the
        weights as arguments, so nothing recompiles)."""
        import jax

        self.params = self.params_for(seed)
        for w in self.engine.workers:
            w.engine.params = jax.device_put(self.params, w.device)

    def start(self, n_shapes: int) -> None:
        import inspect

        from repro.runtime import AsyncEngine, InferenceEngine, ProgramStore

        model, t = self.cell.config["model"], self.cell.traffic
        default = inspect.signature(InferenceEngine).parameters["cache_capacity"]
        self.engine = AsyncEngine(
            self.dims, self.params, devices=self.devices,
            window_ms=float(t["window_ms"]), policy=self.policy,
            kind=model["kind"], readout=model["readout"],
            store=ProgramStore(self.root / "build" / "chipbench" / "store"
                               / self.cell.config["name"]),
            # room for every shape the traffic reaches
            cache_capacity=max(default.default, n_shapes),
        )
        self.engine.start()

    def warm(self, draw: Draw) -> dict:
        """Compile (or load) and prime every (bucket, slots) shape this
        draw can reach, on the devices its buckets are placed on."""
        from repro.graphs import TrafficProfile

        t = self.cell.traffic
        top = int(t["max_graphs"])
        if (c := self.workload.loop.concurrency(t)) is not None:
            top = min(top, c)
        slots = [s for s in (2**k for k in range(8)) if s <= top]
        prof = TrafficProfile()
        for it in draw.items:
            prof.record_request(self.policy.bucket_of(it.csr))
        for b in prof.requests:
            for s in slots:
                prof.record_batch(b, s)
        if self.engine is None:
            self.start(len(prof.requests) * len(slots))
        rep = self.engine.precompile(prof)
        return {"shapes": rep.n_shapes, "store_hits": rep.n_store_hits,
                "compiled": rep.n_compiled, "searches": rep.n_searches,
                "traces": rep.n_traces}

    def n_batches(self) -> int:
        return sum(w.engine.stats().n_batches for w in self.engine.workers)

    def window(self, draw: Draw, seconds: float, rid0: int = 0,
               traced: bool = False) -> "Window":
        from jax.profiler import TraceAnnotation

        from repro.runtime import Request

        span = TraceAnnotation if traced else (lambda _: contextlib.nullcontext())
        recs: list[Record] = []
        futs = []

        def send(it: Item, due: float):
            """Build the request, wait for its due time (0: now), submit
            it; returns its future."""
            with span("bench.generate"):
                req = Request(graph=it.csr, x=draw.features(it),
                              rid=rid0 + len(recs))
            rec = Record(due=due, item=it)
            recs.append(rec)
            if due > 0:
                with span("bench.wait"):
                    while (now := time.perf_counter()) < due:
                        time.sleep(due - now)
            rec.sent = time.perf_counter()
            with span("bench.submit"):
                fut = self.engine.submit_async(req)
            rec.submit_s = time.perf_counter() - rec.sent

            def done(f, rec=rec):
                t_done = time.perf_counter()
                rec.result = f.exception() or f.result()
                rec.done = t_done
            fut.add_done_callback(done)
            futs.append((fut, rec))
            return fut

        b0, c0 = self.n_batches(), self.compiles.compiles
        tr0, trs0 = self.compiles.traces, self.compiles.trace_s
        with span("bench.window"):
            t0 = time.perf_counter()
            t_end = t0 + seconds
            self.workload.loop.drive(send, draw, self.cell.traffic, t0, t_end,
                                     span)
            t_close = time.perf_counter()
            with span("bench.wait"):
                wait([f for f, _ in futs], timeout=DRAIN_S)
                # a future wakes its waiters before it runs its callbacks
                for f, rec in futs:
                    while f.done() and rec.done is None:
                        time.sleep(1e-4)
        return Window(records=recs, t0=t0, t_end=t_end, t_close=t_close,
                      seconds=seconds,
                      n_batches=self.n_batches() - b0,
                      compiles=self.compiles.compiles - c0,
                      compiled=self.compiles.names[c0:],
                      traces=self.compiles.traces - tr0,
                      trace_s=self.compiles.trace_s - trs0)

    def memory_peak(self) -> int:
        peaks = []
        for d in self.devices:
            st = d.memory_stats() or {}
            peaks.append(int(st.get("peak_bytes_in_use", 0)))
        return max(peaks)

    def pallas_layers(self) -> dict:
        """Per (v_bucket, d_bucket): which layers resolved to a Pallas
        kernel, from the cached Programs of every device."""
        from repro.core import resolve_kernel_key

        out = {}
        for w in self.engine.workers:
            for (v_bucket, _, d_bucket), prog in w.engine.programs():
                out[(v_bucket, d_bucket)] = [
                    bool(resolve_kernel_key(s.policy, s.order, s.use_pallas)[2])
                    for s in prog.specs]
        return out

    def close(self) -> None:
        self.engine.close()


@dataclass
class Window:
    records: list
    t0: float
    t_end: float
    t_close: float
    seconds: float
    n_batches: int
    compiles: int
    compiled: list = field(default_factory=list)
    traces: int = 0
    trace_s: float = 0.0

    def ok(self, r: Record) -> bool:
        return getattr(r.result, "status", None) == "ok"

    @property
    def answered(self) -> list:
        return [r for r in self.records if r.done is not None]


# -- metrics ---------------------------------------------------------------

@dataclass
class Context:
    """What a metric reader may read."""

    cell: Cell
    setup_s: float
    win: Window
    dims: list
    pallas: dict  # (v_bucket, d_bucket) -> [bool per layer]
    policy: object
    device_kind: str
    model: object = None  # the config's models/<kind>.py
    trace: dict | None = None

    def latencies_ms(self) -> list[float]:
        """Due (open loop) or send (closed loop) to completion, for every
        request due in the window that was answered."""
        return [1e3 * (r.done - (r.due if r.due > 0 else r.sent))
                for r in self.win.answered]

    def served(self, until: float | None = None) -> list:
        """ok requests answered (before ``until`` where given)."""
        return [r for r in self.win.answered if self.win.ok(r)
                and (until is None or r.done <= until)]

    def layer_works(self, rec: Record):
        """[(Work, on_pallas)] per layer for one served request."""
        flags = self.pallas.get(self.policy.bucket_of(rec.item.csr),
                                [False] * len(self.dims))
        e = rec.item.edge
        nnz = e.nnz
        return [(self.model.layer_work(e.n, nnz, fi, fo), on)
                for (fi, fo), on in zip(self.dims, flags)]


def read_metrics(entries, ctx: Context, root: Path = ROOT) -> dict:
    out = {}
    for m in entries:
        v = load_reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# -- the check --------------------------------------------------------------

def check(cell: Cell, draw: Draw, win: Window, params, seed: int,
          dtype=None, root: Path = ROOT) -> dict:
    """Compare served answers with the reference of the config's model
    kind: every answer where the traffic's ``check.sample`` is 0, else a
    seeded sample of that many answered requests that holds the largest
    graph.  Requests that share a key share one reference.  ``dtype`` puts
    the reference computed at a lower precision in the program's place
    (the control).  Returns ``{number: (value, limit)}``."""
    from .reference import max_gap

    model = load_part("models", cell.config["model"]["kind"], root)
    lim = cell.traffic["check"]
    readout = cell.config["model"]["readout"]
    served = [r for r in win.answered if win.ok(r)]
    unanswered = len(win.records) - len(win.answered)
    numbers = {"unanswered": (unanswered, 0)}
    if not served:
        numbers["none_served"] = (1, 0)
        return numbers
    k = int(lim["sample"]) or len(served)
    if k < len(served):
        big = max(range(len(served)), key=lambda i: served[i].item.edge.n)
        rest = [i for i in range(len(served)) if i != big]
        pick = [big] + list(stream_rng(seed, 3).choice(rest, size=k - 1,
                                                        replace=False))
        served = [served[i] for i in pick]

    def key(r: Record):
        return id(r) if r.item.key is None else r.item.key
    inputs = {}
    for r in served:
        inputs.setdefault(key(r), r.item)
    keys, items = list(inputs), list(inputs.values())

    def outputs(dt):
        return dict(zip(keys, model.outputs(
            [it.edge for it in items], [draw.features(it) for it in items],
            params, readout=readout, dtype=dt)))
    refs = outputs(None)
    ctl = None if dtype is None else outputs(dtype)
    outs = [r.result.output if ctl is None else ctl[key(r)] for r in served]
    numbers["max_gap"] = (max_gap(outs, [refs[key(r)] for r in served]),
                          float(lim["max_gap"]))
    return numbers


def is_correct(numbers: dict) -> bool:
    return all(v <= lim for v, lim in numbers.values())


# -- entry ------------------------------------------------------------------

def require_devices(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise SystemExit(f"chipbench: needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips; JAX "
                         f"found {len(devs)}")
    return devs[:chips]


def trace_dir(cell: str, root: Path = ROOT) -> Path:
    return root / "build" / "chipbench" / "trace" / cell


def run(args, t_start: float, root: Path = ROOT) -> int:
    """One run of one cell; prints the result line last on stdout."""
    import jax

    from repro.runtime import enable_persistent_compilation_cache

    cell = load_cell(args.workload, root)
    devices = require_devices(cell.chips, args.cpu_rehearsal)
    enable_persistent_compilation_cache()
    t1 = time.perf_counter()
    sess = Session(cell, devices, args.seed, root)
    t2 = time.perf_counter()
    draw = sess.workload.draw(args.seed, args.seconds)
    t3 = time.perf_counter()
    warm = sess.warm(draw)
    t4 = time.perf_counter()
    print(f"set-up: imports and devices {t1 - t_start:.3f} s, graphs "
          f"{t2 - t1:.3f} s, draw {t3 - t2:.3f} s, engine and warm-up "
          f"{t4 - t3:.3f} s {json.dumps(warm)}", flush=True)
    traced = bool(args.trace)
    tdir = trace_dir(cell.name, root)
    if traced:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    # set-up's objects (graphs, the draw) stay out of the collector's scans
    gc.collect()
    gc.freeze()
    dump = open(args.watch_stalls, "w") if args.watch_stalls else None
    setup_s = time.perf_counter() - t_start
    with StallWatch(dump=dump) as watch:
        win = sess.window(draw, args.seconds, traced=traced)
    if dump is not None:
        dump.close()
    trace = None
    if traced:
        jax.profiler.stop_trace()
        from .trace import extract

        (pb,) = sorted(tdir.glob("plugins/profile/*/*.xplane.pb"))
        trace = extract(pb)
        if args.dump_trace:
            Path(args.dump_trace).parent.mkdir(parents=True, exist_ok=True)
            Path(args.dump_trace).write_text(json.dumps(trace))
        else:
            shutil.rmtree(tdir, ignore_errors=True)
    ctx = Context(cell=cell, setup_s=setup_s, win=win, dims=sess.dims,
                  pallas=sess.pallas_layers(), policy=sess.policy,
                  device_kind=devices[0].device_kind,
                  model=load_part("models", cell.config["model"]["kind"], root),
                  trace=trace)
    metrics = read_metrics(cell.per_layer if traced else cell.end_to_end,
                           ctx, root)
    lat = [r.sent - r.due for r in win.records if r.due > 0]
    n_ok = sum(win.ok(r) for r in win.records)
    print(f"requests: {len(win.records)} due in the window, "
          f"{len(win.answered)} answered, {n_ok} ok; "
          f"{win.n_batches} batches", flush=True)
    print(f"backend compiles inside the window: {win.compiles}"
          + (f" {sorted(set(win.compiled))}" if win.compiled else "")
          + f"; traces {win.traces} ({win.trace_s:.4f} s)", flush=True)
    print(watch.summary(), flush=True)
    if lat:
        print(f"generator lateness due->sent: p50 "
              f"{percentile(lat, 50) * 1e3:.4f} ms, p95 "
              f"{percentile(lat, 95) * 1e3:.4f} ms", flush=True)
    else:
        print("generator lateness: closed loop, sent on completion",
              flush=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": sess.memory_peak()}
    breakdown = None
    if trace is not None:
        from .trace import idle_gaps, mean_busy_ns, top_ops, window_ns

        busy = mean_busy_ns(trace)
        device["busy_s"] = (busy or 0.0) / 1e9
        device["window_s"] = window_ns(trace) / 1e9
        breakdown = {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}
    attempted = len(win.records)
    failed = attempted - n_ok
    params = sess.params
    sess.close()
    del sess
    gc.collect()
    numbers = check(cell, draw, win, params, args.seed, root=root)
    line = {"correct": is_correct(numbers), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in numbers.items()}
    print(json.dumps(line), flush=True)
    for k, (v, lim) in numbers.items():
        print(f"check {k}: {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    return 0
