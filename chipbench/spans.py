#!/usr/bin/env python3
"""The program's own spans and wait counters, read beside the device trace.

    python chipbench/spans.py --workload citeseer-full --seed 7 --seconds 20

The serving path opens a ``jax.profiler.TraceAnnotation`` at each layer
boundary, on the clock of the device trace: ``repro.admit`` (admission, per
request, with its ``rid``) and, per micro-batch with its ``batch`` id,
``repro.assemble`` (block-diagonal CSR and feature block), ``repro.stage``
(feature block to the device), ``repro.bind`` (padded-ELL build) and
``repro.execute`` (dispatch, device time, copy back).  ``AsyncEngineStats``
sums the time requests wait in a batching window (``window_wait_s`` over
``n_window_waits``) and the time flushed groups wait in a worker's inbox
(``inbox_wait_s`` over ``n_groups``).

:func:`program_events` keeps those spans from an ``.xplane.pb`` as
``[name, start_ns, duration_ns, id]``; :func:`layer_readings` turns them and
the counters into per-layer numbers; :func:`idle_gaps` names each of the
longest device idle gaps by the program span over it.  The harness's
result line reads none of these (``trace.extract`` keeps ``bench.*`` spans
only).  Run as a script, one process serves the same draw of the cell in
three windows, with the profiler off, on, and off again, and prints one
JSON line: the end-to-end metrics of each window (the cost of tracing), the
accepted per-layer metrics and the program's readings of the traced one,
and the idle gaps by name.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace as T  # noqa: E402

PREFIX = "repro."
#: the spans of one micro-batch, in the order it passes them
BATCH_SPANS = ("repro.assemble", "repro.stage", "repro.bind",
               "repro.execute")
#: ``AsyncEngineStats`` fields: (sum of seconds, count)
WAITS = {"window_wait": ("window_wait_s", "n_window_waits"),
         "inbox_wait": ("inbox_wait_s", "n_groups")}


def program_events(xplane_path: str | Path) -> list[list]:
    """The program's host spans, by start: ``[name, start_ns, duration_ns,
    id]``, ``id`` the span's ``batch`` or ``rid`` (None without either)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(xplane_path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        md = dict(e.stats)
                        out.append([e.name, e.start_ns, e.duration_ns,
                                    md.get("batch", md.get("rid"))])
    return sorted(out, key=lambda ev: ev[1])


def span_mean_ms(trace: dict, name: str) -> float | None:
    """Mean duration of the program spans ``name`` that start inside the
    window span, in ms; None where there is none."""
    lo, hi = trace["span"]
    d = [dur for n, s, dur, _ in trace.get("program", ())
         if n == name and lo <= s < hi]
    return sum(d) / len(d) / 1e6 if d else None


def layer_readings(trace: dict, waits: dict) -> dict:
    """``admit_us`` per request, ``window_wait_ms`` per request,
    ``inbox_wait_ms`` per group and ``<phase>_ms`` per micro-batch, from
    the trace's program spans and a window's deltas of the wait counters;
    None where there is nothing to read."""
    admit = span_mean_ms(trace, "repro.admit")
    out = {"admit_us": None if admit is None else 1e3 * admit}
    for name, (total, n) in WAITS.items():
        out[f"{name}_ms"] = 1e3 * waits[total] / waits[n] if waits[n] else None
    for name in BATCH_SPANS:
        out[f"{name[len(PREFIX):]}_ms"] = span_mean_ms(trace, name)
    return out


def phase_sum_ms(r: dict) -> float | None:
    """The serial phases of a request served alone, in ms: window wait,
    assembly, staging, ELL build, execution.  Admission is inside the
    window wait, which runs from arrival, before admission, to the flush."""
    parts = [r["window_wait_ms"], r["assemble_ms"], r["stage_ms"],
             r["bind_ms"], r["execute_ms"]]
    return None if None in parts else sum(parts)


def idle_gaps(trace: dict, k: int = 10) -> list[list]:
    """:func:`trace.idle_gaps`, each gap named by the program span that
    overlaps it most, and by the ``bench.*`` span only where no program
    span overlaps it.  Without program spans, the same as ``trace``'s."""
    program = [ev[:3] for ev in trace.get("program", ())]
    by_program = T.idle_gaps(dict(trace, host=program), k)
    return [p if p[0] != "unattributed" else b
            for p, b in zip(by_program, T.idle_gaps(trace, k))]


# -- the tool ---------------------------------------------------------------

def _waits(engine) -> dict:
    st = engine.stats()
    return {f: getattr(st, f) for pair in WAITS.values() for f in pair}


def measure(workload: str, seed: int, seconds: float, root: Path = ROOT,
            allow_cpu: bool = False, dump: Path | None = None) -> dict:
    import jax

    from chipbench.bench import (Context, Session, load_cell, load_part,
                                 read_metrics, require_devices, trace_dir)
    from repro.runtime import enable_persistent_compilation_cache

    cell = load_cell(workload, root)
    devices = require_devices(cell.chips, allow_cpu)
    enable_persistent_compilation_cache()
    sess = Session(cell, devices, seed, root)
    model = load_part("models", cell.config["model"]["kind"], root)

    def context(win, trace=None):
        return Context(cell=cell, setup_s=0.0, win=win, dims=sess.dims,
                       pallas=sess.pallas_layers(), policy=sess.policy,
                       device_kind=devices[0].device_kind, model=model,
                       trace=trace)

    end_to_end = [m for m in cell.end_to_end if m["name"] != "setup_s"]
    try:
        draw = sess.workload.draw(seed, seconds)
        sess.warm(draw)
        before = context(sess.window(draw, seconds))
        tdir = trace_dir(cell.name, root) / "spans"
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        w0 = _waits(sess.engine)
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
        win = sess.window(draw, seconds, rid0=10**6, traced=True)
        jax.profiler.stop_trace()
        w1 = _waits(sess.engine)
        after = context(sess.window(draw, seconds, rid0=2 * 10**6))
    finally:
        sess.close()
    (pb,) = sorted(tdir.glob("plugins/profile/*/*.xplane.pb"))
    trace = T.extract(pb)
    trace["program"] = program_events(pb)
    shutil.rmtree(tdir, ignore_errors=True)
    if dump is not None:
        dump.parent.mkdir(parents=True, exist_ok=True)
        dump.write_text(json.dumps(trace))
    on = context(win, trace)
    readings = layer_readings(trace, {k: w1[k] - w0[k] for k in w0})
    lat = on.latencies_ms()
    return {
        "workload": workload, "seed": seed, "seconds": seconds,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "end_to_end": {k: read_metrics(end_to_end, c, root) for k, c in
                       (("off", before), ("on", on), ("off_after", after))},
        "per_layer": read_metrics(cell.per_layer, on, root),
        "program": readings,
        "phase_sum_ms": phase_sum_ms(readings),
        "latency_mean_ms": statistics.fmean(lat) if lat else None,
        "compiles": sum(c.win.compiles for c in (before, on, after)),
        "idle_gaps": idle_gaps(trace) if trace["devices"] else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--dump", type=Path, default=None,
                    help="also write the reduced traced window as JSON here")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    out = measure(args.workload, args.seed, args.seconds,
                  allow_cpu=args.cpu_rehearsal, dump=args.dump)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
