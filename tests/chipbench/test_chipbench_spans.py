"""The program's spans on the profiler's clock, and their reduction:
per-layer readings and idle gaps named by the program span over them."""
import json

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from chipbench_testlib import ROOT, result_line, run, tiny_copy
from chipbench import spans as S
from chipbench import trace as T
from test_chipbench_trace import SMALL

DATA = ROOT / "tests" / "chipbench" / "data"

#: SMALL with program spans: device 0 idles 0-10, 35-60, 70-95 and device
#: 1 idles 50-100; the program's spans cover 35-60 and 60-80
PROGRAM = [["repro.admit", 2, 3, 7], ["repro.assemble", 30, 15, 1],
           ["repro.stage", 45, 15, 1], ["repro.bind", 60, 4, 1],
           ["repro.execute", 64, 16, 1], ["repro.execute", 150, 5, 2]]
WAITS = {"window_wait_s": 0.03, "n_window_waits": 3, "inbox_wait_s": 0.0,
         "n_groups": 0}


def test_program_spans_name_the_gaps_they_overlap():
    tr = dict(SMALL, program=PROGRAM)
    gaps = S.idle_gaps(tr)
    # 35-60 lies under assemble (10 ns) and stage (15 ns); 70-95 under
    # execute (10 ns) and wait (25 ns): the program span wins where any is
    assert ["repro.stage", 25e-9] in gaps
    assert ["repro.execute", 25e-9] in gaps
    # 50-100 on device 1: stage 10 ns, bind 4, execute 16 -> execute
    assert gaps[0] == ["repro.execute", 50e-9]
    # 0-10 lies under admit (2-5): no bench.* span is needed
    assert ["repro.admit", 10e-9] in gaps


def test_gaps_without_program_spans_read_as_before():
    assert S.idle_gaps(SMALL) == T.idle_gaps(SMALL)
    assert S.idle_gaps(dict(SMALL, program=[])) == T.idle_gaps(SMALL)


def test_gap_outside_every_program_span_keeps_its_bench_name():
    tr = dict(SMALL, program=[["repro.admit", 2, 3, 7]])
    gaps = S.idle_gaps(tr)
    assert gaps[0] == ["bench.wait", 50e-9]
    assert ["repro.admit", 10e-9] in gaps


def test_layer_readings():
    r = S.layer_readings(dict(SMALL, program=PROGRAM), WAITS)
    # the second execute starts after the window (0-100) and is left out
    assert r == {"admit_us": 3e-3, "window_wait_ms": 10.0,
                 "inbox_wait_ms": None, "assemble_ms": 15e-6,
                 "stage_ms": 15e-6, "bind_ms": 4e-6, "execute_ms": 16e-6}
    assert S.phase_sum_ms(r) == pytest.approx(10.0 + 50e-6)
    none = S.layer_readings(SMALL, dict(WAITS, n_window_waits=0))
    assert set(none.values()) == {None}
    assert S.phase_sum_ms(none) is None


def test_async_engine_spans_under_the_profiler(tmp_path):
    """Every span of the serving path reaches the profiler's trace with its
    id: one micro-batch's four spans share a ``batch`` and come in order."""
    from repro.graphs import TABLE4
    from repro.graphs.datasets import make_graph
    from repro.runtime import AsyncEngine, InferenceEngine, Request

    dims = [(16, 8)]
    params = InferenceEngine(dims).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(6):
        g = make_graph(TABLE4["mutag"], rng)
        x = rng.normal(size=(g.n_nodes, 16)).astype(np.float32)
        reqs.append(Request(graph=g, x=x, rid=100 + i))
    with AsyncEngine(dims, params, window_ms=5.0) as eng:
        eng.submit(reqs[:1])  # compile off the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        with TraceAnnotation("bench.window"):
            res = eng.submit(reqs[1:])
        jax.profiler.stop_trace()
    assert all(r.status == "ok" for r in res)
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    tr = T.extract(pb)
    tr["program"] = S.program_events(pb)
    names = {n for n, *_ in tr["program"]}
    assert names == {"repro.admit", *S.BATCH_SPANS}
    admits = [i for n, _, _, i in tr["program"] if n == "repro.admit"]
    assert sorted(admits) == [r.rid for r in reqs[1:]]
    batches: dict = {}
    for n, s, d, i in tr["program"]:
        if n != "repro.admit":
            batches.setdefault(i, []).append((s, d, n))
    assert batches and all(isinstance(i, int) for i in batches)
    for evs in batches.values():
        evs.sort()
        assert [n for _, _, n in evs] == list(S.BATCH_SPANS)
        assert all(s1 >= s0 + d0 for (s0, d0, _), (s1, _, _)
                   in zip(evs, evs[1:]))
    r = S.layer_readings(tr, {f: 1 for f in ("window_wait_s",
                                              "n_window_waits",
                                              "inbox_wait_s", "n_groups")})
    assert all(v is not None and v > 0 for v in r.values())


def test_the_tool_on_a_tiny_cell(tmp_path):
    bench = tiny_copy(tmp_path)
    dump = tmp_path / "spans.json"
    rc, out, err = run(bench, "chipbench/spans.py", "--workload", "tiny-full",
                       "--seed", 2**31 + 11, "--seconds", 2,
                       "--cpu-rehearsal", "--dump", dump,
                       env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")})
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["device"]["platform"] == "cpu"
    assert list(line["end_to_end"]) == ["off", "on", "off_after"]
    assert all(set(m) == {"graphs_per_s", "latency_p95_ms"}
               for m in line["end_to_end"].values())
    r = line["program"]
    assert all(v is not None for v in r.values())
    # one client and a 10-ms window: every request waits out the window
    assert 9.0 <= r["window_wait_ms"] < 50.0
    assert line["phase_sum_ms"] <= line["latency_mean_ms"]
    assert line["compiles"] == 0
    assert json.loads(dump.read_text())["program"]


def test_recorded_chip_trace_with_program_spans():
    """A traced ``citeseer-full`` run on a TPU v5e, trimmed to 3 s: the
    program's spans give the readings they gave when it was committed, and
    name every long idle gap that ``trace.idle_gaps`` puts on the client's
    ``bench.*`` spans."""
    tr = json.loads((DATA / "spans_citeseer_full_v5e.json").read_text())
    want = tr.pop("expected")
    r = S.layer_readings(tr, {f: 0 for f in ("window_wait_s",
                                              "n_window_waits",
                                              "inbox_wait_s", "n_groups")})
    assert {k: v for k, v in r.items() if v is not None} == \
        pytest.approx(want["readings"], rel=1e-12)
    assert T.idle_share(tr) == pytest.approx(want["idle_share"], rel=1e-12)
    assert [n for n, _ in S.idle_gaps(tr)] == want["idle_gaps"]
    assert all(n.startswith("repro.") for n in want["idle_gaps"])
    assert {n for n, _ in T.idle_gaps(tr)} <= {"bench.generate",
                                                "bench.submit", "bench.wait"}
