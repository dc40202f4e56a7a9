"""Whole runs of tiny cells on the CPU, in child processes: the result
line's schema, cells and a metric added as files, the refusal without a
TPU."""
import json

import pytest

from chipbench_testlib import ROOT, run, result_line, tiny_copy

RUN = "chipbench/run.py"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("chipbench"))


def _end_to_end(bench, cell: str) -> set:
    """The end-to-end metrics ``BENCHMARK.json`` gives the cell."""
    spec = json.loads((bench / "BENCHMARK.json").read_text())
    return {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}


def _schema(line: dict, trace: bool) -> None:
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert ("breakdown" in line) == trace
    assert line["device"]["platform"] == "cpu"
    assert isinstance(line["device"]["memory_peak_bytes"], int)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_no_tpu_no_result():
    rc, out, err = run(ROOT, RUN, "--workload", "citeseer-full", "--seed", 1,
                       "--seconds", 1)
    assert rc != 0 and "needs a TPU" in err
    assert "{" not in out


def test_only_the_benchmark_files_no_result(tmp_path):
    bench = tiny_copy(tmp_path)
    rc, out, _ = run(bench, RUN, "--workload", "tiny-full", "--seed", 1,
                     "--seconds", 1, "--cpu-rehearsal", src=False)
    assert rc != 0 and "{" not in out


def test_closed_loop_end_to_end(bench):
    rc, out, err = run(bench, RUN, "--workload", "tiny-full", "--seed",
                       2**31 + 3, "--seconds", 2, "--trace", 0,
                       "--cpu-rehearsal")
    assert rc == 0, err[-3000:]
    line = result_line(out)
    _schema(line, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == _end_to_end(bench, "tiny-full")
    assert {"setup_s", "graphs_per_s", "latency_p95_ms"} <= set(line["metrics"])
    assert "backend compiles inside the window: 0" in out
    assert "generator lateness" in out
    assert err.strip().splitlines()[-1].startswith("check ")


def test_open_loop_traced_with_an_added_metric(bench):
    rc, out, err = run(bench, RUN, "--workload", "tiny-imdb", "--seed", 5,
                       "--seconds", 2, "--trace", 1, "--cpu-rehearsal")
    assert rc == 0, err[-3000:]
    line = result_line(out)
    _schema(line, trace=True)
    assert line["correct"] is True
    assert line["attempted"] == 50
    # the CPU has no device trace: only host-clock and counter metrics
    assert set(line["metrics"]) == {"submit_us.stream",
                                    "graphs_per_batch.stream",
                                    "answered_share.tiny"}
    assert line["metrics"]["answered_share.tiny"]["value"] == 100.0
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_subgraph_stream(bench, tmp_path):
    stacks = tmp_path / "stacks.txt"
    rc, out, err = run(bench, RUN, "--workload", "tiny-sub", "--seed", 8,
                       "--seconds", 2, "--cpu-rehearsal",
                       "--watch-stalls", stacks)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True and line["attempted"] == 12
    assert set(line["metrics"]) == _end_to_end(bench, "tiny-sub")
    assert {"setup_s", "latency_p50_ms"} <= set(line["metrics"])
    assert "host stalls inside the window over 0.2 s:" in out
    assert stacks.is_file()  # stacks are written only if a stall comes


def test_an_arrival_process_added_as_a_file(bench):
    rc, out, err = run(bench, RUN, "--workload", "tiny-even", "--seed", 6,
                       "--seconds", 2, "--cpu-rehearsal")
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True and line["attempted"] == 50
    assert set(line["metrics"]) == _end_to_end(bench, "tiny-even")


def test_four_devices(bench):
    rc, out, err = run(bench, RUN, "--workload", "tiny-x4", "--seed", 9,
                       "--seconds", 2, "--cpu-rehearsal", devices=4)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True and line["device"]["count"] == 4


def test_too_few_devices_no_result(bench):
    rc, out, err = run(bench, RUN, "--workload", "tiny-x4", "--seed", 9,
                       "--seconds", 1, "--cpu-rehearsal")
    assert rc != 0 and "needs 4 chips" in err and "{" not in out


def test_added_files_leave_the_copied_ones_unchanged(bench):
    for p in (ROOT / "chipbench").rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            q = bench / p.relative_to(ROOT)
            assert q.read_bytes() == p.read_bytes(), p
    spec = json.loads((bench / "BENCHMARK.json").read_text())
    assert {"tiny-full", "tiny-x4"} <= {w["name"] for w in spec["workloads"]}
