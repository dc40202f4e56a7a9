"""The reduction from a profiler trace to the reported numbers."""
import json

import pytest

from chipbench_testlib import ROOT
from chipbench import trace as T

DATA = ROOT / "tests" / "chipbench" / "data"

#: a hand-made trace: window 0..100 ns, two devices, host spans
SMALL = {
    "span": [0, 100],
    "devices": {
        "/device:TPU:0": [["fused_agg_cmb", 10, 20], ["fusion.1", 25, 10],
                          ["spmm_ell.3", 60, 10], ["copy", 95, 20]],
        "/device:TPU:1": [["fusion.1", 0, 50]],
    },
    "host": [["bench.window", 0, 100], ["bench.submit", 36, 20],
             ["bench.wait", 70, 30]],
}


def test_busy_union_clips_and_merges():
    ev = SMALL["devices"]["/device:TPU:0"]
    assert T.busy_intervals(ev, 0, 100) == [[10, 35], [60, 70], [95, 100]]
    assert T.busy_ns(ev, 0, 100) == 40


def test_idle_share_is_the_mean_over_devices():
    # device 0 busy 40 of 100, device 1 busy 50 of 100
    assert T.mean_busy_ns(SMALL) == 45
    assert T.idle_share(SMALL) == pytest.approx(0.55)
    assert T.idle_share(dict(SMALL, devices={})) is None


def test_kernel_time_by_name():
    assert T.kernel_ns(SMALL, ("fused_agg_cmb", "spmm_ell")) == 30
    assert T.kernel_ns(SMALL, ("fusion",)) == 60
    assert T.kernel_ns(SMALL, ("fused",)) == 0


def test_top_ops():
    assert T.top_ops(SMALL, k=2) == [["fusion.1", 60e-9], ["fused_agg_cmb", 20e-9]]


def test_idle_gaps_named_by_the_host_span_over_them():
    gaps = T.idle_gaps(SMALL)
    # device 0: 0-10 (no span), 35-60 (submit 36-56), 70-95 (wait);
    # device 1: 50-100 (wait 70-100 over submit 50-56)
    assert gaps[0] == ["bench.wait", 50e-9]
    assert ["bench.submit", 25e-9] in gaps and ["bench.wait", 25e-9] in gaps
    assert ["unattributed", 10e-9] in gaps


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("trace_*.json")))
def test_recorded_chip_trace(name):
    """A trace recorded on a TPU v5e, trimmed: the reduction gives the
    numbers it gave when the trace was committed."""
    tr = json.loads((DATA / name).read_text())
    want = tr.pop("expected")
    assert tr["devices"] and all(k.startswith("/device:TPU:")
                                 for k in tr["devices"])
    assert T.idle_share(tr) == pytest.approx(want["idle_share"], rel=1e-12)
    assert T.kernel_ns(tr, ("fused_agg_cmb", "spmm_ell")) == \
        pytest.approx(want["agg_kernel_ns"], rel=1e-12)
    assert [n for n, _ in T.top_ops(tr)] == want["top_ops"]
    names = {n for n, _ in T.idle_gaps(tr)}
    assert names <= {"bench.generate", "bench.submit", "bench.wait",
                     "unattributed"}
