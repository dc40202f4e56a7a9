"""The work and roofline arithmetic of the benchmark."""
import pytest

import chipbench_testlib  # noqa: F401  (puts the repository root on sys.path)
from chipbench.work import Work, gcn_layer_work, least_time, peaks, share


def test_gcn_layer_work_hand_worked():
    # V=10 nodes, 30 nonzeros, 4 -> 2 features
    w = gcn_layer_work(10, 30, 4, 2)
    assert w.ops == 2 * 10 * 4 * 2 + 2 * 30 * 2 == 280
    assert w.bytes == 4 * (10 * 4 + 4 * 2 + 10 * 2) + 8 * 30 == 512


def test_gcn_layer_work_citeseer_layer_one():
    w = gcn_layer_work(3327, 9977, 3703, 16)
    assert w.ops == 2 * 3327 * 3703 * 16 + 2 * 9977 * 16
    assert w.bytes == 4 * (3327 * 3703 + 3703 * 16 + 3327 * 16) + 8 * 9977


def test_aggregation_takes_the_narrow_side():
    assert (gcn_layer_work(5, 7, 16, 100).ops
            == gcn_layer_work(5, 7, 100, 16).ops)


def test_least_time_names_its_bound():
    peak = peaks("TPU v5 lite")
    t, bound = least_time(Work(1.97e14, 1.0), peak)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = least_time(Work(1.0, 8.19e11), peak)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_share_is_a_percentage_and_refuses_above_one():
    assert share(1.0, 4.0) == 25.0
    assert share(2.0, 2.0) == 100.0
    with pytest.raises(ValueError, match="above 100"):
        share(2.0, 1.0)
    with pytest.raises(ValueError):
        share(1.0, 0.0)


def test_peaks_are_keyed_by_device_kind():
    p = peaks("TPU v5 lite")
    assert p["flops_per_s"] == 1.97e14 and p["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError, match="no peaks"):
        peaks("cpu")
