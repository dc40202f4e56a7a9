"""BENCHMARK.json: its shape, and every name resolving to its files."""
import json
import re

import pytest

from chipbench_testlib import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all((ROOT / p).is_dir() for p in SPEC["paths"])
    assert (ROOT / SPEC["command"][1]).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_entries_have_exactly_the_contract_keys():
    keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }
    for section, want in keys.items():
        for e in SPEC[section]:
            assert set(e) - {"workloads"} == want, e["name"]


def test_names_units_and_lengths():
    names = [e["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[s]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())


def test_cells_resolve_their_files_by_name():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert cfg["name"] == w["config"]
        assert {"model", "dataset", "precision"} <= set(cfg)
        traffic = json.loads(
            (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert traffic["loop"] in ("closed", "poisson")
        assert "max_gap" in traffic["check"]
        assert w["chips"] in (1, 4)
    assert {w["config"] for w in SPEC["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    SPEC["end_to_end"] + SPEC["per_layer"]])
def test_every_metric_has_a_reader(metric):
    from chipbench.bench import load_reader

    assert callable(load_reader(metric))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    from chipbench.bench import load_cell

    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        cell = load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e and m["moves"] in names, m["name"]


def test_loops_request_kinds_and_models_resolve_by_name():
    from chipbench.bench import load_cell, load_part

    for w in SPEC["workloads"]:
        cell = load_cell(w["name"])
        loop = load_part("loops", cell.traffic["loop"])
        assert callable(loop.drive) and callable(loop.offsets)
        kind = load_part("kinds", cell.traffic["requests"]["kind"])
        assert callable(kind.prepare) and callable(kind.draw)
        model = load_part("models", cell.config["model"]["kind"])
        assert callable(model.outputs) and callable(model.layer_work)


def test_an_unknown_model_kind_has_no_reference():
    from chipbench.bench import load_part

    with pytest.raises(FileNotFoundError, match="no models file"):
        load_part("models", "gin")
