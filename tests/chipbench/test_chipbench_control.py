"""The output check fails what it must: the lower-precision control, and a
run whose served answers are altered where they are produced."""
import json

import pytest

from chipbench_testlib import result_line, run, tiny_copy

#: runs chipbench/run.py with served answers altered inside the engine: the
#: first one (FAULT=first), or every one (FAULT=all) where only a sample of
#: the answers is compared
FAULTY_RUN = '''
import os
import sys
from pathlib import Path
root = Path(__file__).resolve().parent
sys.path[0:0] = [str(root)]
from repro.runtime import engine

attempt = engine.InferenceEngine._attempt
state = {"done": False}

def altered(self, *a, **k):
    outs = attempt(self, *a, **k)
    if os.environ["FAULT"] == "all":
        return [o + 0.5 for o in outs]
    if not state["done"]:
        state["done"] = True
        outs[0] = outs[0] + 0.5
    return outs

engine.InferenceEngine._attempt = altered
sys.argv[0] = str(root / "chipbench" / "run.py")
import runpy
runpy.run_path(sys.argv[0], run_name="__main__")
'''


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tiny_copy(tmp_path_factory.mktemp("chipbench"))
    (root / "faulty_run.py").write_text(FAULTY_RUN)
    return root


def test_controls_fail_and_the_program_passes(bench):
    """Three seeds at the tiny cell's own load: every float8 control
    reading is over the cell's limit, every program reading under it."""
    rc, out, err = run(bench, "chipbench/readings.py", "--workload",
                       "tiny-full", "--seeds", "1,2,3", "--seconds", 1,
                       "--cpu-rehearsal")
    assert rc == 0, err[-3000:]
    rows = [json.loads(s) for s in out.strip().splitlines() if s.startswith("{")]
    limit = json.loads((bench / "chipbench" / "traffic" / "tiny-full.json")
                       .read_text())["check"]["max_gap"]
    seeds = [r for r in rows if "seed" in r]
    assert len(seeds) == 3
    for r in seeds:
        assert r["program"]["max_gap"] < limit
        assert r["program"]["unanswered"] == 0
        assert r["float8_e4m3fn"] > limit
        assert r["bfloat16"] > r["program"]["max_gap"]
    assert rows[-1]["summary"]["seeds"] == 3


@pytest.mark.parametrize("cell,fault", [("tiny-full", "first"),
                                        ("tiny-imdb", "all")])
def test_an_altered_answer_is_not_correct(bench, cell, fault):
    rc, out, err = run(bench, "faulty_run.py", "--workload", cell, "--seed",
                       4, "--seconds", 2, "--cpu-rehearsal",
                       env={"FAULT": fault})
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is False
    gap = line["checks"]["max_gap"]
    assert gap["value"] > gap["limit"]
