"""Helpers of the benchmark's CPU tests: a copy of the benchmark with tiny
cells added as files, and runs of it in a child process on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: tiny cells, each standing in for the benchmark cell it copies
TINY = {"tiny-full": "citeseer-full", "tiny-sub": "citeseer-sub-poisson",
        "tiny-imdb": "imdb-poisson", "tiny-x4": "imdb-poisson",
        "tiny-even": "imdb-poisson"}

#: an arrival process that exists only in the copy, added as a file and
#: used by ``tiny-even``
EXTRA_LOOP = '''"""Open loop: evenly spaced arrivals at ``rate_per_s`` (added as a file)."""
import numpy as np


def offsets(traffic, seconds, seed):
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    return np.arange(n) * (seconds / n)


def concurrency(traffic):
    return None


def drive(send, draw, traffic, t0, t_end, span):
    for it, off in zip(draw.items, draw.offsets):
        send(it, t0 + off)
'''

#: a per-layer metric that exists only in the copy, added as a file
EXTRA_METRIC = {
    "name": "answered_share.tiny", "unit": "%", "better": "higher",
    "source": "host_clock", "layer": "front end", "moves": "latency_p50_ms",
    "workloads": ["tiny-sub", "tiny-imdb", "tiny-x4", "tiny-even"]}
EXTRA_READER = '''"""Share of the window's requests answered (added as a file)."""


def read(ctx):
    recs = ctx.win.records
    return 100.0 * len(ctx.win.answered) / len(recs) if recs else None
'''


def _dump(path: Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def tiny_copy(tmp: Path) -> Path:
    """``BENCHMARK.json`` and ``chipbench/`` copied under ``tmp``, plus tiny
    configs, traffic mixes, cells, one extra metric and one extra arrival
    process, all added as new files and new entries: no file of the copy is
    edited but ``BENCHMARK.json``."""
    root = tmp / "bench"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    cb = root / "chipbench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cite = json.loads((cb / "configs" / "gcn-citeseer.json").read_text())
    cite.update(name="tiny-cite")
    cite["model"]["layers"] = [[64, 16], [16, 6]]
    cite["dataset"].update(n_nodes=200, n_edges=560, n_features=64)
    _dump(cb / "configs" / "tiny-cite.json", cite)
    imdb = json.loads((cb / "configs" / "gcn-imdb-bin.json").read_text())
    imdb.update(name="tiny-imdb")
    _dump(cb / "configs" / "tiny-imdb.json", imdb)
    spec["configs"] += [
        {"name": n, "source": "tiny copy", "file": f"chipbench/configs/{n}.json",
         "reduced": [], "why": "CPU rehearsal"} for n in ("tiny-cite", "tiny-imdb")]
    for tiny, big in TINY.items():
        t = json.loads((cb / "traffic" / f"{big}.json").read_text())
        if tiny == "tiny-sub":
            t.update(rate_per_s=6.0)
            t["requests"].update(pool=12, min_nodes=20, max_nodes=100)
            t["check"]["sample"] = 8
        elif tiny in ("tiny-imdb", "tiny-x4", "tiny-even"):
            t.update(rate_per_s=25.0, max_graphs=4)
            t["check"]["sample"] = 16
        if tiny == "tiny-even":
            t["loop"] = "uniform"
        _dump(cb / "traffic" / f"{tiny}.json", t)
        w = next(w for w in spec["workloads"] if w["name"] == big)
        spec["workloads"].append(dict(w, name=tiny, traffic=tiny,
                                      chips=4 if tiny == "tiny-x4" else 1,
                                      config="tiny-cite" if "cite" in
                                      w["config"] else "tiny-imdb"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [t for t, b in TINY.items() if b in m["workloads"]]
    spec["per_layer"].append(dict(EXTRA_METRIC))
    (cb / "metrics" / f"{EXTRA_METRIC['name']}.py").write_text(EXTRA_READER)
    (cb / "loops" / "uniform.py").write_text(EXTRA_LOOP)
    _dump(root / "BENCHMARK.json", spec)
    return root


def run(root: Path, script: str, *args, devices: int = 1, env=None,
        src: bool = True, timeout: float = 600):
    """``python <root>/<script> args`` on the CPU; (rc, stdout, stderr)."""
    e = dict(os.environ)
    e.update(JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e["PYTHONPATH"] = str(ROOT / "src") if src else ""
    if devices > 1:
        e["XLA_FLAGS"] = (e.get("XLA_FLAGS", "") +
                          f" --xla_force_host_platform_device_count={devices}")
    e.update(env or {})
    p = subprocess.run([sys.executable, str(root / script), *map(str, args)],
                       cwd=root, env=e, capture_output=True, text=True,
                       timeout=timeout)
    return p.returncode, p.stdout, p.stderr


def result_line(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])
