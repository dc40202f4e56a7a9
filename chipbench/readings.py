#!/usr/bin/env python3
"""Readings that set the output check's limits: the program's widest gap to
the reference over many seeds, and the controls' over the same requests.

    python chipbench/readings.py --workload citeseer-full --seeds 1,2,3 --seconds 8

One process serves every seed through one engine (new weights and a new
draw per seed, a short window at the cell's own load), then compares the
served answers with the float32 reference (the lower reading) and puts the
reference computed at each lower precision in the program's place (the
controls; the smallest of a control's readings is its upper reading).  One
JSON line per seed, then a summary line.  The benchmark's own runs never
run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    import os

    sys.path[0:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

#: the controls, lowest precision last
CONTROLS = ("bfloat16", "float8_e4m3fn")


def readings(workload: str, seeds, seconds: float, root: Path = ROOT,
             allow_cpu: bool = False, controls=CONTROLS):
    """Per seed: the program's numbers and each control's ``max_gap``."""
    import jax.numpy as jnp

    from chipbench.bench import (Session, check, load_cell,
                                 require_devices)
    from repro.runtime import enable_persistent_compilation_cache

    cell = load_cell(workload, root)
    devices = require_devices(cell.chips, allow_cpu)
    enable_persistent_compilation_cache()
    sess = Session(cell, devices, seeds[0], root)
    rows = []
    try:
        for i, seed in enumerate(seeds):
            if i:
                sess.reseed(seed)
            draw = sess.workload.draw(seed, seconds)
            sess.warm(draw)
            win = sess.window(draw, seconds, rid0=100000 * i)
            prog = check(cell, draw, win, sess.params, seed)
            row = {"seed": seed, "served": sum(win.ok(r) for r in win.records),
                   "program": {k: v for k, (v, _) in prog.items()}}
            for name in controls:
                ctl = check(cell, draw, win, sess.params, seed,
                            dtype=getattr(jnp, name))
                row[name] = ctl["max_gap"][0]
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        sess.close()
    return rows


def summary(rows, controls=CONTROLS) -> dict:
    lower = max(r["program"]["max_gap"] for r in rows)
    out = {"lower": lower, "seeds": len(rows)}
    for name in controls:
        out[f"upper_{name}"] = min(r[name] for r in rows)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = readings(args.workload, seeds, args.seconds,
                    allow_cpu=args.cpu_rehearsal)
    print(json.dumps({"summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
