#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee.

    python chipbench/sweep.py --workload imdb-poisson --rates 200,400,800 --seconds 10

One engine serves every rate in turn (the cell's traffic with its
``rate_per_s`` replaced).  Per rate it prints the offered and served
graphs/s, p50 and p95 latency, the p95 of the window's last third against
its first third (a backlog that grows shows there), and the requests still
unanswered when the window closed.  The knee is the highest rate whose
served rate keeps up with the offered one and whose backlog does not grow;
the cell's traffic file then fixes four fifths of it.  The benchmark's own
runs never run this.
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


def sweep(workload: str, rates, seconds: float, seed: int, root: Path = ROOT,
          allow_cpu: bool = False):
    from chipbench.bench import Session, load_cell, require_devices
    from chipbench.stats import percentile
    from repro.runtime import enable_persistent_compilation_cache

    cell = load_cell(workload, root)
    devices = require_devices(cell.chips, allow_cpu)
    enable_persistent_compilation_cache()
    sess = Session(cell, devices, seed, root)
    rows = []
    try:
        for i, rate in enumerate(rates):
            cell.traffic["rate_per_s"] = float(rate)
            draw = sess.workload.draw(seed + i, seconds)
            sess.warm(draw)
            win = sess.window(draw, seconds, rid0=10**6 * i)
            lat = [1e3 * (r.done - r.due) for r in win.answered]
            third = len(win.records) // 3
            first = [1e3 * (r.done - r.due) for r in win.records[:third]
                     if r.done is not None]
            last = [1e3 * (r.done - r.due) for r in win.records[-third:]
                    if r.done is not None]
            row = {
                "rate": rate,
                "offered_per_s": len(win.records) / seconds,
                "served_per_s": sum(win.ok(r) and r.done <= win.t_end
                                    for r in win.records) / seconds,
                "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
                "p95_first_third_ms": percentile(first, 95),
                "p95_last_third_ms": percentile(last, 95),
                "unanswered_at_close": sum(r.done is None or r.done > win.t_close
                                           for r in win.records),
                "batches": win.n_batches, "compiles_in_window": win.compiles,
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        sess.close()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated per s")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sweep(args.workload, [float(r) for r in args.rates.split(",")],
          args.seconds, args.seed, allow_cpu=args.cpu_rehearsal)
    return 0


if __name__ == "__main__":
    sys.exit(main())
