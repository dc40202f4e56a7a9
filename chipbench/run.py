#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python chipbench/run.py --workload citeseer-full --seed 7 --seconds 10 --trace 0

Prints progress lines, then one JSON object as the last line of stdout:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number of the output check beside its limit (also the last lines of
stderr).  Exits non-zero without printing a result when JAX finds no TPU
or fewer chips than the cell asks for; ``--cpu-rehearsal`` lets it run on
the CPU for rehearsals, and then its output names the ``cpu``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]
# the compile cache lives at one fixed path inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="allow JAX's CPU backend (rehearsals only)")
    ap.add_argument("--dump-trace", default=None,
                    help="also write the reduced trace as JSON here")
    ap.add_argument("--watch-stalls", default=None,
                    help="write every thread's stack here at each host stall")
    args = ap.parse_args(argv)
    from chipbench.bench import run

    return run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
