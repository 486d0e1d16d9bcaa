"""Find the highest offered rate a cell sustains: runs the cell in this
process once per value of one traffic parameter (e.g. ``rate`` or
``session_rate``) and prints the end-to-end numbers and the backlog
left at the window's close. The chosen rate is then written into the
traffic file as a number.

    python bench/tools/sweep.py --workload <cell> --param rate \\
        --values 2,3,4 --seconds 20
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench.harness import cell, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--param", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 1000)
    args = ap.parse_args()
    for v in [float(x) for x in args.values.split(",")]:
        c = spec.resolve(spec.load_benchmark(), args.workload)
        c.traffic[args.param] = v
        r = cell.run(c, args.seed, args.seconds, False, time.perf_counter())
        gc.collect()          # the last run's engine and weights
        row = {args.param: v, "correct": r["correct"],
               **{k: m["value"] for k, m in r["metrics"].items()},
               **r["window"]}
        print("SWEEP " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
