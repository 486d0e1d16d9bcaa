"""Readings that set the limits of ``correct``: runs one cell in this
process for several seeds, each with the cell's own window, and prints
per seed the numbers the check reads -- and, for the served tokens, the
same numbers of the float8 control and of the bfloat16 witness at the
same positions, each judged by the configured limits (the control must
come out not correct). ``--fault drop_decode`` plants a fault in the
accountant (every fifth decode booking dropped) to read the upper end
of ``energy_rel_err``.

    python bench/tools/control.py --workload <cell> --seeds 1,2,3 --seconds 51
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


def drop_decode():
    from repro.serve import power
    orig = power.PowerAccountant.record_decode
    calls = {"n": 0}

    def dropping(self, slots, acts, weight, site):
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            return None
        return orig(self, slots, acts, weight, site)
    power.PowerAccountant.record_decode = dropping


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=("drop_decode",), default=None)
    args = ap.parse_args()
    if args.fault == "drop_decode":
        drop_decode()
    c = spec.resolve(spec.load_benchmark(), args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = cell.run(c, seed, args.seconds, False, time.perf_counter(),
                     control=args.fault is None)
        gc.unfreeze()
        gc.collect()          # the last run's engine and weights
        row = {"seed": seed, "correct": r["correct"],
               **{k: v["value"] for k, v in r["checks"].items()},
               "metrics": {k: v["value"] for k, v in r["metrics"].items()},
               "window": r["window"]}
        ctl = r.get("control", {})
        row["served"] = ctl.get("served")
        for k in ("control", "witness"):
            if k in ctl:
                row[k] = {"correct": ctl[k]["correct"], **ctl[k]["readings"]}
        print("READING " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
