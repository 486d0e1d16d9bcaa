"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
BENCHMARK.json at the root of the checkout. Runs only on a TPU: with no
accelerator, or fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

P_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench.harness.cell import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(p_start=P_START))
