"""Put a traced run's device-idle time down to the serving engine's own
phases: idle gaps by the innermost host span of either family
(``bench.*`` and the engine's ``serve.*``), idle time inside each engine
phase, and engine spans per step.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace 1 --keep-trace <dir>
    python bench/tools/serve_idle_gaps.py <dir>/trace.xplane.pb
"""
import collections
import json
import os
import sys

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench.harness import trace  # noqa: E402
from bench.metrics import _serve_spans  # noqa: E402


def main(path: str) -> dict:
    tr = trace.load(path)
    t0, t1 = trace.window(tr)
    serve, _ = _serve_spans.from_file(path)
    both = trace.Trace(tr.ops, tr.modules, tr.spans + serve, tr.n_devices)
    counts = collections.Counter(n for n, s, _ in serve if t0 <= s <= t1)
    steps = counts["serve.step"] or 1
    busy = trace.busy_ns(tr, t0, t1)
    return {
        "window_s": (t1 - t0) * 1e-9, "idle_s": (t1 - t0 - busy) * 1e-9,
        "engine_steps": counts["serve.step"],
        "idle_gaps": trace.idle_gaps(both, t0, t1, k=16),
        "idle_s_inside": {n: 1e-9 * _serve_spans.idle_within(
            tr, t0, t1, serve, (n,)) for n in sorted(counts)},
        "spans_per_step": {n: c / steps for n, c in sorted(counts.items())},
    }


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1]), indent=1))
