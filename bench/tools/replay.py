"""A second witness for a served sequence's widest logit gap: runs one
cell as a benchmark run does, then serves the sampled greedy request
with the widest gap once more, alone, on a fresh engine with prefix
reuse and monitoring off, and prints whether the program serves the
same tokens, with the reference's gaps of both; then, for every sampled
request, fed its served tokens up to its widest gap (teacher forcing),
which token the program picks there alone, and that token's gap.

    python bench/tools/replay.py --workload <cell> --seeds 1,2 --seconds 51
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

import numpy as np  # noqa: E402

from bench.harness import cell, correct, spec, system, weights  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402


def keep(module, name: str, into: dict):
    """Wrap ``module.name`` so that its last result is kept in ``into``."""
    orig = getattr(module, name)

    def call(*a, **kw):
        into[name] = orig(*a, **kw)
        return into[name]
    setattr(module, name, call)


def witness(c, seed: int, seconds: float) -> dict:
    kept = {}
    keep(correct, "sample_tracks", kept)
    keep(weights, "for_config", kept)
    r = cell.run(c, seed, seconds, False, time.perf_counter())
    gc.unfreeze()
    gc.collect()              # the run's engine; its weights are kept
    cfg, params = c.config, kept["for_config"]
    plain = c.family.plain_weights(params, cfg)
    pad = int(cfg["engine"]["cache_len"])

    def gaps(prompt, served):
        return c.reference.token_gaps(plain, cfg,
                                      list(prompt) + list(served),
                                      len(prompt), pad)["served"]
    sample = kept["sample_tracks"]
    widest = {t.req.uid: gaps(t.req.prompt, t.req.generated) for t in sample}
    worst = max(sample, key=lambda t: widest[t.req.uid].max())
    g0 = widest[worst.req.uid]

    alone = json.loads(json.dumps(cfg))
    if alone["engine"]["mode"] == "paged":
        alone["engine"]["prefix_cache"] = False
    scfg = system.serve_config(alone, dict(c.traffic, power_monitor=False),
                               seed)
    eng = ServeEngine(params, c.family.arch_config(cfg), scfg)

    def pick_alone(prompt, served, i):
        r1 = eng.submit(list(prompt) + list(served[:i]), max_new_tokens=1)
        eng.run()
        pick = int(r1.generated[0])
        return pick, float(gaps(prompt, list(served[:i]) + [pick])[i])

    req = eng.submit(list(worst.req.prompt),
                     max_new_tokens=len(worst.req.generated))
    eng.run()
    served, again = list(worst.req.generated), list(req.generated)
    diff = next((i for i, (a, b) in enumerate(zip(served, again)) if a != b),
                None)
    g1 = gaps(worst.req.prompt, again)
    # every sampled request at its widest gap, fed the served tokens
    forced = []
    for t in sample:
        g = widest[t.req.uid]
        i = int(np.argmax(g))
        pick, gap = pick_alone(t.req.prompt, t.req.generated, i)
        forced.append({"uid": t.req.uid, "at": i, "served_gap": float(g[i]),
                       "alone_gap": gap,
                       "same": pick == t.req.generated[i]})
    system.free_engine(eng)
    return {
        "seed": seed, "correct": r["correct"],
        "prompt_len": len(worst.req.prompt), "served": len(served),
        "widest_gap": float(g0.max()), "widest_at": int(np.argmax(g0)),
        "mean_gap": float(g0.mean()),
        "replay_same_tokens": served == again, "first_difference": diff,
        "replay_widest_gap": float(g1.max()),
        "replay_mean_gap": float(g1.mean()),
        "teacher_forced": forced}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    c = spec.resolve(spec.load_benchmark(), args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        print("WITNESS " + json.dumps(witness(c, seed, args.seconds)),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
