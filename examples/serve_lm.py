"""Serving driver: a mixed workload through the continuous-batching engine.

Submits ``--requests`` requests with randomized prompt lengths, token
budgets and sampling parameters (half greedy, half temperature+top-k),
pumps ``ServeEngine.step()`` until the queue drains, and prints one line
per retired request -- tokens generated, finish reason, and the request's
own BIC + ZVG streaming-power report -- plus engine-level throughput,
occupancy, and the serve-wide paper-style power aggregate.

With ``--telemetry`` the engine also partitions the retirement stream
into windows of ``--window`` requests and re-runs per-site design
selection per window (hysteresis via ``--hysteresis``/``--min-dwell``),
printing the flip timeline -- see docs/observability.md.

Run:  PYTHONPATH=src python examples/serve_lm.py --requests 16
      PYTHONPATH=src python examples/serve_lm.py --telemetry --window 4
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import SMOKES
from repro.models import lm
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import (SamplingParams, ServeConfig, ServeEngine,
                         TelemetryConfig)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=96)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--no-power", action="store_true",
                    help="skip per-request power accounting")
    ap.add_argument("--telemetry", action="store_true",
                    help="windowed online design selection + flip timeline")
    ap.add_argument("--window", type=int, default=4,
                    help="retired requests per telemetry window")
    ap.add_argument("--stride", type=int, default=None,
                    help="window stride (< window slides; default tumbling)")
    ap.add_argument("--hysteresis", type=float, default=0.0,
                    help="relative margin a challenger design must win by")
    ap.add_argument("--min-dwell", type=int, default=1,
                    help="windows an incumbent holds before challengers")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    tcfg = (TelemetryConfig(window=args.window, stride=args.stride,
                            hysteresis=args.hysteresis,
                            min_dwell=args.min_dwell)
            if args.telemetry else None)
    if args.telemetry and args.no_power:
        ap.error("--telemetry requires power accounting (drop --no-power)")
    enable_compile_cache()
    cfg = SMOKES[args.arch]
    params = lm.init_model(jax.random.key(0), cfg)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=args.slots, cache_len=args.cache_len,
        power_monitor=not args.no_power, seed=args.seed,
        telemetry=tcfg))

    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        prompt = list(rng.integers(0, cfg.vocab,
                                   int(rng.integers(2, args.max_prompt))))
        samp = (SamplingParams() if i % 2 == 0 else
                SamplingParams(temperature=0.8, top_k=20))
        engine.submit(prompt, max_new_tokens=int(rng.integers(4, args.max_new)),
                      sampling=samp)

    print(f"arch={cfg.name} (reduced config), slots={args.slots}, "
          f"cache_len={args.cache_len}, requests={args.requests}")
    t0 = time.perf_counter()
    finished = engine.run()
    dt = time.perf_counter() - t0

    hdr = (f"{'req':>4s} {'prompt':>6s} {'new':>4s} {'reason':8s} "
           f"{'slot':>4s}")
    if not args.no_power:
        hdr += f" {'save%':>6s} {'stream-save%':>12s} {'zero%':>6s}"
    print(hdr)
    for r in sorted(finished, key=lambda r: r.uid):
        line = (f"{r.uid:4d} {r.prompt_len:6d} {len(r.generated):4d} "
                f"{r.finish_reason:8s} {r.slot:4d}")
        if r.power is not None:
            line += (f" {r.power.saving_total * 100:6.2f} "
                     f"{r.power.saving_streaming * 100:12.2f} "
                     f"{r.power.zero_fraction * 100:6.1f}")
        print(line)

    st = engine.stats
    print(f"\n{len(finished)} requests in {st['steps']} engine steps "
          f"({st['decode_steps']} decode steps, "
          f"mean occupancy {engine.occupancy():.2f}/{args.slots} slots)")
    print(f"{st['tokens']} tokens in {dt:.2f}s = {st['tokens'] / dt:.0f} "
          f"tok/s (includes compile)")
    if not args.no_power:
        agg = engine.trace_report().summary()
        print(f"serve-wide (energy-weighted): "
              f"{agg['total_saving'] * 100:.2f}% total / "
              f"{agg['streaming_saving'] * 100:.2f}% streaming saving, "
              f"zero fraction {agg['mean_zero_fraction'] * 100:.1f}%")
    if args.telemetry:
        engine.telemetry.finalize()
        print("\nflip timeline (windows of "
              f"{args.window} retirements, hysteresis "
              f"{args.hysteresis:g}, min dwell {args.min_dwell}):")
        print(engine.telemetry.timeline.table())


if __name__ == "__main__":
    main()
