"""Shared helpers for the benchmark suite.

Every benchmark prints ``name,us_per_call,derived`` CSV rows (per the
harness convention) plus a human-readable block, and caches expensive
CNN analyses as JSON under results/bench/.
"""
from __future__ import annotations

import json
import os
import time

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def cache_path(name: str) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    return os.path.join(RESULTS, name + ".json")


def cached(name: str, fn, force: bool = False):
    path = cache_path(name)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    out = fn()
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=float)
    return out


def timed(fn, *args, warmup: int = 1, iters: int = 3):
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    dt = (time.perf_counter() - t0) / iters
    return out, dt * 1e6


def row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def emit_artifact(path: str, cells: dict, **meta) -> None:
    """Write a benchmark's structured-JSON artifact (the CI upload):
    metadata keys first, every measured cell under ``"cells"``."""
    with open(path, "w") as f:
        json.dump({**meta, "cells": cells}, f, indent=1, default=float)
    print(f"# wrote {path}")


def benchmark_cli(main, quick_help: str = "smaller workload (CI smoke)",
                  argv=None) -> None:
    """The standard benchmark entry point: ``--quick`` + ``--emit-json``,
    the CSV header, then ``main(quick=..., emit_json=...)``."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help=quick_help)
    ap.add_argument("--emit-json", default=None, metavar="PATH",
                    help="also write every cell as structured JSON "
                         "(the CI artifact)")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    main(quick=args.quick, emit_json=args.emit_json)


def analyze_cached(net: str, n_images: int = 1):
    """Cached per-layer CNN power analysis used by several benchmarks."""
    from repro.apps.cnn import analysis

    def run():
        layers = analysis.analyze_network(net, n_images=n_images)
        return {
            "layers": [vars(l) for l in layers],
            "summary": analysis.network_summary(layers),
        }

    return cached(f"cnn_{net}_{n_images}img", run)
