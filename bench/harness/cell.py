"""One run of one cell: set-up, warm phase, measured window, reductions,
the check that decides ``correct``, and the result line."""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
import types

import jax
import numpy as np

from bench.harness import correct, e2e, spec, system, trace, weights
from bench.harness.loop import Loop, Spans
from repro.serve import SamplingParams, ServeEngine


class CompileClock:
    """Counts JAX compiles and sums their trace, lowering and compile
    durations (persistent-cache loads included) while installed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def install(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program kept, whatever the environment says."""
    path = os.path.join(root, ".bench_cache", "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks_for(kind: str, bench_dir: str) -> dict:
    table = spec.load_json(os.path.join(bench_dir, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}")
    return table[kind]


def _bucket(engine, n: int) -> int:
    return max(engine._bucket(n), n)


def warm_programs(engine, gen, duration: float, vocab: int) -> None:
    """Compile every program the traffic will drive: one short request
    per prefill shape the prompts fall in (each runs prefill, the first
    token, decode steps and, when monitored, both accounting paths),
    then the accountant's per-prompt accounting at every
    ``(prompt length, reused prefix)`` the traffic can produce."""
    ps = (engine.scfg.paging.page_size
          if engine.scfg.paging is not None else None)
    shapes = gen.prefill_shapes(duration, ps)
    rng = np.random.default_rng(0)
    lengths = sorted({n for n, _ in shapes})
    chunk = engine.scfg.paging.prefill_chunk if ps else 0
    reps = {}
    for n in lengths:
        key = ("chunk",) if chunk and n > chunk else ("dense", _bucket(engine, n))
        reps.setdefault(key, n)
    for i, n in enumerate(sorted(reps.values())):
        samp = SamplingParams() if i % 2 else SamplingParams(0.8, 20)
        engine.submit([int(t) for t in rng.integers(0, vocab, n)],
                      max_new_tokens=3, sampling=samp)
    engine.run()
    acc = engine.accountant
    if acc is None:
        return
    slot = 1 << 30
    for n, lo in sorted(shapes):
        toks = np.zeros((1, _bucket(engine, n)), np.int32)
        toks[0, :n] = rng.integers(0, vocab, n)
        acc.begin(slot, -1, n)
        engine._record_prefill_power(slot, toks, lo, n)
        acc.suspend(slot)
    jax.effects_barrier()


def _window_counts(loop, inst, stats0, stats1, w0, w1) -> dict:
    reqs = [(tr.due, tr.stamps) for tr in loop.tracks]
    d = {k: stats1.get(k, 0) - stats0.get(k, 0) for k in stats1}
    return {"tokens": e2e.tokens_in(reqs, w0, w1),
            "decode_steps": d["decode_steps"],
            "occupancy_sum": d["occupancy_sum"],
            "engine_steps": d["steps"],
            "prefix_hit_requests": d.get("prefix_hit_requests", 0),
            "admissions": inst.admissions,
            "prompt_tokens": inst.prompt_tokens,
            "decode_log": list(inst.decode_log),
            "prefills": list(inst.prefills),
            "acct_rows": list(inst.acct_rows),
            "accountant_s": inst.acc_timer.seconds}


def _decode_tokens_flops(family, cfg: dict, decode_log) -> float:
    """Model FLOPs of the window's decode steps from each step's live
    rows and their attended positions (the family's counts)."""
    per_tok = family.token_flops(cfg, 0, True)
    att = family.token_flops(cfg, 1, False) - family.token_flops(cfg, 0,
                                                                 False)
    return sum(rows * per_tok + att * pos for rows, pos in decode_log)


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        p_start: float, require_tpu: bool = True, fault=None,
        root: str = spec.ROOT, keep_trace: str | None = None,
        control: bool = False) -> dict:
    dev = device_info(cell.chips, require_tpu)
    enable_cache(root)
    clock = CompileClock().install()
    cfg, traffic, family = cell.config, cell.traffic, cell.family
    # off the chip (the CPU tests of this harness) the arithmetic runs
    # against the v5e's peaks; run.py never gets here without a TPU
    peaks = peaks_for(dev["kind"] if require_tpu else "TPU v5 lite",
                      cell.bench_dir)

    arch = family.arch_config(cfg)
    scfg = system.serve_config(cfg, traffic, seed)
    params = weights.for_config(system.init_fn(arch), seed, cfg)
    jax.block_until_ready(params)
    engine = ServeEngine(params, arch, scfg)
    kind = spec.load_kind(traffic["kind"], cell.bench_dir)
    ctx = {"horizon": scfg.cache_len, "load_traffic": cell.load_traffic,
           "load_kind": lambda k: spec.load_kind(k, cell.bench_dir)}
    warm_s = float(traffic["warm_seconds"])
    horizon = warm_s + seconds + 5.0
    warm_programs(engine, kind(traffic, seed, cfg["vocab_size"], ctx),
                  horizon, cfg["vocab_size"])
    gen = kind(traffic, seed, cfg["vocab_size"], ctx)

    spans = Spans()
    inst = system.Instruments(engine, spans, seed)
    monitored = engine.accountant is not None
    if monitored:
        inst.capture_counters()
    if fault is not None:
        fault(engine)
    loop = Loop(engine, gen, traffic, spans)
    loop.start(horizon)
    loop.run_until(loop.t0 + warm_s)
    setup_compiles = clock.compiles
    # set-up's objects out of the collector's way: a full collection in
    # the window then walks only what the window made
    gc.collect()
    gc.freeze()

    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        spans.tracing = True
    stats0 = dict(engine.stats)
    w0 = time.perf_counter()
    setup_s = w0 - p_start
    inst.on = True
    loop.run_until(w0 + seconds)
    w1 = time.perf_counter()
    inst.on = False
    stats1 = dict(engine.stats)
    if traced:
        spans.tracing = False
        jax.profiler.stop_trace()
    window_compiles = clock.compiles - setup_compiles
    backlog = engine.scheduler.n_pending
    wc = _window_counts(loop, inst, stats0, stats1, w0, w1)
    inst.release()

    if gen.open_loop:
        loop.drain_first_tokens(w0 + seconds, 60.0)
    mem = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    lateness = sorted(loop.lateness) or [0.0]
    log(f"window: {w1 - w0:.3f} s, {wc['tokens']} tokens, "
        f"{wc['decode_steps']} decode steps, {wc['admissions']} admissions, "
        f"compiles in window {window_compiles}, set-up compiles "
        f"{setup_compiles} ({clock.seconds:.2f} s compiling in all), "
        f"generator late p50 {1e3 * e2e.percentile(lateness, 50):.3f} ms "
        f"max {1e3 * lateness[-1]:.3f} ms, backlog at close {backlog}")

    reqs = [(tr.due, tr.stamps) for tr in loop.tracks]
    metrics = {}
    breakdown = None
    if not traced:
        values = {"out_tok_s": lambda: e2e.out_tok_s(reqs, w0, w1),
                  "itl_p95_ms": lambda: e2e.itl_p95_ms(reqs, w0, w1),
                  "ttft_p90_ms": lambda: e2e.ttft_p90_ms(reqs, w0,
                                                         w0 + seconds),
                  "setup_s": lambda: setup_s}
        log(f"samples: {len(e2e.gaps_in(reqs, w0, w1))} token gaps, "
            f"{len(e2e.ttfts(reqs, w0, w0 + seconds))} requests due")
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]](),
                                  "unit": m["unit"]}
    else:
        xplane = trace.find_xplane(log_dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(keep_trace, "trace.xplane.pb"))
        tr = trace.load(xplane)
        t0, t1 = trace.window(tr)
        busy = trace.busy_ns(tr, t0, t1)
        dev["busy_s"] = busy * 1e-9
        dev["window_s"] = (t1 - t0) * 1e-9
        rctx = types.SimpleNamespace(
            trace=tr, t0=t0, t1=t1, window_s=(t1 - t0) * 1e-9,
            busy_s=busy * 1e-9, config=cfg, counts=family, peaks=peaks,
            window=wc, monitored=monitored, engine_mode=cfg["engine"]["mode"],
            decode_flops=_decode_tokens_flops(family, cfg, wc["decode_log"]))
        for m in cell.per_layer:
            v = spec.load_metric(m["name"], cell.bench_dir).read(rctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": trace.top_ops(tr, t0, t1),
                     "idle_gaps": trace.idle_gaps(tr, t0, t1)}

    shutil.rmtree(log_dir, ignore_errors=True)

    # ------------------------------------------------------- correctness
    system.free_engine(engine)
    sample = correct.sample_tracks(loop.tracks, w0, w1, seed)
    plain = family.plain_weights(params, cfg)
    t_ref = time.perf_counter()
    c_ref = clock.compiles
    gaps = (correct.gap_readings(cell.reference, plain, cfg, sample,
                                 scfg.cache_len, control)
            if sample else {})
    numbers = dict(gaps.get("served") or
                   {"logit_gap": None, "logit_gap_mean": None})
    t_gap = time.perf_counter()
    if monitored:
        numbers["energy_rel_err"] = (correct.energy_rel_err(
            family, plain, cfg, sample, inst.prefill_log, scfg.cache_len)
            if sample else None)
        t_energy = time.perf_counter()
        numbers["counter_mismatch"] = (
            float(correct.counter_mismatch(inst.captured))
            if inst.captured else None)
        log(f"reference times: logits {t_gap - t_ref:.2f} s, energies "
            f"{t_energy - t_gap:.2f} s, counters "
            f"{time.perf_counter() - t_energy:.2f} s, "
            f"{clock.compiles - c_ref} compiles")
    limits = cfg.get("limits", {})
    ok, checks = correct.judge(numbers, limits)
    log(f"reference: {len(sample)} requests, "
        f"{sum(len(t.req.generated) for t in sample)} served tokens, "
        f"{len(inst.captured)} counter passes, "
        f"{time.perf_counter() - t_ref:.2f} s")
    for name, v in numbers.items():
        if name not in checks:
            log(f"reading {name} {v!r} (no limit: not compared)")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    due = [t for t in loop.tracks if w0 <= t.due < w0 + seconds]
    result = {"correct": bool(ok), "attempted": len(due),
              # open loop: a request due in the window and never served,
              # even after the drain, failed; a closed loop's last
              # requests are cut by the window, not failed
              "failed": (sum(1 for t in due if not t.stamps)
                         if gen.open_loop else 0),
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"seconds": w1 - w0, "tokens": wc["tokens"],
                        "decode_steps": wc["decode_steps"],
                        "admissions": wc["admissions"],
                        "compiles": window_compiles, "backlog": backlog,
                        "late_max_ms": 1e3 * lateness[-1]}
    if control:
        # the control and the witness in the program's place, judged by
        # the same limits: the control has to come out not correct
        result["control"] = {"served": gaps.get("served")}
        for k in ("control", "witness"):
            if k in gaps:
                good, chk = correct.judge(dict(numbers, **gaps[k]), limits)
                result["control"][k] = {"correct": good, "checks": chk,
                                        "readings": gaps[k]}
    result["checks"] = checks
    return result


def main(argv=None, p_start: float | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this dir")
    args = ap.parse_args(argv)
    p_start = time.perf_counter() if p_start is None else p_start
    bench = spec.load_benchmark()
    cell = spec.resolve(bench, args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace), p_start,
                 keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0
