"""Chip smoke: serve full-width qwen1.5-0.5b on a TPU with power monitoring.

Drives the system's main path once through the entry points a user calls
(``repro.configs.ARCHS``, ``lm.init_model``, ``ServeEngine``) at the
published width of qwen1.5-0.5b (24 layers, d_model 1024, vocab 151936),
with random weights made from ``--seed``, and checks what comes out. It
is a smoke test, not a benchmark: the times it prints only orient.

One chip (the default) runs two phases:

* ``serve``    -- a dozen mixed requests (prompts of 16-512 tokens, 8-32
  new tokens, half greedy and half temperature/top-k) through the engine
  with per-request BIC/ZVG power monitoring. Every request must retire
  with its budget and a finite power report, the serve-wide report must
  hold both monitored sites, and the greedy requests' tokens must equal
  a second run with monitoring off.
* ``counters`` -- the Pallas counter kernel, compiled by Mosaic, against
  the pure-JAX reference on the real layer-0 operands, integer for
  integer.

``--chips 4`` runs only the mesh path: the greedy requests through a
4-way tensor-parallel engine and through the single-device engine in
the same process, comparing prefill logits (within a bf16 tolerance) and
the prefill power reports (exactly).

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a
TPU the script exits non-zero before any phase and prints no such line.

Run:  python chip_smoke.py
      python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.core import monitor  # noqa: E402
from repro.core.bic import NAMED_SEGMENTS  # noqa: E402
from repro.core.bits import to_bits  # noqa: E402
from repro.design.evaluate import menu_args  # noqa: E402
from repro.kernels.power_counters import CounterSpec, edge_counters  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import SamplingParams, ServeConfig, ServeEngine  # noqa: E402

ARCH = "qwen1.5-0.5b"

#: prefill logits of the 4-way tensor-parallel engine against the single
#: device's: TP reorders the partial sums of every projection, and each
#: of the 24 layers re-rounds its bf16 activations, so the two can differ
#: by a few bf16 ulps (2^-8 relative) per layer; 2^-4 of the logit scale
#: bounds that accumulation with margin while still catching a wrong
#: shard (which moves logits by O(1) of the scale)
MESH_LOGIT_RTOL = 2.0 ** -4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums JAX's trace, lowering and compile durations (persistent-cache
    loads included) while installed, to split compile from run time."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def make_requests(seed: int, vocab: int, n: int = 12,
                  prompt: tuple[int, int] = (16, 512),
                  new: tuple[int, int] = (8, 32)) -> list[tuple]:
    """``n`` seeded requests ``(prompt tokens, max_new_tokens, sampling)``:
    log-uniform prompt lengths (every power-of-two prefill bucket in the
    range gets traffic), uniform budgets, even indices greedy."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(round(math.exp(rng.uniform(math.log(prompt[0]),
                                              math.log(prompt[1])))))
        toks = [int(t) for t in rng.integers(0, vocab, plen)]
        budget = int(rng.integers(new[0], new[1] + 1))
        samp = (SamplingParams() if i % 2 == 0
                else SamplingParams(temperature=0.8, top_k=20))
        reqs.append((toks, budget, samp))
    return reqs


def _is_greedy(samp: SamplingParams) -> bool:
    return samp.temperature == 0.0


def _run(engine: ServeEngine, reqs: list[tuple]) -> list:
    for toks, budget, samp in reqs:
        engine.submit(toks, max_new_tokens=budget, sampling=samp)
    return sorted(engine.run(), key=lambda r: r.uid)


def serve_phase(params, cfg, reqs, max_slots: int = 8,
                cache_len: int = 1024) -> dict:
    """Monitored serving, then the same traffic unmonitored."""
    scfg = ServeConfig(max_slots=max_slots, cache_len=cache_len,
                       power_monitor=True)
    engine = ServeEngine(params, cfg, scfg)
    done = _run(engine, reqs)
    check(len(done) == len(reqs),
          f"{len(done)} of {len(reqs)} requests retired")
    for r, (_, budget, _) in zip(done, reqs):
        check(r.finish_reason == "eos" or (r.finish_reason == "length"
                                           and len(r.generated) == budget),
              f"request {r.uid} retired by {r.finish_reason!r} after "
              f"{len(r.generated)} of {budget} tokens")
        check(r.power is not None, f"request {r.uid} has no power report")
        energies = [v for comps in r.power.energy.values()
                    for v in comps.values()]
        check(bool(energies) and all(math.isfinite(v) for v in energies),
              f"request {r.uid} has non-finite energies")
    report = engine.trace_report()
    names = {s.name for s in report.sites}
    for site, _ in lm.pick_monitor_weights(params):
        for kind in ("prefill", "decode"):
            check(f"{kind}/{site}" in names,
                  f"trace_report lacks {kind}/{site}: {sorted(names)}")

    plain = ServeEngine(params, cfg, ServeConfig(
        max_slots=max_slots, cache_len=cache_len, power_monitor=False))
    done_off = _run(plain, reqs)
    greedy = [i for i, (_, _, s) in enumerate(reqs) if _is_greedy(s)]
    for i in greedy:
        check(done[i].generated == done_off[i].generated,
              f"greedy request {done[i].uid}: tokens differ with "
              f"monitoring on and off")
    summary = report.summary()
    return {"requests": len(done),
            "tokens": engine.stats["tokens"],
            "greedy_equal_monitor_off": len(greedy),
            "sites": sorted(names),
            "saving_total": summary["total_saving"]}


def _streams(params, cfg, seed: int, mcfg, batch: int = 8,
             prompt_len: int = 256):
    """The accountant's operand streams, built the way
    ``repro.serve.power`` builds them: per monitored site, the north
    (weight) stream, the west stream of every row of one decode batch,
    and the west stream of one prompt's rows at prefill."""
    geom = mcfg.design_list[0].geometry
    rng = np.random.default_rng(seed + 1)
    embed = jax.jit(lm.make_embed_step(cfg))
    x_dec = embed(params, {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (batch, 1)))})[:, 0]      # [B, D]
    x_pre = embed(params, {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (1, prompt_len)))})[0]   # [S, D]
    out = []
    for site, w in lm.pick_monitor_weights(params):
        a_dec, w2 = monitor.subsample_operands(x_dec, w, mcfg)
        a_pre, _ = monitor.subsample_operands(x_pre, w, mcfg)
        north = to_bits(w2)
        north = jnp.pad(north, ((0, 0), (0, (-north.shape[1]) % geom.cols)))
        row_bits = to_bits(a_dec)                               # [B, K]
        west = jnp.zeros(row_bits.shape + (geom.rows,), jnp.uint16)
        west = west.at[:, :, 0].set(row_bits)                   # [B, K, R]
        prefill = to_bits(a_pre).T                              # [K, S]
        prefill = jnp.pad(prefill,
                          ((0, 0), (0, (-prefill.shape[1]) % geom.rows)))
        out.append((site, north, west, prefill))
    return out


def counters_phase(params, cfg, seed: int) -> dict:
    """Compiled Pallas counters == reference counters, exactly."""
    mcfg = monitor.DEFAULT_MONITOR
    ((_, _), kw), = menu_args(mcfg.design_list).items()
    west_spec = CounterSpec(bic_variants=kw["west_bic"], zvg=kw["west_zvg"])
    north_spec = CounterSpec(bic_variants=kw["north_bic"],
                             zvg=kw["north_zvg"])
    full_spec = CounterSpec(bic_variants=tuple(NAMED_SEGMENTS.values()),
                            zvg=True, hist=True)

    def both(stream, spec, batched):
        def run(backend):
            fn = lambda s: edge_counters(s, spec, backend=backend)
            return jax.device_get(jax.vmap(fn)(stream) if batched
                                  else fn(stream))
        return run("pallas"), run("ref")

    compared = 0
    for site, north, west, prefill in _streams(params, cfg, seed, mcfg):
        cases = [("north", north, north_spec, False),
                 ("north/full-menu", north, full_spec, False),
                 ("west/decode-rows", west, west_spec, True),
                 ("west/prefill", prefill, west_spec, False),
                 ("west/full-menu", prefill, full_spec, False)]
        for name, stream, spec, batched in cases:
            got, want = both(stream, spec, batched)
            check(got.keys() == want.keys(), f"{site} {name}: row names")
            for row in want:
                g, w = np.asarray(got[row]), np.asarray(want[row])
                check(g.shape == w.shape and np.array_equal(g, w),
                      f"{site} {name}: counter row {row!r} differs "
                      f"(pallas {g.ravel()[:8]} vs ref {w.ravel()[:8]})")
                compared += w.size
        print(f"  {site}: north {tuple(north.shape)}, decode west "
              f"{tuple(west.shape)}, prefill west {tuple(prefill.shape)}")
    return {"integer_counters_equal": compared}


def mesh_phase(params, cfg, reqs, max_slots: int = 8,
               cache_len: int = 1024, model: int = 4) -> dict:
    """4-way TP engine vs the single-device engine on the greedy
    requests: prefill logits within MESH_LOGIT_RTOL, prefill power
    reports identical."""
    reqs = [r for r in reqs if _is_greedy(r[2])]
    scfg = ServeConfig(max_slots=max_slots, cache_len=cache_len,
                       power_monitor=True)
    mesh = make_host_mesh(data=1, model=model)
    single = ServeEngine(params, cfg, scfg)
    sharded = ServeEngine(params, cfg, scfg, mesh=mesh)

    per_dev: dict = {}
    total = 0
    for leaf in jax.tree.leaves(sharded.params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_dev[shard.device] = (per_dev.get(shard.device, 0)
                                     + shard.data.nbytes)
    shares = {str(d): round(b / total, 4) for d, b in per_dev.items()}
    print(f"  parameter share per device: {shares}")
    check(len(per_dev) == model and max(per_dev.values()) <= total / 2,
          f"parameters are not spread over the {model} devices: {shares}")

    worst = 0.0
    for toks, _, _ in reqs:
        logits = []
        for eng in (single, sharded):
            length = len(toks)
            padded = np.zeros((1, eng._bucket(length)), np.int32)
            padded[0, :length] = toks
            lg, _ = eng._prefill(eng.params, {"tokens": jnp.asarray(padded)},
                                 np.int32(length))
            logits.append(np.asarray(jax.device_get(lg), np.float32))
        ref, got = logits
        scale = float(np.abs(ref).max())
        err = float(np.abs(got - ref).max()) / max(scale, 1e-30)
        worst = max(worst, err)
        check(bool(np.isfinite(got).all()) and err <= MESH_LOGIT_RTOL,
              f"prefill logits differ by {err:.3g} of the logit scale "
              f"(limit {MESH_LOGIT_RTOL:g})")

    records = ({}, {})
    done = []
    for eng, rec in zip((single, sharded), records):
        eng.accountant.retire_hooks.append(
            lambda r, rec=rec: rec.__setitem__(r.uid, r))
        done.append(_run(eng, reqs))
        check(len(done[-1]) == len(reqs) == len(rec),
              "mesh phase: not every request retired")
    for uid, r1 in records[0].items():
        pre1 = [s for s in r1.sites if s.site.startswith("prefill/")]
        pre2 = [s for s in records[1][uid].sites
                if s.site.startswith("prefill/")]
        check(bool(pre1) and pre1 == pre2,
              f"request {uid}: prefill power report differs on the mesh")
    same = sum(a.generated == b.generated for a, b in zip(*done))
    return {"requests": len(reqs), "logit_err_max": worst,
            "prefill_reports_equal": len(records[0]),
            "greedy_tokens_identical (not checked)": same}


def _memory_line(devices) -> str:
    parts = []
    for d in devices:
        stats = d.memory_stats() or {}
        parts.append(f"{d.id}: in_use={stats.get('bytes_in_use', 'n/a')} "
                     f"peak={stats.get('peak_bytes_in_use', 'n/a')}")
    return "; ".join(parts)


def _phase(name: str, fn, *args, **kw) -> dict:
    with CompileClock() as clock:
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        wall = time.perf_counter() - t0
    print(f"phase {name}: passed; wall {wall:.1f} s, of which compile "
          f"{clock.seconds:.1f} s (smoke timings, not a benchmark)")
    for k, v in out.items():
        print(f"  {k}: {v}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + counters phases; 4: mesh path only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the traffic")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")

    cfg = ARCHS[ARCH]
    t0 = time.perf_counter()
    params = jax.block_until_ready(lm.init_model(jax.random.key(args.seed),
                                                 cfg))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {ARCH} full width, {cfg.n_layers} layers, "
          f"{n_params} parameters, random init in "
          f"{time.perf_counter() - t0:.1f} s")
    reqs = make_requests(args.seed, cfg.vocab)

    if args.chips == 4:
        _phase("mesh", mesh_phase, params, cfg, reqs)
    else:
        _phase("serve", serve_phase, params, cfg, reqs)
        _phase("counters", counters_phase, params, cfg, args.seed)
    print(f"memory: {_memory_line(jax.devices())}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
