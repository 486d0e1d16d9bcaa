"""Production training driver.

Wires together: config registry, mesh + logical-axis sharding (FSDP/TP/SP),
AdamW (+ grad accumulation / compression), deterministic data, atomic
checkpointing with resume, preemption handling, straggler timing, and the
paper's PowerMonitor as a first-class metric stream.

Usage (CPU-host example; the same script drives a real fleet where
jax.distributed.initialize() picks up the pod topology):

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --smoke --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import Checkpointer
from repro.configs import get_config
from repro.data.pipeline import DataConfig, make_source
from repro.models import lm
from repro.optim import AdamW, cosine_schedule
from repro.runtime import fault, sharding as sh
from repro.runtime.compile_cache import enable_compile_cache

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    arch: str = "qwen1.5-0.5b"
    smoke: bool = False
    steps: int = 100
    seq: int = 256
    batch: int = 8
    lr: float = 3e-4
    warmup: int = 20
    grad_accum: int = 1
    compress_grads: bool = False
    ckpt_dir: str = ""
    ckpt_every: int = 25
    model_parallel: int = 1
    power_monitor: bool = False
    # full-model power tracing (repro.trace): every N steps, interpret the
    # forward pass and log network-level BIC+ZVG savings; 0 = off. Traces
    # run host-side outside the jitted step (they are analysis, not
    # training work) -- keep the interval large on real runs.
    power_trace_every: int = 0
    power_trace_dir: str = ""
    seed: int = 0


def build(tc: TrainConfig, mesh):
    cfg = get_config(tc.arch, smoke=tc.smoke)
    opt = AdamW(lr=cosine_schedule(tc.lr, tc.warmup, tc.steps),
                compress=tc.compress_grads)
    constrain = sh.make_constrain(mesh)
    step_fn = lm.make_train_step(cfg, opt, constrain=constrain,
                                 grad_accum=tc.grad_accum,
                                 monitor=tc.power_monitor)
    return cfg, opt, step_fn


def init_state(cfg, opt, mesh, seed):
    """Initialize params/opt-state directly into their shardings."""
    pshard = sh.param_shardings(mesh, jax.eval_shape(
        lambda: lm.init_model(jax.random.key(seed), cfg)))
    init = jax.jit(lambda: lm.init_model(jax.random.key(seed), cfg),
                   out_shardings=pshard)
    with jax.transfer_guard("allow"):
        params = init()
    oshard = sh.opt_state_shardings(mesh, params, opt.init(
        jax.eval_shape(lambda: lm.init_model(jax.random.key(seed), cfg))))
    opt_state = jax.jit(opt.init, out_shardings=oshard)(params)
    return params, opt_state, pshard, oshard


def _power_trace(tc: TrainConfig, cfg, params, batch, step: int) -> dict:
    """Trace the full forward pass through the SA power model and log the
    network-level aggregate (the paper's overall-savings methodology,
    applied to the training workload as it runs)."""
    from repro.models import lm as lm_mod
    from repro.trace import trace_model

    # forward + output head (the logits projection dominates many LMs)
    rep = trace_model(
        lambda p, b: lm_mod.logits_fn(p, cfg,
                                      lm_mod.apply_model(p, cfg, b)[0]),
        params, batch, name=f"{cfg.name}@{step}")
    agg = rep.summary()
    log.info(
        "power-trace step %d: %d matmul sites, zero %.1f%%, "
        "streaming saving %.1f%%, total saving %.1f%% (share %.1f%%)",
        step, agg["n_sites"], agg["mean_zero_fraction"] * 100,
        agg["streaming_saving"] * 100, agg["total_saving"] * 100,
        agg["streaming_share"] * 100)
    if tc.power_trace_dir:
        import os
        os.makedirs(tc.power_trace_dir, exist_ok=True)
        rep.to_json(os.path.join(tc.power_trace_dir,
                                 f"trace_step{step:06d}.json"))
    return agg


def train(tc: TrainConfig, mesh=None) -> dict:
    from repro.launch.mesh import make_host_mesh
    mesh = mesh or make_host_mesh(model=tc.model_parallel)
    cfg, opt, step_fn = build(tc, mesh)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    params, opt_state, pshard, oshard = init_state(cfg, opt, mesh, tc.seed)

    ckpt = Checkpointer(tc.ckpt_dir) if tc.ckpt_dir else None
    start_step = 0
    if ckpt is not None:
        latest = ckpt.latest_step()
        if latest is not None:
            state = ckpt.restore(latest, (params, opt_state),
                                 (pshard, oshard))
            params, opt_state = state
            start_step = latest + 1
            log.info("resumed from checkpoint step %d", latest)

    data = make_source(cfg, DataConfig(seq_len=tc.seq,
                                       global_batch=tc.batch,
                                       seed=tc.seed))
    timer = fault.StepTimer()
    metrics_hist = []
    power_traces = []

    with mesh, fault.Preemption() as preempt:
        for step in range(start_step, tc.steps):
            timer.start()
            batch = jax.tree.map(jnp.asarray, data.batch(step))
            params, opt_state, metrics = jit_step(
                params, opt_state, batch, jnp.int32(step))
            loss = float(metrics["loss"])
            dt = timer.stop(step)
            metrics_hist.append({"step": step, "loss": loss, "dt": dt})
            if step % 10 == 0 or step == tc.steps - 1:
                log.info("step %5d loss %.4f (%.0f ms)", step, loss,
                         dt * 1e3)
            if tc.power_trace_every and step % tc.power_trace_every == 0:
                agg = _power_trace(tc, cfg, params, batch, step)
                power_traces.append({"step": step, **agg})
            if ckpt is not None and (step % tc.ckpt_every == 0
                                     or step == tc.steps - 1
                                     or preempt.requested):
                ckpt.save(step, (params, opt_state))
            if preempt.requested:
                log.warning("exiting at step %d on preemption", step)
                break
        if ckpt is not None:
            ckpt.wait()

    return {"final_loss": metrics_hist[-1]["loss"] if metrics_hist
            else float("nan"),
            "history": metrics_hist,
            "power_traces": power_traces,
            "stragglers": timer.straggler_steps,
            "median_step_time": timer.median}


def main() -> None:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainConfig):
        flag = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            ap.add_argument(flag, action="store_true")
        else:
            ap.add_argument(flag, type=type(f.default), default=f.default)
    args = ap.parse_args()
    tc = TrainConfig(**{f.name: getattr(args, f.name)
                        for f in dataclasses.fields(TrainConfig)})
    enable_compile_cache()
    out = train(tc)
    log.info("done: final loss %.4f, median step %.0f ms, %d stragglers",
             out["final_loss"], out["median_step_time"] * 1e3,
             len(out["stragglers"]))


if __name__ == "__main__":
    main()
