"""Mixture-of-Experts: capacity-based top-k routing for training, dropless
routing for serving.

Mesh-TF-style einsum dispatch: tokens are grouped (group = a sequence chunk)
and each group dispatches into per-expert capacity buffers via one-hot
einsums. Under GSPMD this form shards cleanly: groups ride the data axes,
the expert dim rides the ``expert`` logical axis (mapped to the TP/"model"
mesh axis), and the dispatch/combine einsums lower to all-to-alls.

Paper tie-in: tokens dropped by capacity overflow produce *all-zero rows* in
the dispatched expert inputs -- exactly the zero streams the paper's
zero-value gating exploits (measured by the PowerMonitor, and skippable by
the ``zvg_matmul`` kernel at tile granularity on TPU).

Serving (prefill and decode) routes dropless instead: every (token,
expert) pair the router picks is computed, whatever the load, so a
prompt's tokens never depend on which other tokens share its group. An
expert layer may hold only a contiguous share of the experts
(``MoEConfig.first_expert`` / ``held``: one chip's share of an
expert-parallel deployment); it still routes over all of them and
computes only its own experts' part of each token's output.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 16
    top_k: int = 2
    expert_ff: int = 1408
    num_shared: int = 0             # shared (always-on) experts
    shared_ff: int = 0              # ff width of the shared expert block
    capacity_factor: float = 1.25
    group_size: int = 512           # tokens per dispatch group
    router_noise: float = 0.0
    first_expert: int = 0           # the experts this layer holds:
    held: int = 0                   # [first, first + held); 0 = all

    @property
    def n_held(self) -> int:
        return self.held or self.num_experts


def make_moe(key, d: int, cfg: MoEConfig) -> dict:
    ks = jax.random.split(key, 5)
    e, f = cfg.n_held, cfg.expert_ff
    p = {
        "router": L.dense_param(ks[0], d, cfg.num_experts, "embed", None),
        "w_gate": L.Param(
            L.normal_init(ks[1], (e, d, f), d ** -0.5),
            ("expert", "embed", "ff")),
        "w_up": L.Param(
            L.normal_init(ks[2], (e, d, f), d ** -0.5),
            ("expert", "embed", "ff")),
        "w_down": L.Param(
            L.normal_init(ks[3], (e, f, d), f ** -0.5),
            ("expert", "ff", "embed")),
    }
    if cfg.num_shared:
        p["shared"] = L.make_mlp(ks[4], d,
                                 cfg.shared_ff or cfg.expert_ff
                                 * cfg.num_shared)
    return p


def _topk_dispatch(logits: jax.Array, k: int, capacity: int):
    """Build dispatch/combine tensors for top-k capacity routing.

    Args:
      logits: ``f32[G, S, E]`` router logits per group.
    Returns:
      dispatch ``[G, S, E, C]`` one-hot, combine ``[G, S, E, C]`` weighted,
      aux load-balancing loss (scalar).
    """
    g, s, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    # aux loss (Switch-style): mean prob * mean assignment per expert
    top1 = jnp.argmax(logits, axis=-1)
    me = jnp.mean(jax.nn.one_hot(top1, e), axis=(0, 1))
    ce = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    gates, experts = jax.lax.top_k(probs, k)            # [G,S,k]
    dispatch = jnp.zeros((g, s, e, capacity), logits.dtype)
    combine = jnp.zeros((g, s, e, capacity), logits.dtype)
    # occupancy counter per expert, updated across the k selections
    occupancy = jnp.zeros((g, e), jnp.int32)
    for i in range(k):
        sel = jax.nn.one_hot(experts[:, :, i], e)       # [G,S,E]
        pos = occupancy[:, None, :] + jnp.cumsum(sel, axis=1) - sel
        pos = pos.astype(jnp.int32)
        keep = (pos < capacity) * sel
        occupancy = occupancy + jnp.sum(keep, axis=1).astype(jnp.int32)
        oh_pos = jax.nn.one_hot(pos, capacity, dtype=logits.dtype)
        d_i = keep[..., None] * oh_pos                  # [G,S,E,C]
        dispatch = dispatch + d_i
        combine = combine + d_i * gates[:, :, i][..., None, None]
    # the 0/1 routing structure is discrete: its cotangent is identically
    # zero, and stop_gradient removes the [G,S,E,C]-sized cotangent einsum
    # + its cross-shard regather from the backward pass entirely. Gate
    # gradients still flow through `combine`'s multiply. (§Perf cell B.)
    dispatch = jax.lax.stop_gradient(dispatch)
    return dispatch, combine, aux


def _ep_constrain(t: jax.Array, expert_dim: int) -> jax.Array:
    """Best-effort constraint pinning expert-parallel buffers [g, e, c, d]
    to (groups over data axes, experts over the model axis). With BOTH dims
    pinned, GSPMD lowers the producer->consumer resharding to the canonical
    EP all-to-all instead of replicate-and-slice (P(None, ...) would mean
    "replicate g", which forces exactly that pathology).
    No-op without a mesh in scope. (§Perf cell B.)"""
    from jax.sharding import PartitionSpec as P
    for gspec in ((("pod", "data"),), ("data",)):
        try:
            spec = [None] * t.ndim
            spec[0] = gspec[0] if isinstance(gspec[0], tuple) else gspec[0]
            spec[expert_dim] = "model"
            return jax.lax.with_sharding_constraint(t, P(*spec))
        except Exception:                                # noqa: BLE001
            continue
    return t


def _router_logits(p: dict, x: jax.Array) -> jax.Array:
    """Every expert's router logit, in float32."""
    return (x @ p["router"].value.astype(jnp.float32)
            if x.dtype == jnp.float32
            else x.astype(jnp.float32) @ p["router"].value)


def _held_experts(p: dict, x: jax.Array, probs: jax.Array, cfg: MoEConfig,
                  actf) -> jax.Array:
    """Dropless top-k over all experts, computed for the held ones only.

    ``x [N, D]``, ``probs [N, E]``. The ``N * top_k`` (token, expert)
    pairs are sorted by held expert, with the pairs of experts this
    layer does not hold after them, and the three expert products run as
    grouped matmuls (``lax.ragged_dot``) over the held groups. Rows past
    the held groups are not computed, and what they hold is not defined
    (the TPU leaves whatever its buffer held, NaN included), so they are
    selected away, not multiplied by a zero gate. Each pair's output is
    weighted by its router probability (not renormalised over the top k)
    and summed into its token in float32."""
    n, d = x.shape
    k, held = cfg.top_k, cfg.n_held
    gates, experts = jax.lax.top_k(probs, k)                 # [N, k]
    local = experts - cfg.first_expert
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held).reshape(-1)         # [N * k]
    order = jnp.argsort(group, stable=True)
    token = order // k
    sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    xs = x[token]
    wv = lambda name: p[name].value.astype(x.dtype)
    h = actf(jax.lax.ragged_dot(xs, wv("w_gate"), sizes)) \
        * jax.lax.ragged_dot(xs, wv("w_up"), sizes)
    y = jax.lax.ragged_dot(h, wv("w_down"), sizes)
    gate = gates.reshape(-1)[order]
    computed = jnp.arange(n * k) < jnp.sum(sizes)
    out = jnp.zeros((n, d), jnp.float32).at[token].add(jnp.where(
        computed[:, None], y.astype(jnp.float32) * gate[:, None], 0.0))
    return out.astype(x.dtype)


def apply_moe(p: dict, x: jax.Array, cfg: MoEConfig, act: str = "silu",
              dropless: bool = False):
    """MoE layer: ``x [B, S, D] -> (y [B, S, D], aux_loss)``. Training
    routes with capacity buffers; ``dropless`` (the serving paths) routes
    every pick (``_held_experts``), with a zero aux loss."""
    b, s, d = x.shape
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]
    if dropless:
        x2 = x.reshape(b * s, d)
        with jax.named_scope("router"):
            probs = jax.nn.softmax(_router_logits(p, x2), axis=-1)
        with jax.named_scope("experts"):
            out = _held_experts(p, x2, probs, cfg, actf).reshape(b, s, d)
        if "shared" in p:
            with jax.named_scope("shared"):
                out = out + L.apply_mlp(p["shared"], x, act)
        return out, jnp.zeros((), jnp.float32)
    if cfg.n_held != cfg.num_experts:
        raise ValueError(
            f"capacity routing needs every expert; this layer holds "
            f"{cfg.n_held} of {cfg.num_experts} (dropless serving only)")
    gs = min(cfg.group_size, s)
    assert s % gs == 0, (s, gs)
    ng = s // gs
    xg = x.reshape(b * ng, gs, d)
    logits = _router_logits(p, xg)
    capacity = max(int(gs * cfg.top_k * cfg.capacity_factor
                       / cfg.num_experts), 1)
    dispatch, combine, aux = _topk_dispatch(logits, cfg.top_k, capacity)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    # dispatch locally per data shard, then constrain the expert buffers to
    # expert(=model)-sharding: GSPMD lowers the resharding to the canonical
    # EP all-to-all (group-gather/expert-scatter) instead of replicating
    xin = jnp.einsum("gsec,gsd->gecd", dispatch, xg)
    xin = _ep_constrain(xin, 1)
    h = actf(jnp.einsum("gecd,edf->gecf", xin,
                        p["w_gate"].value.astype(x.dtype))) \
        * jnp.einsum("gecd,edf->gecf", xin, p["w_up"].value.astype(x.dtype))
    y = jnp.einsum("gecf,efd->gecd", h, p["w_down"].value.astype(x.dtype))
    y = _ep_constrain(y, 1)
    out = jnp.einsum("gsec,gecd->gsd", combine, y)
    out = out.reshape(b, s, d)
    if "shared" in p:
        out = out + L.apply_mlp(p["shared"], x, act)
    return out, aux
