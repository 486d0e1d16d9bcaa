"""Plain float32 forward pass of a DeepSeek-V2 decoder (multi-head latent
attention with YaRN rope, a dense SwiGLU FFN in the leading layers,
routed and shared SwiGLU experts in the rest), written from the
published description (arXiv:2405.04434 and the published
``modeling_deepseek.py``) and the configuration file's keys alone.

No cache, no batching, no kernels: one whole sequence, every position,
every matrix product at ``HIGHEST`` precision. The attention is the
expanded form (per-head keys and values made from the latent), computed
one block of queries at a time so that a whole horizon fits beside the
engine's weights.

Rope: the published model stores each head's rope dimensions as adjacent
pairs and de-interleaves them (``(0, 2, 4, ..., 1, 3, 5, ...)``) before
its half-split rotation; this reference does the same, and the
benchmark's ``plain_weights`` puts the program's rope columns in that
interleaved order. YaRN: inverse frequencies blended between the
unscaled and the ``factor``-scaled ones by a linear ramp between the
correction dimensions of ``beta_fast`` and ``beta_slow``, cos and sin
scaled by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``,
the softmax scale by ``mscale(factor, mscale_all_dim) ** 2``.

Experts: the router scores every expert (``published`` count, softmax),
each token takes its top ``num_experts_per_tok`` with their raw
probabilities as gates, and only the experts held here (``n_routed_
experts`` of them from ``first_held_expert``) add their part: the same
share the program computes. The shared experts are one SwiGLU of width
``n_shared_experts * moe_intermediate_size``.

``low`` rounds the operands of every matrix product -- the weight
products (the router and every expert included), the latent products
and both attention products -- before it: ``"fp8"`` is the control,
float8 (e4m3) with a per-tensor scale, the step below the bfloat16 the
configuration states; ``"bf16"`` is a witness of what bfloat16 rounding
alone does to the served tokens.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
#: queries per attention block
Q_BLOCK = 512


def _fp8(a):
    s = jnp.max(jnp.abs(a)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _round(a, low):
    if low == "fp8":
        return _fp8(a)
    if low == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    return a


def _ein(spec, a, b, low):
    return jnp.einsum(spec, _round(a, low), _round(b, low), precision=HI)


def _mm(x, w, low):
    return _ein("...d,df->...f", x, w, low)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_tables(dim, theta, rs, s):
    """cos, sin ``[S, dim]`` of the published YaRN rotary embedding."""
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = 1.0 - ramp
    freq_extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                  / dim))
    freq_inter = 1.0 / (factor * theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inv = freq_inter * (1.0 - extra) + freq_extra * extra
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)
    mag = (_yarn_mscale(factor, rs["mscale"])
           / _yarn_mscale(factor, rs["mscale_all_dim"]))
    return jnp.cos(emb) * mag, jnp.sin(emb) * mag


def _rope(x, cos, sin):
    """Published rotation of ``x [S, ..., dim]``: de-interleave, then
    ``x * cos + rotate_half(x) * sin``."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d,)
    return x * cos.reshape(shape) + rot * sin.reshape(shape)


def _hparams(cfg: dict) -> tuple:
    rs = cfg["rope_scaling"]
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]),
            tuple(sorted((k, v) for k, v in rs.items() if k != "type")),
            cfg["num_experts_per_tok"],
            int(cfg.get("first_held_expert", 0)),
            int(cfg.get("published", {}).get("n_routed_experts",
                                              cfg["n_routed_experts"])))


def _attention(lw, a, cos, sin, hp, low):
    h, dn, dr, eps, _, rs, *_ = hp
    rs = dict(rs)
    s = a.shape[0]
    scale = (dn + dr) ** -0.5 * _yarn_mscale(
        float(rs["factor"]), rs["mscale_all_dim"]) ** 2
    q = _ein("sd,dhe->she", a, lw["wq"], low)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cos, sin)
    ckv = _rms(_mm(a, lw["w_dkv"], low), lw["kv_norm"], eps)
    k_pe = _rope(_mm(a, lw["w_kr"], low), cos, sin)          # [S, dr]
    k_nope = _ein("sr,rhe->she", ckv, lw["w_uk"], low)
    v = _ein("sr,rhe->she", ckv, lw["w_uv"], low)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (s, h, dr))], -1)
    qf = jnp.concatenate([q_nope, q_pe], -1)
    nb = -(-s // Q_BLOCK)
    qf = jnp.pad(qf, ((0, nb * Q_BLOCK - s), (0, 0), (0, 0)))
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qf, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = _ein("qhe,khe->hqk", qb, k, low) * scale
        sc = jnp.where((qpos[:, None] >= kpos[None, :])[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        return _ein("hqk,khe->qhe", p, v, low)
    o = jax.lax.map(block, jnp.arange(nb)).reshape(nb * Q_BLOCK, h, -1)[:s]
    return _mm(o.reshape(s, -1), lw["wo"], low)


def _swiglu(x, gate, up, down, low):
    return _mm(jax.nn.silu(_mm(x, gate, low)) * _mm(x, up, low), down, low)


def _experts(lw, a, hp, low):
    """Routed experts held here (gated by their top-k probabilities over
    every expert) plus the shared experts."""
    *_, top_k, first, width = hp
    held = lw["w_gate"].shape[0]
    probs = jax.nn.softmax(_mm(a, lw["router"], low), -1)    # [S, width]
    gates, idx = jax.lax.top_k(probs, top_k)
    onehot = idx[..., None] == (first + jnp.arange(held))    # [S, k, held]
    g = jnp.sum(jnp.where(onehot, gates[..., None], 0.0), 1)  # [S, held]
    hg = jax.nn.silu(_ein("sd,edf->esf", a, lw["w_gate"], low))
    hu = _ein("sd,edf->esf", a, lw["w_up"], low)
    y = _ein("esf,efd->esd", hg * hu, lw["w_down"], low)
    routed = jnp.einsum("se,esd->sd", g, y, precision=HI)
    return routed + _swiglu(a, lw["s_gate"], lw["s_up"], lw["s_down"], low)


@partial(jax.jit, static_argnames=("hp", "low"))
def _forward(w, tokens, hp: tuple, low):
    h, dn, dr, eps, theta, rs, *_ = hp
    s = tokens.shape[0]
    cos, sin = _yarn_tables(dr, theta, dict(rs), s)
    x = w["embed"][tokens]

    def layer(x, lw, ffn):
        x = x + _attention(lw, _rms(x, lw["norm1"], eps), cos, sin, hp, low)
        return x + ffn(lw, _rms(x, lw["norm2"], eps))

    dense = lambda lw, a: _swiglu(a, lw["gate"], lw["up"], lw["down"], low)
    for lw in w["dense"]:
        x = layer(x, lw, dense)
    x, _ = jax.lax.scan(
        lambda x, lw: (layer(x, lw, lambda l, a: _experts(l, a, hp, low)),
                       None), x, w["moe"])
    x = _rms(x, w["final_norm"], eps)
    return _mm(x, w["unembed"], low)


@jax.jit
def _gaps(logits, pos, tok):
    """Per position: how far the logit of ``tok`` lies below the best,
    in units of that position's logit standard deviation."""
    rows = logits[pos]
    best = rows.max(-1)
    std = rows.std(-1)
    got = jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]
    return (best - got) / std, rows.argmax(-1)


def forward(weights: dict, cfg: dict, tokens, low: str | None = None):
    """Logits ``[S, V]`` (float32) of every position of ``tokens``."""
    return _forward(weights, tokens, _hparams(cfg), low)


def token_gaps(weights: dict, cfg: dict, seq: list[int], start: int,
               pad_to: int, control: bool = False):
    """The tokens ``seq[start:]`` judged by the reference: for each, how
    far its logit lies below the reference's best at the position that
    predicted it (in logit standard deviations). With ``control``, the
    same for the tokens the float8 control (``"control"``) and the
    bfloat16 witness (``"witness"``) put first there instead."""
    import numpy as np
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    n = len(seq) - start
    pos = np.zeros(pad_to, np.int32)          # fixed shapes: one compile
    pos[:n] = np.arange(start - 1, len(seq) - 1)
    served = np.zeros(pad_to, np.int32)
    served[:n] = seq[start:]
    pos, served = jnp.asarray(pos), jnp.asarray(served)
    ref = forward(weights, cfg, jnp.asarray(toks))
    gap, _ = _gaps(ref, pos, served)
    out = {"served": np.asarray(gap)[:n]}
    if control:
        for key, low in (("control", "fp8"), ("witness", "bf16")):
            _, pick = _gaps(forward(weights, cfg, jnp.asarray(toks), low),
                            pos, served)
            g, _ = _gaps(ref, pos, pick)
            out[key] = np.asarray(g)[:n]
    return out
