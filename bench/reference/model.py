"""Plain float32 forward pass of a dense decoder (attention with grouped
key/value heads, rotary positions, RMSNorm, gated SiLU MLP, tied or
untied head), written from the published description and the
configuration file's keys alone.

No cache, no batching, no kernels: one whole sequence, every position,
every matrix product at ``HIGHEST`` precision. ``low`` rounds the
operands of every matrix product -- the weight products and both
attention products, so also the keys and values -- before it:
``"fp8"`` is the control, float8 (e4m3) with a per-tensor scale, the
step below the bfloat16 the configuration states; ``"bf16"`` is a
witness of what bfloat16 rounding alone does to the served tokens.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


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


def _mm(x, w, low):
    return jnp.matmul(_round(x, low), _round(w, low), precision=HI)


def _ein(spec, a, b, low):
    return jnp.einsum(spec, _round(a, low), _round(b, low), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv              # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _hparams(cfg: dict) -> tuple:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return (h, cfg["num_key_value_heads"], hd,
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            float(cfg.get("embedding_multiplier", 1.0)),
            float(cfg.get("residual_multiplier", 1.0)),
            float(cfg.get("attention_multiplier", 0.0)) or hd ** -0.5,
            1.0 / float(cfg.get("logits_scaling", 1.0)),
            bool(cfg["tie_word_embeddings"]))


@partial(jax.jit, static_argnames=("hp", "low"))
def _forward(w, tokens, hp: tuple, low):
    h, kv, hd, eps, theta, emb_mult, resid, scale, logit_mult, tied = hp
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens] * emb_mult
    causal = pos[:, None] >= pos[None, :]

    def layer(x, lw):
        a = _rms(x, lw["norm1"], eps)
        q = _mm(a, lw["wq"], low) + lw.get("bq", 0.0)
        k = _mm(a, lw["wk"], low) + lw.get("bk", 0.0)
        v = _mm(a, lw["wv"], low) + lw.get("bv", 0.0)
        q = _rope(q.reshape(s, h, hd), pos, theta)
        k = _rope(k.reshape(s, kv, hd), pos, theta)
        v = v.reshape(s, kv, hd)
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        sc = _ein("qhd,khd->hqk", q, k, low) * scale
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        o = _ein("hqk,khd->qhd", p, v, low).reshape(s, h * hd)
        x = x + resid * _mm(o, lw["wo"], low)
        a = _rms(x, lw["norm2"], eps)
        y = jax.nn.silu(_mm(a, lw["gate"], low)) * _mm(a, lw["up"], low)
        x = x + resid * _mm(y, lw["down"], low)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x, w["final_norm"], eps)
    head = w["embed"].T if tied else w["unembed"]
    return _mm(x, head, low) * logit_mult


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
