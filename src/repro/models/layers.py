"""Parameter substrate and common transformer layers.

Parameters are plain pytrees of arrays. Every parameter is created through
:class:`Param`, which carries *logical axis names* alongside the value;
``split_tree`` separates the two so jit sees pure arrays while the runtime
maps logical axes -> mesh axes (t5x-style) for FSDP/TP/SP/EP sharding.

All apply functions are pure and usable under ``jax.eval_shape`` (the
multi-pod dry-run instantiates every model at full scale without allocating
a single parameter).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Param:
    """A parameter value + logical axis names (one per dim).

    Registered as a pytree (axes are static aux data) so vmap/eval_shape can
    traverse it. Note: under vmap the value gains a leading dim while axes
    stay put; ``fix_stacked_axes`` re-aligns stacked trees by prepending the
    "layers" logical axis.
    """
    value: Any                      # jax.Array | ShapeDtypeStruct
    axes: tuple[str | None, ...]


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.axes),
    lambda axes, children: Param(children[0], axes))


def is_param(x) -> bool:
    return isinstance(x, Param)


def fix_stacked_axes(tree, prefix: str = "layers"):
    """After vmapping an init, prepend the stacking axis to every Param."""
    def fix(p):
        if p.value.ndim == len(p.axes) + 1:
            return Param(p.value, (prefix,) + tuple(p.axes))
        return p
    return jax.tree.map(fix, tree, is_leaf=is_param)


def split_tree(tree):
    """Split a Param tree into (values, logical_axes) trees."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


def merge_tree(values, axes):
    return jax.tree.map(Param, values, axes,
                        is_leaf=lambda x: x is None or not isinstance(x, dict))


# ------------------------------------------------------------------ inits
def normal_init(key, shape, stddev, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(stddev, dtype)


def dense_param(key, d_in: int, d_out: int, in_ax: str | None,
                out_ax: str | None, dtype=jnp.float32,
                stddev: float | None = None) -> Param:
    """Fan-in-scaled dense kernel [d_in, d_out]."""
    std = stddev if stddev is not None else d_in ** -0.5
    return Param(normal_init(key, (d_in, d_out), std, dtype), (in_ax, out_ax))


def bias_param(d: int, ax: str | None = None, dtype=jnp.float32) -> Param:
    return Param(jnp.zeros((d,), dtype), (ax,))


def scale_param(d: int, ax: str | None = None, dtype=jnp.float32) -> Param:
    return Param(jnp.ones((d,), dtype), (ax,))


# ------------------------------------------------------------------ norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dt)


def make_norm(kind: str, d: int) -> dict:
    if kind == "rms":
        return {"scale": scale_param(d)}
    return {"scale": scale_param(d), "bias": bias_param(d)}


def apply_norm(kind: str, p: dict, x: jax.Array) -> jax.Array:
    if kind == "rms":
        return rms_norm(x, p["scale"].value if is_param(p["scale"])
                        else p["scale"])
    s = p["scale"].value if is_param(p["scale"]) else p["scale"]
    b = p["bias"].value if is_param(p["bias"]) else p["bias"]
    return layer_norm(x, s, b)


# ------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """Inverse frequencies [head_dim // 2] (f32)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                            / head_dim))


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """YaRN rotary scaling as DeepSeek-V2 publishes it (``rope_scaling``
    of type ``yarn``): ``factor`` stretches the low frequencies, a linear
    ramp between the correction dimensions of ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position`` positions
    blends them with the unscaled ones, and the attention's softmax
    scale gains ``yarn_mscale(factor, mscale_all_dim) ** 2``."""
    factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, y: YarnRope) -> np.ndarray:
    """YaRN inverse frequencies ``[dim // 2]`` (float32, host constants)."""
    def correction_dim(rotations):
        return (dim * math.log(y.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extra_mask = 1.0 - ramp
    freq_extra = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32)
                                  / dim))
    freq_inter = freq_extra / y.factor
    return (freq_inter * (1.0 - extra_mask)
            + freq_extra * extra_mask).astype(np.float32)


def yarn_cos_scale(y: YarnRope) -> float:
    """The factor on YaRN's cos and sin tables."""
    return (yarn_mscale(y.factor, y.mscale)
            / yarn_mscale(y.factor, y.mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0,
               yarn: YarnRope | None = None) -> jax.Array:
    """Rotary embedding over the last dim (half-split pairs), YaRN-scaled
    when ``yarn`` is given.

    Args:
      x: ``[..., S, H, D]`` (positions broadcast over H).
      positions: ``[..., S]`` integer positions.
    """
    d = x.shape[-1]
    if yarn is None:
        freqs, mag = rope_freqs(d, theta), 1.0
    else:
        freqs, mag = jnp.asarray(yarn_inv_freq(d, theta, yarn)), \
            yarn_cos_scale(yarn)
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]                    # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    if mag != 1.0:
        cos, sin = cos * mag, sin * mag
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array,
                sections: Sequence[int], theta: float = 1e6) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): the head-dim frequency bands are split
    into ``sections`` (summing to D/2), each rotated by its own position
    stream (temporal / height / width).

    Args:
      x: ``[B, S, H, D]``.
      positions: ``[3, B, S]`` integer positions (t, h, w).
      sections: per-component frequency-band sizes, sum = D // 2.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                        # [D/2]
    ang_per = positions[..., None].astype(jnp.float32) * freqs  # [3,B,S,D/2]
    # select which component drives each frequency band
    sel = jnp.repeat(jnp.arange(len(sections)),
                     jnp.asarray(sections), total_repeat_length=d // 2)
    ang = jnp.take_along_axis(
        jnp.moveaxis(ang_per, 0, -1), sel[None, None, :, None], axis=-1
    )[..., 0]                                           # [B,S,D/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d: int) -> jax.Array:
    """Classic sinusoidal embeddings ``[..., d]`` (MusicGen-style)."""
    half = d // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# -------------------------------------------------------------------- MLP
def make_mlp(key, d: int, f: int, gated: bool = True) -> dict:
    ks = jax.random.split(key, 3)
    p = {"up": dense_param(ks[0], d, f, "embed", "ff")}
    if gated:
        p["gate"] = dense_param(ks[1], d, f, "embed", "ff")
    p["down"] = dense_param(ks[2], f, d, "ff", "embed")
    return p


def apply_mlp(p: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    from . import matmul as mm
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[act]
    h = mm.matmul(x, p["up"].value.astype(x.dtype))
    if "gate" in p:
        h = actf(mm.matmul(x, p["gate"].value.astype(x.dtype))) * h
    else:
        h = actf(h)
    return mm.matmul(h, p["down"].value.astype(x.dtype))
