"""Logical-axis -> mesh-axis sharding rules (t5x-style).

Parameters carry logical axis names (repro.models.layers.Param); this module
resolves them against a mesh into NamedShardings, with:

* FSDP: the "embed" logical axis shards over the composed data axes
  ("pod", "data") -- parameters AND optimizer state are ZeRO-3 sharded.
* TP:   "heads" / "ff" / "vocab" / "heads_ff" shard over "model".
* EP:   "expert" shards over "model" (experts live TP-wide).
* SP:   activations between blocks are constrained to
  P(dp_axes, "model", None) -- sequence-parallel residual stream.

Every rule application is guarded by divisibility: a dimension that does
not divide evenly over its assigned mesh axes falls back to replication
(never a compile error), and a mesh axis is never used twice in one spec.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import layers as L

# logical axis -> preferred mesh axes (first-fit with divisibility checks)
LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "embed": ("pod", "data"),      # FSDP
    "vocab": ("model",),
    "heads": ("model",),
    "ff": ("model",),
    "heads_ff": ("model",),
    "expert": ("model",),
    "kv_lora": ("model",),
    "layers": (),                  # scan axis: never sharded
}

# Serving rules: weights are read every step and there is no optimizer
# state, so FSDP-style gathering over the data axes is pure overhead --
# replicate over data, shard only on the model (TP) axis. (§Perf cell A.)
LOGICAL_RULES_SERVE: dict[str, tuple[str, ...]] = {
    **LOGICAL_RULES,
    "embed": (),
}


def decode_compute_backend(mesh: Mesh | None, kernel_backend: str) -> str:
    """The kernel backend the serve decode jit may trace.

    A GSPMD-partitioned decode graph cannot host per-device
    ``pallas_call`` bodies, so mesh decode always compiles the ``"ref"``
    model compute regardless of ``ServeConfig.kernel_backend``. The
    power accountant is NOT downgraded: it streams gathered local
    operands outside the decode jit, so mesh + ``"pallas"`` keeps the
    fused counter pass and the cross-backend bit-identity contract
    (``tests/multidevice/test_serve_kernel_mesh.py``).
    """
    return kernel_backend if mesh is None else "ref"


def _mesh_axes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _resolve_dim(logical: str | None, size: int, mesh: Mesh,
                 used: set[str], rules: dict | None = None):
    """Mesh axes for one dimension, or None (replicate)."""
    rules = rules if rules is not None else LOGICAL_RULES
    if logical is None:
        return None
    want = [a for a in rules.get(logical, ())
            if a in mesh.axis_names and a not in used]
    if not want:
        return None
    sizes = _mesh_axes(mesh)
    # greedy prefix of the preferred axes whose product divides the dim
    chosen: list[str] = []
    prod = 1
    for a in want:
        if size % (prod * sizes[a]) == 0:
            chosen.append(a)
            prod *= sizes[a]
    if not chosen:
        return None
    used.update(chosen)
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


def spec_for(axes: tuple[str | None, ...], shape: tuple[int, ...],
             mesh: Mesh, rules: dict | None = None) -> P:
    used: set[str] = set()
    return P(*[_resolve_dim(ax, dim, mesh, used, rules)
               for ax, dim in zip(axes, shape)])


def param_shardings(mesh: Mesh, params, serve: bool = False) -> Any:
    """Tree of NamedSharding matching a Param tree (values untouched).

    ``serve=True`` uses the TP-only serving rules (no FSDP gathering)."""
    rules = LOGICAL_RULES_SERVE if serve else LOGICAL_RULES

    def one(p: L.Param):
        return L.Param(
            NamedSharding(mesh, spec_for(p.axes, p.value.shape, mesh,
                                         rules)),
            p.axes)
    return jax.tree.map(one, params, is_leaf=L.is_param)


def tree_shardings(mesh: Mesh, tree) -> Any:
    """Greedy shardings for non-Param pytrees (decode states, batches):
    batch dim -> data axes, then the largest remaining dim -> model."""
    sizes = _mesh_axes(mesh)
    dpx = dp_axes(mesh)
    dp_size = math.prod(sizes[a] for a in dpx) if dpx else 1
    model = sizes.get("model", 1)

    def one(a):
        if not hasattr(a, "shape") or a.ndim == 0:
            return NamedSharding(mesh, P())
        spec: list = [None] * a.ndim
        # batch axis: decode states are stacked [G, B, ...], plain batches
        # are [B, ...] -- shard the first dp-divisible dim of the leading
        # two over the data axes.
        bdim = None
        for i in range(min(2, a.ndim)):
            if dpx and a.shape[i] % dp_size == 0 and a.shape[i] > 0:
                bdim = i
                spec[i] = dpx if len(dpx) > 1 else dpx[0]
                break
        if model > 1:
            # prefer TRAILING dims (feature/head dims) for the model axis:
            # sharding a KV cache's sequence dim would force GSPMD to
            # all-gather it inside decode attention.
            for i in range(a.ndim - 1, 0, -1):
                if i == bdim:
                    continue
                if a.shape[i] % model == 0 and a.shape[i] >= model:
                    spec[i] = "model"
                    break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, tree)


def cache_shardings(mesh: Mesh, states) -> Any:
    """Shardings for a serving slot cache (``lm.make_decode_state``).

    Unlike :func:`tree_shardings`, this knows the decode-state layout
    contract: ``head`` / ``tail`` leaves are ``[B, ...]`` while scanned
    ``groups`` leaves are ``[G, B, ...]`` (the scan axis is never
    sharded), so the TRUE batch dim -- the slot axis -- shards over the
    data axes. One trailing feature dim (the lane-dense ``kv * hd`` of
    full-attention K/V, a ring cache's head dim, an MLA lora rank) shards
    over "model", matching the TP layout the serve rules give the
    attention weights; on the flat K/V dim each shard holds whole KV
    heads when "model" divides kv. The dim right after the slot axis is
    the cache sequence/window dim and is never given to "model" (sharding
    it would force an all-gather inside every decode step). Every
    assignment is divisibility-guarded: awkward slot counts or head
    counts fall back to replication, never to a compile error.
    """
    sizes = _mesh_axes(mesh)
    dpx = dp_axes(mesh)
    dp_size = math.prod(sizes[a] for a in dpx) if dpx else 1
    dp = dpx if len(dpx) > 1 else (dpx[0] if dpx else None)
    model = sizes.get("model", 1)

    def leaf(batch_axis):
        def one(a):
            if not hasattr(a, "shape") or a.ndim <= batch_axis:
                return NamedSharding(mesh, P())
            spec: list = [None] * a.ndim
            if dpx and a.shape[batch_axis] % dp_size == 0:
                spec[batch_axis] = dp
            if model > 1:
                # trailing feature dims only; when the leaf has a
                # sequence dim (ndim - batch_axis >= 3) it sits at
                # batch_axis + 1 and is excluded from candidates
                lo = (batch_axis + 2 if a.ndim - batch_axis >= 3
                      else batch_axis + 1)
                for i in range(a.ndim - 1, lo - 1, -1):
                    if a.shape[i] % model == 0 and a.shape[i] >= model:
                        spec[i] = "model"
                        break
            return NamedSharding(mesh, P(*spec))
        return one

    return {
        "head": jax.tree.map(leaf(0), states["head"]),
        "groups": jax.tree.map(leaf(1), states["groups"]),
        "tail": jax.tree.map(leaf(0), states["tail"]),
    }


def paged_cache_shardings(mesh: Mesh, states) -> Any:
    """Shardings for a paged KV pool (``serve.paging.cache``).

    The pool is ``lm.make_decode_state`` with the PAGE axis in the slot
    axis's role (leaves ``[P, page_size, ...]``, scanned groups
    ``[G, P, page_size, ...]``), so the slot-cache rules apply verbatim:
    pages shard over the data axes, one trailing feature dim over
    "model", and the dim after the page axis is the within-page sequence
    dim -- never sharded. Keeping the reserved trash page inside the pool
    (rather than allocating ``num_pages - 1``) is what preserves the
    page-axis divisibility this layout wants.
    """
    return cache_shardings(mesh, states)


def batch_shardings(mesh: Mesh, batch) -> Any:
    """Input batches: shard the batch dim over the data axes; leading-
    component leaves (M-RoPE positions [3, B, S]) shard dim 1."""
    dpx = dp_axes(mesh)
    sizes = _mesh_axes(mesh)
    dp_size = math.prod(sizes[a] for a in dpx) if dpx else 1
    dp = dpx if len(dpx) > 1 else (dpx[0] if dpx else None)

    def one(a):
        if not hasattr(a, "shape") or a.ndim == 0 or not dpx:
            return NamedSharding(mesh, P())
        spec = [None] * a.ndim
        if a.shape[0] % dp_size == 0:
            spec[0] = dp
        elif a.ndim > 1 and a.shape[1] % dp_size == 0:
            spec[1] = dp
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, batch)


def make_constrain(mesh: Mesh, seq_shard: bool = True):
    """Residual-stream constraint: batch over data axes + sequence-parallel
    over "model" (decode steps with S=1 skip the seq constraint)."""
    dpx = dp_axes(mesh)
    sizes = _mesh_axes(mesh)
    dp_size = math.prod(sizes[a] for a in dpx) if dpx else 1
    model = sizes.get("model", 1)
    dp = dpx if len(dpx) > 1 else (dpx[0] if dpx else None)

    def constrain(x: jax.Array) -> jax.Array:
        if x.ndim != 3:
            return x
        b, s, _ = x.shape
        bspec = dp if (dpx and b % dp_size == 0) else None
        sspec = "model" if (seq_shard and model > 1 and s % model == 0
                            and s > 1) else None
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(bspec, sspec, None)))

    return constrain


def opt_state_shardings(mesh: Mesh, params, opt_state):
    """AdamW state mirrors the param tree (Param leaves inside m/v/err)."""
    ps = param_shardings(mesh, params)

    def like(sub):
        return ps if sub is not None else None

    import repro.optim.adamw as aw
    return aw.AdamWState(m=like(opt_state.m), v=like(opt_state.v),
                         err=like(opt_state.err))
