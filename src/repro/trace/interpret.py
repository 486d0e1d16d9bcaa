"""Jaxpr interpreter that discovers every matmul a callable executes.

``jax.make_jaxpr`` turns any jit-able function -- a decode step, a CNN
forward, a whole train loss -- into a closed jaxpr. This module walks that
jaxpr with *concrete* operands, recursing through the structural primitives
(``pjit``, ``remat2``, ``custom_jvp/vjp_call``, ``cond``, ``while``) and
**unrolling** ``scan`` so that every layer of a scanned transformer stack is
visited with the activations it actually sees. At every ``dot_general`` /
``conv_general_dilated`` equation the interpreter reshapes the live operands
into the ``[M, K] x [K, N]`` form a systolic array streams and hands them to
a callback; everything else evaluates through the primitive's normal bind,
so the interpreted function computes exactly what the jitted one does.

Site names are hierarchical and *stable across calls*: the jaxpr equation
order is deterministic, so ``scan[3]/attn/dot#0`` names the same weight
matmul on every decode step -- which is what lets
:mod:`repro.trace.capture` accumulate statistics per site.

Conv lowering matches :mod:`repro.apps.cnn.nets` (`_im2col`): the K axis is
ordered (spatial..., channel) to agree with an HWIO ``w.reshape(-1, cout)``,
so a traced conv streams the identical operand a hand-written im2col
analysis would. Grouped convs (depthwise) become ``groups`` batched
``[M, K_g] x [K_g, N_g]`` matmuls, the honest SA mapping.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import core as jcore
from jax.extend.core import Literal as _Literal

# Primitives that carry a sub-jaxpr the interpreter must recurse into so
# inner matmuls are seen with concrete operands (a plain bind would execute
# them opaquely). pjit stores its ClosedJaxpr under "jaxpr", closed_call
# under "call_jaxpr".
_CALL_LIKE = {"pjit", "closed_call"}
_CUSTOM_CALL = {"custom_jvp_call", "custom_vjp_call",
                "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr"}


@dataclasses.dataclass
class MatmulSite:
    """One matmul the traced function executed, in SA streaming form.

    ``lhs``/``rhs`` are always rank-3: ``[B, M, K]`` and ``[B, K, N]``
    with B the (flattened) batch dimension -- B > 1 for batched
    ``dot_general`` (e.g. attention scores) and grouped convolutions,
    where the SA runs B independent ``[M,K] x [K,N]`` problems.
    """
    name: str
    kind: str            # "dot_general" | "conv" | "dwconv"
    lhs: jax.Array       # [B, M, K]
    rhs: jax.Array       # [B, K, N]

    @property
    def shape(self) -> tuple[int, int, int, int]:
        return (self.lhs.shape[0], self.lhs.shape[1],
                self.lhs.shape[2], self.rhs.shape[2])

    @property
    def macs(self) -> float:
        b, m, k, n = self.shape
        return float(b) * m * k * n


class _Scope:
    """Hierarchical site naming: structural frames (scan iteration, nested
    jit name) + the equation's own named_scope stack + a per-prefix
    occurrence counter."""

    def __init__(self):
        self.frames: list[str] = []
        self.counts: dict[str, int] = {}

    def push(self, frame: str):
        self.frames.append(frame)

    def pop(self):
        self.frames.pop()

    def site_name(self, eqn) -> str:
        stack = str(eqn.source_info.name_stack)
        parts = list(self.frames)
        if stack:
            parts.append(stack)
        prefix = "/".join(parts) if parts else "<top>"
        k = self.counts.get(prefix, 0)
        self.counts[prefix] = k + 1
        return f"{prefix}/dot#{k}"


def _frame(eqn, label: str) -> str:
    """Structural frame name: the equation's own named_scope stack (which
    sub-jaxpr name stacks do NOT inherit) + a positional label."""
    stack = str(eqn.source_info.name_stack)
    return f"{stack}/{label}" if stack else label


def dot_operands_3d(lhs: jax.Array, rhs: jax.Array, dimension_numbers
                    ) -> tuple[jax.Array, jax.Array]:
    """Reshape general ``dot_general`` operands to ``[B,M,K] x [B,K,N]``.

    Batch dims pair elementwise (lb[i] with rb[i]) and flatten into B;
    contract dims pair elementwise and flatten into K in matching order, so
    the streamed K sequence is identical for both operands.
    """
    (lc, rc), (lb, rb) = dimension_numbers
    lo = [d for d in range(lhs.ndim) if d not in lc and d not in lb]
    ro = [d for d in range(rhs.ndim) if d not in rc and d not in rb]
    A = jnp.transpose(lhs, list(lb) + lo + list(lc))
    W = jnp.transpose(rhs, list(rb) + list(rc) + ro)
    b = math.prod(lhs.shape[d] for d in lb)
    m = math.prod(lhs.shape[d] for d in lo)
    k = math.prod(lhs.shape[d] for d in lc)
    n = math.prod(rhs.shape[d] for d in ro)
    return A.reshape(b, m, k), W.reshape(b, k, n)


def conv_operands_3d(lhs: jax.Array, rhs: jax.Array, params: dict
                     ) -> tuple[jax.Array, jax.Array, str] | None:
    """Lower a ``conv_general_dilated`` to its im2col matmul operands.

    Returns ``(A [G,M,Kg], W [G,Kg,Ng], kind)`` or None for the rare
    ``batch_group_count > 1`` form (conv input-gradients), which has no
    single-SA streaming interpretation.
    """
    if params.get("batch_group_count", 1) != 1:
        return None
    dn = params["dimension_numbers"]
    groups = params.get("feature_group_count", 1)
    # canonicalize: lhs -> (N, *spatial, C), rhs -> (*spatial, I, O)
    lspec, rspec = dn.lhs_spec, dn.rhs_spec
    nsp = lhs.ndim - 2
    x = jnp.transpose(lhs, (lspec[0],) + tuple(lspec[2:]) + (lspec[1],))
    w = jnp.transpose(rhs, tuple(rspec[2:]) + (rspec[1], rspec[0]))
    ksp = w.shape[:nsp]
    cin_total = x.shape[-1]
    cin_g = w.shape[-2]                       # I per group
    cout_total = w.shape[-1]
    canon = jax.lax.ConvDimensionNumbers(
        lhs_spec=(0, nsp + 1) + tuple(range(1, nsp + 1)),
        rhs_spec=(nsp + 1, nsp) + tuple(range(nsp)),
        out_spec=(0, nsp + 1) + tuple(range(1, nsp + 1)))
    patches = jax.lax.conv_general_dilated_patches(
        x, ksp, params["window_strides"], params["padding"],
        lhs_dilation=params.get("lhs_dilation"),
        rhs_dilation=params.get("rhs_dilation"),
        dimension_numbers=canon)
    # feature dim of patches is (channel-major, then spatial); reorder to
    # (spatial..., channel) to match w.reshape(-1, cout) of HWIO kernels
    # (same convention as repro.apps.cnn.nets._im2col)
    m = math.prod(patches.shape[:-1])
    prodk = math.prod(ksp)
    p = patches.reshape(m, cin_total, prodk)
    A = jnp.transpose(p, (0, 2, 1))           # [M, prodk, C_total]
    if groups == 1:
        A = A.reshape(1, m, prodk * cin_total)
        W = w.reshape(1, prodk * cin_g, cout_total)
        return A, W, "conv"
    # grouped: channels split contiguously into G blocks on both sides
    cout_g = cout_total // groups
    A = A.reshape(m, prodk, groups, cin_g)
    A = jnp.transpose(A, (2, 0, 1, 3)).reshape(groups, m, prodk * cin_g)
    W = w.reshape(prodk * cin_g, groups, cout_g)
    W = jnp.transpose(W, (1, 0, 2))           # [G, Kg, Ng]
    return A, W, "dwconv" if cin_g == 1 else "conv"


class _Interpreter:
    def __init__(self, emit: Callable[[MatmulSite], None],
                 include_conv: bool = True):
        self.emit = emit
        self.include_conv = include_conv
        self.scope = _Scope()
        self.skipped: list[str] = []

    # ---------------------------------------------------------------- core
    def eval_closed(self, closed: jcore.ClosedJaxpr, args: Sequence):
        return self.eval_jaxpr(closed.jaxpr, closed.consts, args)

    def eval_jaxpr(self, jaxpr: jcore.Jaxpr, consts: Sequence,
                   args: Sequence):
        env: dict = {}

        def read(v):
            return v.val if isinstance(v, _Literal) else env[v]

        def write(v, val):
            env[v] = val

        assert len(jaxpr.constvars) == len(consts), \
            (len(jaxpr.constvars), len(consts))
        assert len(jaxpr.invars) == len(args), \
            (len(jaxpr.invars), len(args))
        for v, a in zip(jaxpr.constvars, consts):
            write(v, a)
        for v, a in zip(jaxpr.invars, args):
            write(v, a)

        # XLA-like liveness: free each value after its last textual use,
        # otherwise the interpreter pins every intermediate of the whole
        # forward simultaneously and peak memory dwarfs the jitted run
        live_out = {v for v in jaxpr.outvars
                    if not isinstance(v, _Literal)}
        last_use: dict = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if not isinstance(v, _Literal):
                    last_use[v] = i

        for i, eqn in enumerate(jaxpr.eqns):
            invals = [read(v) for v in eqn.invars]
            outvals = self.eval_eqn(eqn, invals)
            for v, val in zip(eqn.outvars, outvals):
                if not isinstance(v, jcore.DropVar):
                    write(v, val)
            for v in eqn.invars:
                if (not isinstance(v, _Literal) and last_use.get(v) == i
                        and v not in live_out):
                    env.pop(v, None)
        return [read(v) for v in jaxpr.outvars]

    # ---------------------------------------------------------------- eqns
    def eval_eqn(self, eqn, invals):
        prim = eqn.primitive
        name = prim.name
        if name == "dot_general":
            self.on_dot(eqn, invals)
        elif name == "conv_general_dilated" and self.include_conv:
            self.on_conv(eqn, invals)
        elif name in _CALL_LIKE:
            frame = _frame(eqn, str(eqn.params.get("name") or ""))
            closed = (eqn.params["jaxpr"] if "jaxpr" in eqn.params
                      else eqn.params["call_jaxpr"])
            if frame:
                self.scope.push(frame)
            try:
                return self.eval_closed(closed, invals)
            finally:
                if frame:
                    self.scope.pop()
        elif name in _CUSTOM_CALL:
            closed = eqn.params["call_jaxpr"]
            n = len(closed.jaxpr.invars)
            # custom_jvp/vjp pass num_consts leading residual args
            return self.eval_closed(closed, invals[len(invals) - n:])
        elif name in ("remat2", "remat", "checkpoint"):
            return self.eval_jaxpr(eqn.params["jaxpr"], (), invals)
        elif name == "scan":
            return self.eval_scan(eqn, invals)
        elif name == "while":
            return self.eval_while(eqn, invals)
        elif name == "cond":
            idx = int(invals[0])
            branch = eqn.params["branches"][idx]
            return self.eval_closed(branch, invals[1:])
        # default: bind the primitive as-is
        subfuns, bind_params = prim.get_bind_params(eqn.params)
        ans = prim.bind(*subfuns, *invals, **bind_params)
        return ans if prim.multiple_results else [ans]

    def on_dot(self, eqn, invals):
        lhs, rhs = invals
        A, W = dot_operands_3d(lhs, rhs, eqn.params["dimension_numbers"])
        self.emit(MatmulSite(self.scope.site_name(eqn), "dot_general",
                             A, W))

    def on_conv(self, eqn, invals):
        lhs, rhs = invals
        lowered = conv_operands_3d(lhs, rhs, eqn.params)
        if lowered is None:
            self.skipped.append(self.scope.site_name(eqn))
            return
        A, W, kind = lowered
        self.emit(MatmulSite(self.scope.site_name(eqn), kind, A, W))

    # ------------------------------------------------------- control flow
    def eval_scan(self, eqn, invals):
        p = eqn.params
        nc, ncarry, length = p["num_consts"], p["num_carry"], p["length"]
        consts = invals[:nc]
        carry = list(invals[nc:nc + ncarry])
        xs = invals[nc + ncarry:]
        order = range(length - 1, -1, -1) if p["reverse"] else range(length)
        n_ys = len(eqn.outvars) - ncarry
        ys: list[list] = [[None] * length for _ in range(n_ys)]
        for i in order:
            xi = [jax.lax.index_in_dim(x, i, 0, keepdims=False) for x in xs]
            self.scope.push(_frame(eqn, f"scan[{i}]"))
            try:
                outs = self.eval_closed(p["jaxpr"],
                                        consts + carry + xi)
            finally:
                self.scope.pop()
            carry = list(outs[:ncarry])
            for j, y in enumerate(outs[ncarry:]):
                ys[j][i] = y
        if length == 0:
            # zero-length scan still has [0, ...]-shaped ys outputs; build
            # them from the outvar avals (jnp.stack([]) would raise)
            stacked = [jnp.zeros(v.aval.shape, v.aval.dtype)
                       for v in eqn.outvars[ncarry:]]
        else:
            stacked = [jnp.stack(y) for y in ys]
        return carry + stacked

    def eval_while(self, eqn, invals):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_consts = invals[:cn]
        body_consts = invals[cn:cn + bn]
        carry = list(invals[cn + bn:])
        it = 0
        while True:
            # evaluate the condition with this interpreter too (avoids the
            # deprecated jax.core.eval_jaxpr; cond jaxprs rarely contain
            # matmuls, but if one does it is simply traced as well)
            pred = self.eval_closed(p["cond_jaxpr"],
                                    cond_consts + carry)[0]
            if not bool(pred):
                break
            self.scope.push(_frame(eqn, f"while[{it}]"))
            try:
                carry = list(self.eval_closed(p["body_jaxpr"],
                                              body_consts + carry))
            finally:
                self.scope.pop()
            it += 1
        return carry


def trace_fn(fn: Callable, *args, emit: Callable[[MatmulSite], None],
             include_conv: bool = True, name: str = ""):
    """Run ``fn(*args)`` under the matmul-discovering interpreter.

    Every executed ``dot_general``/conv is reported to ``emit`` as a
    :class:`MatmulSite` with concrete operands; the function's outputs are
    computed faithfully and returned, along with the list of site names
    that could not be lowered (conv input-gradients).

    Returns:
      (outputs, skipped_site_names)
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    flat, _ = jax.tree_util.tree_flatten(args)
    interp = _Interpreter(emit, include_conv=include_conv)
    if name:
        interp.scope.push(name)
    out_flat = interp.eval_closed(closed, flat)
    out_tree = jax.tree_util.tree_structure(out_shape)
    return jax.tree_util.tree_unflatten(out_tree, out_flat), interp.skipped
