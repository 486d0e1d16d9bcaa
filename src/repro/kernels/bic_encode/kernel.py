"""Pallas TPU kernel: parallel bus-invert encoder (single segment).

The paper's encoder is a sequential recurrence (the invert decision at cycle
t depends on the transmitted value at t-1). Ported naively, that serializes
the T axis -- hostile to both the VPU and the MXU. We instead exploit an
algebraic identity that makes BIC *parallelizable*:

Because inverting a segment flips ALL of its bits, the Hamming distance
between x_t and the previous transmitted word is either d_t or (w - d_t),
where d_t = ham(x_t, x_{t-1}) over the segment depends only on the RAW
stream. Hence the invert bit follows

    inv_t = inv_{t-1} ? (2 d_t < w) : (2 d_t > w)

i.e. each step applies one of four boolean functions {const0, const1,
identity, negation} to the previous state. Function composition is
associative, so the whole recurrence is an ``associative_scan`` over
(f(0), f(1)) pairs -- O(log T) depth, fully vectorized across lanes. The
d_t values themselves are embarrassingly parallel (shifted-input trick).

This is the DESIGN.md "hardware adaptation" in action (docs/kernels.md): the
ASIC encoder is a tiny serial circuit wired into the weight bus; the TPU
equivalent is a data-parallel scan over the same stream, producing the SAME
transmitted bits -- so toggle counts measured on the kernel's output equal
the ones the paper's encoder would produce, at MXU-friendly throughput.

Grid/VMEM: blocks of (TB, LB) with the T axis as the sequential minor grid
dimension; a (1, LB) scratch carries the boolean state across T blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bits import segment_width
from repro.kernels.platform import resolve_interpret


def _compose(f, g):
    """Compose step functions: h = g after f, represented as (f0, f1) pairs."""
    f0, f1 = f
    g0, g1 = g
    return (jnp.where(f0, g1, g0), jnp.where(f1, g1, g0))


def _bic_kernel(x_ref, xprev_ref, tx_ref, inv_ref, state_ref, *,
                mask: int, width: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...]
    d = jax.lax.population_count((x ^ xprev_ref[...]) & jnp.uint16(mask))
    d = d.astype(jnp.int32)
    a = d * 2 > width   # f(0): invert decision if previous state was 0
    b = d * 2 < width   # f(1): invert decision if previous state was 1

    # prefix-compose the step functions along the block's T axis
    pre0, pre1 = jax.lax.associative_scan(_compose, (a, b), axis=0)
    inv0 = state_ref[...] != 0                     # carried state, [1, LB]
    inv = jnp.where(inv0, pre1, pre0)              # [TB, LB]

    tx_ref[...] = jnp.where(inv, x ^ jnp.uint16(mask), x)
    inv_ref[...] = inv
    state_ref[...] = inv[-1:].astype(state_ref.dtype)


def bic_encode_pallas(x: jax.Array, mask: int,
                      block_t: int = 256, block_l: int = 128,
                      interpret: bool | None = None):
    """Single-segment BIC encode of ``uint16[T, L]`` via the Pallas kernel.

    Returns ``(tx: uint16[T, L], inv: bool[T, L])``; bus assumed to idle at 0.
    """
    interpret = resolve_interpret(interpret)
    x = x.astype(jnp.uint16)
    T, L = x.shape
    width = segment_width(mask)
    xprev = jnp.concatenate([jnp.zeros((1, L), jnp.uint16), x[:-1]], axis=0)

    pt = (-T) % block_t
    pl_ = (-L) % block_l
    if pt:
        x = jnp.concatenate([x, jnp.repeat(x[-1:], pt, axis=0)], axis=0)
        xprev = jnp.concatenate([xprev, jnp.repeat(x[-1:], pt, axis=0)],
                                axis=0)
    if pl_:
        x = jnp.pad(x, ((0, 0), (0, pl_)))
        xprev = jnp.pad(xprev, ((0, 0), (0, pl_)))
    Tp, Lp = x.shape
    grid = (Lp // block_l, Tp // block_t)

    tx, inv = pl.pallas_call(
        functools.partial(_bic_kernel, mask=int(mask), width=width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, block_l), lambda l, t: (t, l)),
            pl.BlockSpec((block_t, block_l), lambda l, t: (t, l)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, block_l), lambda l, t: (t, l)),
            pl.BlockSpec((block_t, block_l), lambda l, t: (t, l)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, Lp), jnp.uint16),
            jax.ShapeDtypeStruct((Tp, Lp), jnp.bool_),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_l), jnp.int32)],
        interpret=interpret,
    )(x, xprev)
    return tx[:T, :L], inv[:T, :L]
