"""Pallas TPU kernel: streaming bit-transition counter.

Counts per-lane Hamming transitions of a ``uint16[T, L]`` stream -- the inner
loop of all switching-activity accounting (docs/kernels.md): every register
on an SA stream's path sees the same value sequence time-shifted, so these
per-stream transition counts, multiplied by path length, ARE the paper's
pipeline toggle totals (no cycle-level simulation). The stream is tiled into
``(TB, LB)`` VMEM blocks; the cross-block boundary term is handled by feeding
the kernel a one-row-shifted copy of the input (no carry needed), and the
per-lane totals are accumulated in the revisited output block across the
sequential T grid axis.

TPU mapping notes:
  * uint16 VREG tiling wants (32, 128)-aligned blocks; the default
    ``block=(256, 128)`` keeps the VMEM working set at 3 x 256 x 128 x 2B
    (x, xprev) + 128 x 4B (acc) ~ 196 KiB << 16 MiB VMEM.
  * XOR + population_count + integer add all map to the VPU; there is no MXU
    work, so the kernel is bandwidth-bound: roofline = 2 bytes/element read
    twice -> ~4 B/elem at 819 GB/s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def _transitions_kernel(x_ref, xprev_ref, o_ref, *, mask: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    diff = (x_ref[...] ^ xprev_ref[...]) & jnp.uint16(mask)
    pc = jax.lax.population_count(diff).astype(jnp.int32)
    o_ref[...] += pc.sum(axis=0, keepdims=True)


def transitions_pallas(x: jax.Array, mask: int = 0xFFFF,
                       init: jax.Array | None = None,
                       block_t: int = 256, block_l: int = 128,
                       interpret: bool | None = None) -> jax.Array:
    """Per-lane transition counts via the Pallas kernel.

    Args/returns as :func:`repro.kernels.transitions.ref.transitions_ref`.
    ``interpret=None`` compiles with Mosaic on a TPU and runs the
    interpreter elsewhere.
    """
    interpret = resolve_interpret(interpret)
    x = x.astype(jnp.uint16)
    T, L = x.shape
    if init is None:
        init = jnp.zeros((L,), jnp.uint16)
    xprev = jnp.concatenate([init[None].astype(jnp.uint16), x[:-1]], axis=0)

    # pad to block multiples; padded rows repeat the last row (no transitions)
    # and padded lanes are zeros (no transitions).
    pt = (-T) % block_t
    pl_ = (-L) % block_l
    if pt:
        x = jnp.concatenate([x, jnp.repeat(x[-1:], pt, axis=0)], axis=0)
        xprev = jnp.concatenate([xprev, jnp.repeat(x[-1:], pt, axis=0)], axis=0)
    if pl_:
        x = jnp.pad(x, ((0, 0), (0, pl_)))
        xprev = jnp.pad(xprev, ((0, 0), (0, pl_)))
    Tp, Lp = x.shape
    grid = (Lp // block_l, Tp // block_t)

    out = pl.pallas_call(
        functools.partial(_transitions_kernel, mask=int(mask)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, block_l), lambda l, t: (t, l)),
            pl.BlockSpec((block_t, block_l), lambda l, t: (t, l)),
        ],
        out_specs=pl.BlockSpec((1, block_l), lambda l, t: (0, l)),
        out_shape=jax.ShapeDtypeStruct((1, Lp), jnp.int32),
        interpret=interpret,
    )(x, xprev)
    return out[0, :L]
