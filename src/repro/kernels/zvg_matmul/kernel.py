"""Pallas TPU kernel: output-stationary matmul with zero-value tile gating.

The paper's zero-value clock gating freezes a PE when its input operand is
zero. TPUs cannot gate individual MXU cells, but the SAME insight applies at
the granularity the hardware does expose: a VMEM *tile* of activations that
is entirely zero contributes nothing to the product, so the kernel skips the
MXU pass and the accumulator update for that tile (``@pl.when``), saving both
compute energy and VMEM<->MXU traffic. ReLU-sparse CNN activations and
token-dropped MoE dispatch buffers hit this path in practice.

Tile granularity: savings here materialize only when an entire [BM, BK]
activation tile is zero, and what is saved is the MXU pass + operand
traffic -- not the per-flop clock load the ASIC gates. The serving decode
path closes most of that gap: :mod:`repro.kernels.zvg_matmul.fused` gates
at PER-REQUEST-ROW granularity (``gated_row_matmul``), which for decode
(one token row per request) is the finest granularity the operand stream
exposes, and fuses the coding-menu counter accumulation into the same
pass. The fine-grained per-PE proposal itself is quantified by the
analytic model (``repro.core.systolic`` + ``repro.core.power``). The
``gated`` output of THIS kernel is the tile-granular analogue of the
paper's gated-slot counter, and its ``a != 0`` gate matches the reference
``gated`` semantics (sign-of-zero is not tracked at tile granularity; the
fused row kernel gates on raw value bits instead, keeping -0.0 and
subnormal rows live so live rows are bit-identical to XLA).

Dataflow: classic output-stationary tiling, grid = (M/BM, N/BN, K/BK) with K
as the sequential minor axis; an f32 VMEM scratch accumulates the (BM, BN)
output tile across the K sweep (numerically identical to a dense matmul --
skipped tiles are exact zeros). A second output reports which (m, k) blocks
were gated (written once, on the n == 0 sweep).

MXU alignment: BM/BN/BK default to 128 to match the 128x128 MXU; bf16 inputs
accumulate in f32 (``preferred_element_type``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _zvg_matmul_kernel(a_ref, b_ref, o_ref, g_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    nonzero = jnp.any(a != 0)

    @pl.when(nonzero)
    def _mac():
        acc_ref[...] += jnp.dot(a, b_ref[...],
                                preferred_element_type=jnp.float32)

    n = pl.program_id(1)

    @pl.when(n == 0)
    def _stats():
        g_ref[0, 0] = jnp.where(nonzero, 0, 1).astype(jnp.int32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def zvg_matmul_pallas(a: jax.Array, b: jax.Array,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128, interpret: bool | None = None):
    """Zero-gated ``a @ b`` with gating statistics.

    Args:
      a: ``[M, K]`` bf16/f32 activations (zero tiles are skipped).
      b: ``[K, N]`` bf16/f32 weights.
    Returns:
      ``(out: f32[M, N], gated: int32[M/BM, K/BK])``.
    """
    interpret = resolve_interpret(interpret)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    pm, pk, pn = (-M) % block_m, (-K) % block_k, (-N) % block_n
    ap = jnp.pad(a, ((0, pm), (0, pk)))
    bp = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp = ap.shape
    Np = bp.shape[1]
    grid = (Mp // block_m, Np // block_n, Kp // block_k)

    out, gated = pl.pallas_call(
        _zvg_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
            pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
            pl.BlockSpec((1, 1), lambda m, n, k: (m, k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], grid[2]), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(ap, bp)
    return out[:M, :N], gated
