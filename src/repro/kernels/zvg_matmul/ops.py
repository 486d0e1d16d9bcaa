"""Jitted public wrapper for the zero-gated matmul."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import zvg_matmul_pallas
from .ref import zvg_matmul_ref


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                   "use_pallas", "interpret"))
def zvg_matmul(a: jax.Array, b: jax.Array,
               block_m: int = 128, block_n: int = 128, block_k: int = 128,
               use_pallas: bool = True, interpret: bool | None = None):
    """Zero-gated matmul: ``(f32[M, N], gated int32[M/BM, K/BK])``.

    Numerically identical to ``a @ b``; the gating only skips work that is
    exactly zero. ``use_pallas=False`` selects the jnp oracle path.
    """
    if use_pallas:
        return zvg_matmul_pallas(a, b, block_m=block_m, block_n=block_n,
                                 block_k=block_k, interpret=interpret)
    return zvg_matmul_ref(a, b, block_m=block_m, block_k=block_k)
