"""Jitted public wrapper for the parallel BIC encoder."""
from __future__ import annotations

from functools import partial

import jax

from repro.core.bits import MANT_MASK

from .kernel import bic_encode_pallas
from .ref import bic_encode_ref


@partial(jax.jit, static_argnames=("mask", "use_pallas", "interpret"))
def bic_encode(x: jax.Array, mask: int = int(MANT_MASK),
               use_pallas: bool = True, interpret: bool | None = None):
    """Single-segment BIC encode of ``uint16[T, L]``.

    Returns ``(tx: uint16[T, L], inv: bool[T, L])``. The default mask is the
    paper's configuration (bf16 mantissa field).
    """
    if use_pallas:
        return bic_encode_pallas(x, mask=mask, interpret=interpret)
    return bic_encode_ref(x, mask=mask)
