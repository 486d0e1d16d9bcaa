"""Jitted public wrapper for the transition-counter kernel."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import transitions_pallas
from .ref import transitions_ref


@partial(jax.jit, static_argnames=("mask", "use_pallas", "interpret"))
def count_transitions(x: jax.Array, mask: int = 0xFFFF,
                      use_pallas: bool = True,
                      interpret: bool | None = None) -> jax.Array:
    """Per-lane transition counts of a ``uint16[T, L]`` stream.

    ``use_pallas=False`` falls back to the pure-jnp oracle (useful inside
    programs that must lower for the CPU dry-run backend).
    """
    if use_pallas:
        return transitions_pallas(x, mask=mask, interpret=interpret)
    return transitions_ref(x, mask=mask)
