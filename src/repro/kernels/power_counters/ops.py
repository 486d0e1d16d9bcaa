"""Jitted public wrapper for the fused power-counter pass.

``edge_counters`` is the one entry point the rest of the stack uses
(:func:`repro.core.systolic.sa_design_report` calls it once per operand
edge). The ``backend`` switch selects the fused Pallas kernel or the
pure-JAX reference:

* ``"pallas"`` -- the fused kernel; ``interpret`` defaults to True off
  TPU so CPU CI runs the identical kernel body through the interpreter.
* ``"ref"``    -- the per-menu-entry pure-JAX path (``ref.py``).
* ``"auto"``   -- the default: the fused kernel on TPU (Mosaic), the
  reference on CPU/GPU, where XLA fuses the small passes well and the
  interpreter would only add overhead. Force ``"pallas"`` on CPU to
  exercise interpret mode (the differential suite does).

The per-process default can be overridden with the environment variable
``REPRO_COUNTER_BACKEND`` (e.g. ``=pallas`` to force the fused path
everywhere), which is how CI pins the kernel job to interpret mode
without touching call sites.
"""
from __future__ import annotations

import os
from functools import partial

import jax

from repro.kernels.platform import resolve_interpret

from .kernel import fused_counters_pallas
from .ref import fused_counters_ref
from .spec import CounterSpec

BACKENDS = ("auto", "pallas", "ref")


def default_backend() -> str:
    """Process-wide default: ``$REPRO_COUNTER_BACKEND`` or ``"auto"``."""
    return os.environ.get("REPRO_COUNTER_BACKEND", "auto")


def resolve_backend(backend: str | None) -> str:
    """Normalize a backend name to ``"pallas"`` or ``"ref"``."""
    backend = backend or default_backend()
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown counter backend {backend!r}; choose from {BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


@partial(jax.jit, static_argnames=("spec", "backend", "interpret",
                                   "block_t", "block_l"))
def _edge_counters(bits: jax.Array, spec: CounterSpec, backend: str,
                   interpret: bool, block_t: int | None,
                   block_l: int | None) -> dict:
    """Jitted core; ``backend`` must already be resolved to
    ``"pallas"``/``"ref"`` so the jit cache is keyed by what actually
    runs, not by an unresolved ``None``."""
    if backend == "pallas":
        counts, rowzeros = fused_counters_pallas(
            bits, spec, block_t=block_t, block_l=block_l,
            interpret=interpret)
    else:
        counts, rowzeros = fused_counters_ref(bits, spec)
    out = {name: counts[i] for i, name in enumerate(spec.rows)}
    out["rowzeros"] = rowzeros
    return out


def edge_counters(bits: jax.Array, spec: CounterSpec,
                  backend: str | None = None,
                  interpret: bool | None = None,
                  block_t: int | None = None,
                  block_l: int | None = None) -> dict:
    """Fused counter pass over one edge stream ``uint16[T, L]``.

    Returns ``{row_name: int32[L]}`` for every row of ``spec.rows`` plus
    ``"rowzeros": int32[T]`` (per-cycle zero words, for the both-edges
    gated-overlap correction). ``interpret=None`` auto-selects: compiled
    on TPU, interpreter elsewhere.

    Backend/env resolution happens HERE, outside the jit, so the jitted
    core is cached under the resolved name and a changed
    ``REPRO_COUNTER_BACKEND`` takes effect on the next direct call.
    (A caller that jitted itself over ``backend=None`` -- e.g. a
    monitoring path tracing a default ``MonitorConfig`` -- still bakes
    the resolution current at ITS first trace into its own cache; set
    the env before the process starts, or pass an explicit backend, to
    steer those.)
    """
    resolved = resolve_backend(backend)
    return _edge_counters(bits, spec, resolved,
                          resolve_interpret(interpret), block_t,
                          block_l)
