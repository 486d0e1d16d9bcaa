"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel directory contains ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jitted public wrapper) and ``ref.py`` (pure-jnp oracle used by
the allclose tests). ``interpret=None``, every kernel's default, compiles
with Mosaic on a TPU and runs the Pallas interpreter elsewhere
(:mod:`repro.kernels.platform`).
"""
from .bic_encode.ops import bic_encode  # noqa: F401
from .power_counters.ops import edge_counters  # noqa: F401
from .power_counters.spec import CounterSpec  # noqa: F401
from .transitions.ops import count_transitions  # noqa: F401
from .zvg_matmul.ops import zvg_matmul  # noqa: F401
