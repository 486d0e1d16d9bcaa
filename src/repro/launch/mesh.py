"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state -- the dry-run must set XLA_FLAGS before any
device initialization.

Mesh layout (TPU v5e pods):
  single pod : (data=16, model=16)               = 256 chips
  multi-pod  : (pod=2, data=16, model=16)        = 512 chips
The "pod" axis composes with "data" for batch/FSDP sharding (DCN-crossing
collectives stay on the gradient/FSDP path); "model" carries TP/SP/EP and
stays inside the pod's ICI domain.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int | None = None, data: int | None = None):
    """A small ("data", "model") mesh over the host's devices
    (tests / examples / single-host serving).

    * ``model`` only: the requested TP width is HONORED (it decides
      memory and layout, so silently shrinking it would lie to the
      caller) and data is whatever is left (``n // model``) -- on an
      8-device host ``model=3`` gives a 2x3 mesh over 6 devices, idling
      two. Only an unsatisfiable request (``model > n``) falls back, to
      ``model = n``.
    * ``data`` and ``model``: exactly that shape, over the first
      ``data * model`` devices -- a 2x2 mesh on an 8-device host is
      legitimate (the suite in ``tests/multidevice`` relies on it).
    """
    devs = jax.devices()
    n = len(devs)
    model = max(model or 1, 1)
    if data is None:
        model = min(model, n)
        data = n // model
    if data < 1 or data * model > n:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices; "
            f"host has {n}")
    arr = np.asarray(devs[:data * model]).reshape(data, model)
    return jax.sharding.Mesh(arr, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
