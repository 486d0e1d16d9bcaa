"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, the Pallas
interpreter on every other backend."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument: ``None`` means
    "interpret off TPU", so no TPU path runs the interpreter unless a
    caller asks for it explicitly."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
