"""Host spans and the host-sync count of the serving engine.

Each phase of ``ServeEngine.step`` runs under a ``serve.<phase>`` span.
A span is a ``jax.profiler.TraceAnnotation``: it records nothing unless
a profiler trace is active (about a microsecond per span when none is),
and then it lands on the trace's host plane, on the same clock as the
device's operations, so every gap in the device's work can be put down
to the engine phase the host was in. Names are fixed strings; request
ids travel as keyword arguments (stats of the trace event).

Every blocking device-to-host fetch the engine and its power accountant
make goes through :func:`fetch`, which runs it under ``serve.sync`` and
counts it in ``stats["host_syncs"]``. See docs/observability.md for the
span tree.
"""
from __future__ import annotations

import jax


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """Context manager for the engine phase ``serve.<name>``."""
    return jax.profiler.TraceAnnotation(f"serve.{name}", **ids)


def fetch(x, stats: dict):
    """``jax.device_get(x)`` under a ``serve.sync`` span, counted in
    ``stats["host_syncs"]``."""
    with span("sync"):
        out = jax.device_get(x)
    stats["host_syncs"] += 1
    return out
