"""The serving engine's own host spans (``serve.*``, ``repro.serve.spans``)
in a traced run, and the device's idle time inside them.

The harness's ``trace.load`` keeps the benchmark's ``bench.*`` spans.
The engine's spans lie in the same trace, on the same clock: when the
loaded trace lacks them, they are read again from the run's trace file.
That file is the newest ``bench_trace_*`` directory's under the
temporary directory (``cell.run`` makes it there), and it is taken only
if its ``bench.step`` spans give the very window of the loaded trace. A
program without these spans reads none, and the readers then return
None.
"""
from __future__ import annotations

import glob
import os
import tempfile

from bench.harness import trace

PREFIX = "serve."


def from_file(path: str) -> tuple[list, tuple | None]:
    """``serve.*`` spans of a trace file and its ``bench.step`` window."""
    import gzip
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    spans, steps = [], []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.name, e.start_ns, e.duration_ns))
                elif e.name == "bench.step":
                    steps.append((e.start_ns, e.start_ns + e.duration_ns))
    window = ((min(s for s, _ in steps), max(e for _, e in steps))
              if steps else None)
    return spans, window


def _from_run_file(ctx) -> list:
    dirs = sorted(glob.glob(os.path.join(tempfile.gettempdir(),
                                         "bench_trace_*")),
                  key=os.path.getmtime, reverse=True)
    for d in dirs:
        try:
            path = trace.find_xplane(d)
        except FileNotFoundError:
            continue
        spans, window = from_file(path)
        if window == (ctx.t0, ctx.t1):
            return spans
    return []


def spans(ctx) -> list:
    """``(name, start_ns, dur_ns)`` of the run's ``serve.*`` spans (read
    once per run and kept on ``ctx``); empty when the program has none."""
    found = getattr(ctx, "serve_spans", None)
    if found is None:
        found = [s for s in ctx.trace.spans if s[0].startswith(PREFIX)]
        if not found:
            found = _from_run_file(ctx)
        ctx.serve_spans = found
    return found


def _subtract(a: list, b: list) -> list:
    """Disjoint sorted intervals ``a`` less the disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while s < e and k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def idle_within(tr: trace.Trace, t0: float, t1: float, span_list: list,
                inside, outside=()) -> float:
    """Device-idle ns inside ``[t0, t1]`` and inside the union of the
    spans named in ``inside``, less the union of those in ``outside``
    (nested spans count once)."""
    def cover(names):
        return trace.union(trace._clip(
            [s for s in span_list if s[0] in names], t0, t1))
    region = _subtract(cover(inside), cover(outside))
    busy = trace.union(trace._clip(tr.ops, t0, t1))
    return sum(e - s for s, e in _subtract(region, busy))
