"""Reduction of a profiler trace to device busy time, per-program device
time and idle gaps attributed to the benchmark's host spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``: device planes are ``/device:TPU:<n>``,
their ``XLA Modules`` line holds one event per program run and their
``XLA Ops`` line one per operation; the host plane holds the
``bench.<name>`` spans. All on one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    ops: list            # (name, start_ns, dur_ns), all device planes
    modules: list        # (name, start_ns, dur_ns)
    spans: list          # (name, start_ns, dur_ns), host bench.* spans
    n_devices: int


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_name(event_name: str) -> str:
    """A program's stable name: the module name without its run id."""
    return _SUFFIX.sub("", event_name)


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` (or a gzipped one, ``.gz``)."""
    import gzip
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        name = plane.name
        if re.match(r"^/device:TPU:\d+$", name):
            devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((program_name(e.name), e.start_ns,
                                    e.duration_ns) for e in line.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith("bench."))
    return Trace(ops, modules, spans, devices)


def window(tr: Trace, span: str = "bench.step") -> tuple[float, float]:
    """First start and last end of the host's ``span`` events."""
    steps = [(s, s + d) for n, s, d in tr.spans if n == span]
    if not steps:
        raise ValueError(f"no {span} spans in the trace")
    return min(s for s, _ in steps), max(e for _, e in steps)


def _clip(events, t0, t1):
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield a, b


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace, t0: float, t1: float) -> float:
    """Union of device-busy intervals inside ``[t0, t1]``, averaged over
    the devices (one chip: the plain union)."""
    return sum(b - a for a, b in union(_clip(tr.ops, t0, t1))) / max(
        tr.n_devices, 1)


def program_time(tr: Trace, t0: float, t1: float) -> dict[str, tuple]:
    """``{program: (runs, device ns)}`` of runs that start in the window."""
    out: dict[str, list] = {}
    for name, s, d in tr.modules:
        if t0 <= s <= t1:
            v = out.setdefault(name, [0, 0.0])
            v[0] += 1
            v[1] += d
    return {k: tuple(v) for k, v in out.items()}


def op_time(tr: Trace, t0: float, t1: float,
            match=lambda name: True) -> tuple[int, float]:
    """``(count, device ns)`` of operations whose name ``match``es."""
    n, tot = 0, 0.0
    for name, s, d in tr.ops:
        if t0 <= s <= t1 and match(name):
            n += 1
            tot += d
    return n, tot


def top_ops(tr: Trace, t0: float, t1: float, k: int = 10) -> list:
    acc: dict[str, float] = {}
    for name, s, d in tr.ops:
        if t0 <= s <= t1:
            acc[name] = acc.get(name, 0.0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v * 1e-9] for n, v in top]


def idle_gaps(tr: Trace, t0: float, t1: float, k: int = 10) -> list:
    """Device-idle time inside the window, summed by the innermost host
    span that covered each gap's middle (``none`` when no span did)."""
    busy = union(_clip(tr.ops, t0, t1))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    spans = sorted(((s, s + d, n) for n, s, d in tr.spans),
                   key=lambda x: x[0])
    starts = [s for s, _, _ in spans]
    acc: dict[str, float] = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        # spans nest (an accountant call inside a step): the innermost
        # cover is the latest-starting span that still covers ``mid``
        name = "none"
        i = bisect.bisect_right(starts, mid) - 1
        for j in range(i, max(i - 256, -1), -1):
            if spans[j][1] >= mid:
                name = spans[j][2]
                break
        acc[name] = acc.get(name, 0.0) + (b - a)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v * 1e-9] for n, v in top]
