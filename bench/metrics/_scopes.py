"""Device time of the decode program's operations by the program's named
scope (``jax.named_scope``: ``moe``, ``mla``, ...), in a traced run.

The harness's ``trace.load`` keeps each device operation's name and
time, not the scope it came from. The profiler records the scope as the
``tf_op`` stat of each operation's metadata (the op's name stack, e.g.
``jit(<unknown>)/while/body/closed_call/b0/moe/experts/dot_general``);
``jax.profiler.ProfileData`` does not expose metadata stats, so the
run's trace file is read again here, with a schema of the few fields
needed (``XSpace`` of the profiler's ``xplane.proto``). The file is the
newest ``bench_trace_*`` directory's under the temporary directory
(``cell.run`` makes it there), taken only if its ``bench.step`` spans
give the very window of the loaded trace, the rule ``_serve_spans``
keeps.

An operation belongs to a scope when the scope is a whole segment of its
name stack. Some operations XLA makes carry none: the ``ragged-dot``
custom calls of the grouped expert matmuls, and the float32 -> bfloat16
casts of the scanned layers' weights that it hoists out of the layer
scan. Such an operation takes the common name stack of the operations of
its compiled program that consume its result (the operands are named in
the HLO text the trace gives each operation): the matmuls take
``moe/experts``, while the hoisted casts, consumed by the scan itself,
keep none and count in the decode program's time (``decode_step_ms``),
in no scope's. The decode program's layer scan is one ``while`` operation
whose time holds every operation of its body: it is left out, and so is
any other ``while``. Operations are counted when they start in the window
inside a run of one of the programs asked for (``_programs.DECODE``
unless said). A program without the scope reads none, and the readers
return None.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
import tempfile

from bench.harness import trace
from bench.metrics._programs import DECODE, device_seconds

_MESSAGES = None
#: an operand's name in an operation's HLO text
_OPERAND = re.compile(r"%([\w.\-]+)")


def _messages():
    """``XSpace`` message class of a schema holding only the fields read
    here (field numbers of the profiler's ``xplane.proto``)."""
    global _MESSAGES
    if _MESSAGES is not None:
        return _MESSAGES
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane_subset.proto",
                                           package="bench_xplane")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for num, fname, ftype, label, tname in fields:
            fd = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                fd.type_name = f".bench_xplane.{tname}"
    rep, opt = F.LABEL_REPEATED, F.LABEL_OPTIONAL
    msg("XStat", (1, "metadata_id", F.TYPE_INT64, opt, None),
        (5, "str_value", F.TYPE_STRING, opt, None))
    msg("XEventMetadata", (1, "id", F.TYPE_INT64, opt, None),
        (2, "name", F.TYPE_STRING, opt, None),
        (5, "stats", F.TYPE_MESSAGE, rep, "XStat"))
    msg("XStatMetadata", (1, "id", F.TYPE_INT64, opt, None),
        (2, "name", F.TYPE_STRING, opt, None))
    msg("EventEntry", (1, "key", F.TYPE_INT64, opt, None),
        (2, "value", F.TYPE_MESSAGE, opt, "XEventMetadata"))
    msg("StatEntry", (1, "key", F.TYPE_INT64, opt, None),
        (2, "value", F.TYPE_MESSAGE, opt, "XStatMetadata"))
    msg("XEvent", (1, "metadata_id", F.TYPE_INT64, opt, None),
        (2, "offset_ps", F.TYPE_INT64, opt, None),
        (3, "duration_ps", F.TYPE_INT64, opt, None))
    msg("XLine", (2, "name", F.TYPE_STRING, opt, None),
        (3, "timestamp_ns", F.TYPE_INT64, opt, None),
        (4, "events", F.TYPE_MESSAGE, rep, "XEvent"))
    msg("XPlane", (2, "name", F.TYPE_STRING, opt, None),
        (3, "lines", F.TYPE_MESSAGE, rep, "XLine"),
        (4, "event_metadata", F.TYPE_MESSAGE, rep, "EventEntry"),
        (5, "stat_metadata", F.TYPE_MESSAGE, rep, "StatEntry"))
    msg("XSpace", (1, "planes", F.TYPE_MESSAGE, rep, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    _MESSAGES = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _MESSAGES


def _stack(tf_op: str) -> str:
    """An operation's name stack: its ``tf_op`` when that is a path (XLA's
    own custom calls carry a bare name, e.g. ``ragged-dot-none:``)."""
    return tf_op if "/" in tf_op else ""


def _common(stacks: list) -> str:
    """The longest run of leading segments the stacks share."""
    if not stacks:
        return ""
    parts = [st.split("/") for st in stacks]
    n = 0
    while all(len(p) > n and p[n] == parts[0][n] for p in parts):
        n += 1
    return "/".join(parts[0][:n])


def _inherit(info: dict, ids) -> dict:
    """Name stack of each operation of one program (metadata ids ``ids``):
    its own, else the common stack of the operations that consume its
    result (found by name among the operands in their HLO text)."""
    by_name = {info[k][2].lstrip("%"): k for k in ids}
    users = {k: [] for k in ids}
    for k in ids:
        for o in info[k][3]:
            if by_name.get(o, k) != k:
                users[by_name[o]].append(k)
    # depth first, without recursion: an operation's users are resolved
    # before it is
    out = {}
    for root in ids:
        todo, path = [(root, False)], set()
        while todo:
            k, users_done = todo.pop()
            if k in out:
                continue
            if info[k][0]:
                out[k] = info[k][0]
            elif users_done:
                path.discard(k)
                out[k] = _common([out[u] for u in users[k] if out.get(u)])
            else:
                path.add(k)
                todo.append((k, True))
                todo.extend((u, False) for u in users[k]
                            if u not in out and u not in path)
    return out


def read_file(path: str) -> tuple[list, list, tuple | None]:
    """``(ops, modules, window)`` of a trace file: device operations as
    ``(name stack, start_ns, dur_ns, is_while, hlo name)``, program runs
    as ``(program, start_ns, dur_ns)``, and the ``bench.step`` window.
    An operation with no name stack of its own takes the common stack of
    the operations of its program that consume its result, and keeps
    ``""`` when none has one."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        space = _messages().FromString(fh.read())
    ops, modules, steps = [], [], []
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/host:"):
            ids = {k for k, m in meta.items() if m.name == "bench.step"}
            # whole nanoseconds, as ``ProfileData`` gives them, so the
            # window compares equal to the loaded trace's
            for line in plane.lines:
                for ev in line.events:
                    if ev.metadata_id in ids:
                        s = line.timestamp_ns + ev.offset_ps // 1000
                        steps.append((float(s),
                                      float(s + ev.duration_ps // 1000)))
            continue
        if not plane.name.startswith("/device:TPU:"):
            continue
        tf_op = {s.key for s in plane.stat_metadata
                 if s.value.name == "tf_op"}
        info = {}
        for k, m in meta.items():
            name = next((st.str_value for st in m.stats
                         if st.metadata_id in tf_op), "")
            hlo, _, text = m.name.partition(" = ")
            info[k] = (_stack(name), " while(" in m.name, hlo,
                       set(_OPERAND.findall(text)))
        runs, events = [], []
        for line in plane.lines:
            for ev in line.events:
                s = line.timestamp_ns + ev.offset_ps / 1000
                d = ev.duration_ps / 1000
                if line.name == "XLA Ops":
                    events.append((ev.metadata_id, s, d))
                elif line.name == "XLA Modules":
                    # the module's name with its id: one compiled program
                    m = meta.get(ev.metadata_id)
                    runs.append((s, s + d, m.name if m else ""))
        runs.sort()
        starts = [r[0] for r in runs]
        where = []
        for k, s, _ in events:
            i = bisect.bisect_right(starts, s) - 1
            where.append(runs[i][2] if i >= 0 and s <= runs[i][1] else None)
        members = {}
        for (k, _, _), prog in zip(events, where):
            if k in info:
                members.setdefault(prog, set()).add(k)
        stacks = {prog: (_inherit(info, ids) if prog is not None
                         else {k: info[k][0] for k in ids})
                  for prog, ids in members.items()}
        for (k, s, d), prog in zip(events, where):
            _, is_while, hlo, _ = info.get(k, ("", False, "", ()))
            ops.append((stacks.get(prog, {}).get(k, ""), s, d, is_while,
                        hlo))
        modules.extend((trace.program_name(n), s, e - s) for s, e, n in runs)
    window = ((min(s for s, _ in steps), max(e for _, e in steps))
              if steps else None)
    return ops, modules, window


def _run_file(ctx):
    """The run's own trace, read once per run and kept on ``ctx``."""
    found = getattr(ctx, "scoped_trace", None)
    if found is not None:
        return found
    found = ([], [])
    dirs = sorted(glob.glob(os.path.join(tempfile.gettempdir(),
                                         "bench_trace_*")),
                  key=os.path.getmtime, reverse=True)
    for d in dirs:
        try:
            path = trace.find_xplane(d)
        except FileNotFoundError:
            continue
        ops, modules, window = read_file(path)
        if window == (ctx.t0, ctx.t1):
            found = (ops, modules)
            break
    ctx.scoped_trace = found
    return found


def scoped_ops(ops, modules, t0: float, t1: float, scope: str,
               programs=DECODE):
    """The operations under ``scope`` that start in ``[t0, t1]`` inside a
    run of one of ``programs``, ``while`` ops left out."""
    runs = sorted((s, s + d) for n, s, d in modules if n in programs)
    starts = [s for s, _ in runs]
    for op in ops:
        name, s, _, is_while, _ = op
        if is_while or scope not in name.split("/") or not t0 <= s <= t1:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s <= runs[i][1]:
            yield op


def scope_ns(ops, modules, t0: float, t1: float, scope: str,
             programs=DECODE) -> float:
    """Device ns of ``scoped_ops``."""
    return sum(op[2] for op in scoped_ops(ops, modules, t0, t1, scope,
                                          programs))


def per_decode_run(ctx, scope: str) -> tuple[int, float] | None:
    """``(decode runs, device seconds under scope)`` in the window, or
    None when the program has no operation under the scope."""
    runs, _ = device_seconds(ctx, DECODE)
    ops, modules = _run_file(ctx)
    if not runs or not ops:
        return None
    ns = scope_ns(ops, modules, ctx.t0, ctx.t1, scope)
    if not ns:
        return None
    return runs, ns * 1e-9
