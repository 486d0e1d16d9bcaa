"""Readers of the serving engine's own spans (``serve.*``): device-idle
time inside the accountant and the rest of the engine step, and host
syncs per step -- on synthetic traces, on the recorded chip trace (which
predates the spans, so they read nothing) and on a traced CPU run of the
whole harness, where they find the run's trace file by its window."""
import os
import sys
import tempfile
import types

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401
import _tiny  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

from bench.harness import spec, trace  # noqa: E402
from bench.metrics import _serve_spans  # noqa: E402

NEW = ("accountant_idle_ms_per_step", "engine_idle_ms_per_step",
       "host_syncs_per_step")
CELL = "qwen05b.offline.monitored"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "qwen_offline_monitored_3s.xplane.pb.gz")


def _ctx(tr, steps=2, monitored=True):
    t0, t1 = trace.window(tr)
    return types.SimpleNamespace(trace=tr, t0=t0, t1=t1, monitored=monitored,
                                 window={"engine_steps": steps})


def _read(name, ctx):
    return spec.load_metric(name).read(ctx)


#: two engine steps, 0-100 and 100-200 ns: device busy 0-10, 40-60,
#: 110-150; the accountant at 10-40 (its counter pass inside, nested in
#: the benchmark's own wrapper) and 110-160; syncs in both steps
SYNTH = trace.Trace(
    ops=[("a", 0, 10), ("b", 40, 20), ("c", 110, 40)], modules=[],
    spans=[("bench.step", 0, 100), ("serve.step", 0, 100),
           ("serve.accountant", 10, 30), ("bench.accountant", 12, 20),
           ("serve.accountant.counters", 15, 10), ("serve.sync", 30, 5),
           ("bench.step", 100, 100), ("serve.step", 100, 100),
           ("serve.accountant", 110, 50), ("serve.sync", 150, 5),
           ("serve.sync", 170, 5)],
    n_devices=1)


def test_idle_within_unions_nested_spans_and_takes_out_outside():
    s = SYNTH.spans
    # idle inside the accountant: 10-40 and 150-160
    assert _serve_spans.idle_within(SYNTH, 0, 200, s,
                                    ("serve.accountant",)) == 40
    # the rest of the steps: 60-110 and 160-200
    assert _serve_spans.idle_within(SYNTH, 0, 200, s, ("serve.step",),
                                    ("serve.accountant",)) == 90
    assert _serve_spans.idle_within(SYNTH, 0, 50, s, ("serve.step",),
                                    ("serve.accountant",)) == 0
    assert _serve_spans.idle_within(SYNTH, 0, 200, s, ("none",)) == 0


def test_readers_on_a_synthetic_trace():
    ctx = _ctx(SYNTH)
    assert _read("accountant_idle_ms_per_step", ctx) == pytest.approx(2e-5)
    assert _read("engine_idle_ms_per_step", ctx) == pytest.approx(4.5e-5)
    assert _read("host_syncs_per_step", ctx) == 1.5
    # accountant plus the rest of the step is all the idle time here
    idle = 200 - trace.busy_ns(SYNTH, 0, 200)
    assert (_read("accountant_idle_ms_per_step", ctx)
            + _read("engine_idle_ms_per_step", ctx)) * 2e6 == pytest.approx(
                idle)
    assert _read("accountant_idle_ms_per_step",
                 _ctx(SYNTH, monitored=False)) is None


def test_recorded_chip_trace_has_no_engine_spans():
    """The recorded trace (a program without the spans): the new readers
    read nothing and ``idle_gaps`` still names only the benchmark's
    spans."""
    tr = trace.load(DATA)
    ctx = _ctx(tr, steps=20)
    assert not any(n.startswith("serve.") for n, _, _ in tr.spans)
    for name in NEW:
        assert _read(name, ctx) is None
    t0, t1 = trace.window(tr)
    assert {n for n, _ in trace.idle_gaps(tr, t0, t1)} <= {
        "bench.step", "bench.accountant", "bench.submit", "none"}


def test_run_trace_file_is_found_by_its_window(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    d = tempfile.mkdtemp(prefix="bench_trace_")
    with jax.profiler.trace(d):
        with jax.profiler.TraceAnnotation("bench.step"):
            with jax.profiler.TraceAnnotation("serve.step"):
                with jax.profiler.TraceAnnotation("serve.sync", uid=3):
                    pass
    tr = trace.load(trace.find_xplane(d))
    assert [n for n, _, _ in tr.spans] == ["bench.step"]
    found = _serve_spans.spans(_ctx(tr))
    assert sorted(n for n, _, _ in found) == ["serve.step", "serve.sync"]
    other = _ctx(tr)
    other.t1 += 1                         # another run's window
    assert _serve_spans.spans(other) == []


def test_traced_tiny_run_reports_the_new_metrics(tmp_path):
    """The whole harness on the CPU, traced: the readers find the run's
    own trace file and read the program's spans."""
    r = _tiny.run(CELL, str(tmp_path), traced=True)
    m = r["metrics"]
    for name in NEW:
        assert m[name]["value"] > 0, name
    # every step decodes (one token fetch, two sites' counters) and at
    # most four rows are admitted (three fetches each)
    assert 3.0 <= m["host_syncs_per_step"]["value"] <= 15.0
    assert r["correct"], r["checks"]
