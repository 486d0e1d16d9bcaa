"""Trace reduction on a three-second profiler trace recorded on a TPU v5e
(the monitored offline qwen1.5-0.5b cell): busy/idle union, device time
per program by stable name, idle gaps attributed to host spans, and the
per-layer readers on it."""
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401

import pytest  # noqa: E402

from bench.harness import cell, spec, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "qwen_offline_monitored_3s.xplane.pb.gz")


@pytest.fixture(scope="module")
def tr():
    return trace.load(DATA)


def _qwen():
    """The qwen configuration and its family, the readers' ``counts``."""
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      "qwen1.5-0.5b.json"))
    return cfg, spec.load_family(cfg["family"])[0]


def test_union_and_busy_on_synthetic_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    t = trace.Trace(ops=[("a", 0, 2), ("b", 1, 2), ("c", 5, 3)], modules=[],
                    spans=[("bench.step", 0, 10),
                           ("bench.accountant", 3, 1.5)], n_devices=1)
    assert trace.busy_ns(t, 0, 10) == 6
    gaps = dict(trace.idle_gaps(t, 0, 10))
    # gap 3-5 has its middle (4) in the accountant span; 8-10 only in step
    assert gaps == {"bench.accountant": 2e-9, "bench.step": 2e-9}


def test_recorded_trace_holds_one_device_and_host_spans(tr):
    assert tr.n_devices == 1
    names = {n for n, _, _ in tr.spans}
    assert {"bench.step", "bench.accountant"} <= names
    t0, t1 = trace.window(tr)
    assert 2.5e9 < t1 - t0 < 3.5e9


def test_busy_and_idle_add_up(tr):
    t0, t1 = trace.window(tr)
    busy = trace.busy_ns(tr, t0, t1)
    idle = sum(v for _, v in trace.idle_gaps(tr, t0, t1)) * 1e9
    assert 0.5 * (t1 - t0) < busy < t1 - t0
    assert busy + idle == pytest.approx(t1 - t0, rel=1e-6)


def test_programs_by_stable_name(tr):
    t0, t1 = trace.window(tr)
    progs = trace.program_time(tr, t0, t1)
    runs, ns = progs["jit__unknown"]          # the decode step
    assert runs >= 18 and 0.05 < ns * 1e-9 / runs < 0.5
    assert progs["jit_sample_tokens"][0] >= runs
    assert "jit__ref_decode_counters" in progs
    n, ns = trace.op_time(tr, t0, t1, lambda s: "custom-call" in s
                          and "_edge_counters" in s)
    assert n >= 2 * runs and ns > 0
    top = trace.top_ops(tr, t0, t1)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0


def test_readers_on_the_recorded_trace(tr):
    cfg, counts = _qwen()
    t0, t1 = trace.window(tr)
    busy = trace.busy_ns(tr, t0, t1) * 1e-9
    window = {"decode_steps": 20, "occupancy_sum": 640, "engine_steps": 20,
              "admissions": 2, "prefix_hit_requests": 0,
              "prompt_tokens": 600,
              "decode_log": [(32, 32 * 600)] * 20, "prefills": [(0, 300)] * 2,
              "acct_rows": [300, 300], "accountant_s": 0.2, "tokens": 642}
    ctx = types.SimpleNamespace(
        trace=tr, t0=t0, t1=t1, window_s=(t1 - t0) * 1e-9, busy_s=busy,
        config=cfg, counts=counts, window=window, monitored=True,
        engine_mode="slot",
        peaks=cell.peaks_for("TPU v5 lite", spec.BENCH_DIR),
        decode_flops=cell._decode_tokens_flops(counts, cfg,
                                              window["decode_log"]))
    read = lambda name: spec.load_metric(name).read(ctx)
    assert read("batch_occupancy") == 32.0
    assert 50 < read("decode_step_ms") < 500
    for share in ("decode_roofline", "counter_roofline", "serve_mfu",
                  "idle_share"):
        v = read(share)
        assert v is not None and 0.0 < v < 100.0, (share, v)
    assert read("prefix_hit_share") is None          # slot engine
    assert read("accountant_ms_per_step") == pytest.approx(10.0)


@pytest.mark.parametrize("base", ["serve_mfu", "idle_share",
                                  "accountant_ms_per_step",
                                  "counter_roofline"])
def test_split_metrics_read_as_their_base(tr, base):
    """A quantity split by the end-to-end metric it moves reads the same
    number under both names."""
    t0, t1 = trace.window(tr)
    cfg, counts = _qwen()
    window = {"engine_steps": 20, "accountant_s": 0.2,
              "decode_log": [(32, 32 * 600)] * 20, "prefills": [(0, 300)],
              "acct_rows": [300]}
    ctx = types.SimpleNamespace(
        trace=tr, t0=t0, t1=t1, window_s=(t1 - t0) * 1e-9,
        busy_s=trace.busy_ns(tr, t0, t1) * 1e-9, config=cfg, counts=counts,
        window=window, monitored=True, engine_mode="slot",
        peaks=cell.peaks_for("TPU v5 lite", spec.BENCH_DIR),
        decode_flops=cell._decode_tokens_flops(counts, cfg,
                                              window["decode_log"]))
    a = spec.load_metric(base).read(ctx)
    assert a is not None
    assert spec.load_metric(base + ".itl").read(ctx) == a
