"""The serving engine's host spans and host-sync count (repro.serve.spans).

Under an active profiler trace, both engines record one ``serve.step``
per engine step with every other ``serve.*`` span inside one, admissions
and retirements carry their request's uid, and each counted host sync
is one ``serve.sync`` span. ``stats["host_syncs"]`` follows from steps,
admissions and monitored sites. Tracing changes no token and no power
report.
"""
import bisect
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import SMOKES
from repro.models import lm
from repro.serve import PagingConfig, SamplingParams, ServeConfig, ServeEngine

ENGINES = {
    "slot": dict(max_slots=2),
    # chunked prefill (prompts above 16 tokens take several admission
    # steps) and prefix reuse (the shared system prompt below)
    "paged": dict(paging=PagingConfig(page_size=8, num_pages=64, max_rows=2,
                                      prefill_chunk=16, prefix_cache=True)),
}
RNG = np.random.default_rng(13)
SYSTEM = list(map(int, RNG.integers(0, 256, 16)))
PROMPTS = [SYSTEM + list(map(int, RNG.integers(0, 256, int(n))))
           for n in (3, 12, 20, 7, 1)]


@pytest.fixture(scope="module")
def model():
    cfg = SMOKES["qwen1.5-0.5b"].with_(compute_dtype="float32")
    return cfg, lm.init_model(jax.random.key(0), cfg)


def _serve(model, kind, monitored=True, trace_dir=None):
    cfg, params = model
    eng = ServeEngine(params, cfg, ServeConfig(
        cache_len=64, power_monitor=monitored, seed=5, **ENGINES[kind]))
    for i, p in enumerate(PROMPTS):
        eng.submit(p, max_new_tokens=4 + i, sampling=(
            SamplingParams() if i % 2 else SamplingParams(0.8, 20)))
    if trace_dir is None:
        return eng, eng.run()
    with jax.profiler.trace(trace_dir):
        done = eng.run()
    return eng, done


def _serve_spans(trace_dir):
    """``(name, start, end, stats)`` of every ``serve.*`` host event."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("serve."))
    return out


@pytest.fixture(scope="module", params=sorted(ENGINES))
def traced(request, model, tmp_path_factory):
    d = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    eng, done = _serve(model, request.param, trace_dir=d)
    return request.param, eng, done, _serve_spans(d)


def test_one_step_span_per_step_and_every_span_inside_one(traced):
    _, eng, _, spans = traced
    steps = sorted((s, e) for n, s, e, _ in spans if n == "serve.step")
    assert len(steps) == eng.stats["steps"]
    starts = [s for s, _ in steps]
    others = [(n, s, e) for n, s, e, _ in spans if n != "serve.step"]
    assert {n for n, _, _ in others} >= {
        "serve.admit", "serve.retire", "serve.accountant",
        "serve.accountant.embed", "serve.accountant.counters",
        "serve.accountant.book", "serve.decode", "serve.sample",
        "serve.sync"}
    for n, s, e in others:
        i = bisect.bisect_right(starts, s) - 1
        assert i >= 0 and e <= steps[i][1], n


def test_admit_and_retire_carry_the_uid(traced):
    kind, _, done, spans = traced
    uids = sorted(r.uid for r in done)
    admitted = [st["uid"] for n, _, _, st in spans if n == "serve.admit"]
    retired = [st["uid"] for n, _, _, st in spans if n == "serve.retire"]
    assert sorted(set(admitted)) == uids
    assert sorted(retired) == uids
    if kind == "paged":      # a chunked prompt is admitted over steps
        assert len(admitted) > len(uids)


def test_each_host_sync_is_one_sync_span(traced):
    _, eng, _, spans = traced
    assert sum(n == "serve.sync" for n, _, _, _ in spans) \
        == eng.stats["host_syncs"] > 0


@pytest.mark.parametrize("kind,monitored", [("slot", True), ("paged", True),
                                            ("slot", False)])
def test_host_syncs_follow_steps_admissions_and_sites(model, kind,
                                                      monitored):
    """Per decode step one sampled-token fetch and one counter fetch per
    monitored site; per admission one first-token fetch and one prompt
    counter fetch per site (every step is sampled at the default
    cadence; no request is preempted here)."""
    eng, done = _serve(model, kind, monitored)
    sites = len(eng._power_weights)
    assert sites == (2 if monitored else 0)
    assert eng.stats["host_syncs"] == (1 + sites) * (
        eng.stats["decode_steps"] + len(done))


def test_tracing_changes_no_token_and_no_power_report(model, traced):
    kind, eng_t, done_t, _ = traced
    eng, done = _serve(model, kind)
    assert len(done) == len(done_t) == len(PROMPTS)
    key = lambda r: r.uid                  # submission order in each run
    for r, t in zip(sorted(done, key=key), sorted(done_t, key=key)):
        assert r.generated == t.generated
        assert r.power.energy == t.power.energy
        assert r.power.zero_fraction == t.power.zero_fraction
        assert r.power.sampled_steps == t.power.sampled_steps
    assert eng.stats == eng_t.stats
