"""End-to-end ``ServeConfig(kernel_backend=...)`` differentials.

Flipping the decode path from stock XLA (``"ref"``) to the fused Pallas
kernels (``"pallas"``) must be INVISIBLE to every number the engine
emits, in the pinned serving configuration (f32 smoke arch, interpret-
mode kernels -- docs/testing.md#kernel-equivalence):

  * bit-identical tokens per request, slot AND paged engines, across
    slot churn and mixed greedy/stochastic co-batches (same PRNG
    consumption order);
  * bit-identical per-request energies and serve-wide ``trace_report()``
    aggregates -- both backends' integer counters price through the ONE
    shared compiled assembler (``serve.power._assemble_decode``), so
    divergence is impossible by construction, and this suite proves the
    construction holds end-to-end;
  * the backend is decode-scoped: prefill and chunked prefill always
    trace ``"ref"``, and the module-global dispatch is restored after
    every engine build.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.models import lm
from repro.models import matmul as mm
from repro.serve import (PagingConfig, SamplingParams, ServeConfig,
                         ServeEngine)

CACHE_LEN = 48
PS = 8
RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def model():
    cfg = SMOKES["qwen1.5-0.5b"].with_(compute_dtype="float32")
    params = lm.init_model(jax.random.key(0), cfg)
    return cfg, params


def _prompts(n, lo=2, hi=24):
    return [list(map(int, RNG.integers(0, 256, int(RNG.integers(lo, hi)))))
            for _ in range(n)]


def _mixed_sampling(n):
    """Alternating greedy / temperature+top-k co-batch (seed 3)."""
    return [SamplingParams() if i % 2 == 0
            else SamplingParams(temperature=0.8, top_k=5)
            for i in range(n)]


def _slot(model, backend, *, slots=3, **kw):
    cfg, params = model
    return ServeEngine(params, cfg, ServeConfig(
        max_slots=slots, cache_len=CACHE_LEN, power_monitor=True, seed=3,
        kernel_backend=backend, **kw))


def _paged(model, backend, *, rows=3, pages=64, **kw):
    cfg, params = model
    return ServeEngine(params, cfg, ServeConfig(
        cache_len=CACHE_LEN, power_monitor=True, seed=3,
        kernel_backend=backend,
        paging=PagingConfig(page_size=PS, num_pages=pages, max_rows=rows),
        **kw))


def _drain(engine, prompts, sampling=None, max_new=5):
    for i, p in enumerate(prompts):
        engine.submit(p, max_new_tokens=max_new,
                      **({"sampling": sampling[i]} if sampling else {}))
    fin = {r.uid: r for r in engine.run()}
    assert len(fin) == len(prompts)
    return fin


def _trace_dict(engine):
    rep = engine.trace_report()
    return (dataclasses.asdict(rep) if dataclasses.is_dataclass(rep)
            else rep.__dict__)


def _assert_engines_identical(ref, pal, prompts, sampling=None):
    fr = _drain(ref, prompts, sampling)
    fp = _drain(pal, prompts, sampling)
    assert ({u: r.generated for u, r in fr.items()}
            == {u: r.generated for u, r in fp.items()})
    for uid in fr:
        assert fr[uid].power.energy == fp[uid].power.energy, uid
        assert fr[uid].power.zero_fraction == fp[uid].power.zero_fraction
    assert _trace_dict(ref) == _trace_dict(pal)


# -------------------------------------------------------------- slot engine
def test_slot_engine_backends_bit_identical(model):
    """8 requests through 3 slots (churn), greedy + stochastic mix."""
    prompts = _prompts(8)
    _assert_engines_identical(_slot(model, "ref"), _slot(model, "pallas"),
                              prompts, _mixed_sampling(8))


def test_slot_engine_backends_greedy(model):
    prompts = _prompts(5)
    _assert_engines_identical(_slot(model, "ref"), _slot(model, "pallas"),
                              prompts)


# ------------------------------------------------------------- paged engine
def test_paged_engine_backends_bit_identical(model):
    """Paged decode runs the fused paged-attention kernel; tokens,
    energies and trace aggregates still match the ref backend exactly."""
    prompts = _prompts(8)
    _assert_engines_identical(_paged(model, "ref"),
                              _paged(model, "pallas"),
                              prompts, _mixed_sampling(8))


def test_paged_pallas_matches_slot_ref(model):
    """Transitive closure: paged+pallas == slot+ref (tokens + energies),
    composing this suite's contract with test_serve_paging's."""
    prompts = _prompts(6)
    fs = _drain(_slot(model, "ref"), prompts)
    fp = _drain(_paged(model, "pallas"), prompts)
    assert ({u: r.generated for u, r in fs.items()}
            == {u: r.generated for u, r in fp.items()})
    for uid in fs:
        assert fs[uid].power.energy == fp[uid].power.energy, uid


# ------------------------------------------------------------------ hygiene
def test_unknown_backend_rejected(model):
    cfg, params = model
    with pytest.raises(ValueError, match="kernel_backend"):
        ServeEngine(params, cfg, ServeConfig(kernel_backend="bogus"))
    with pytest.raises(ValueError, match="unknown kernel backend"):
        with mm.use_kernel_backend("bogus"):
            pass


@pytest.mark.parametrize("paged", [False, True])
def test_pallas_backend_rejected_on_tpu(model, monkeypatch, paged):
    """On a TPU the fused decode kernels would meet Mosaic, which refuses
    them as shaped today: the engine must raise, neither interpreting
    them nor swapping in "ref"."""
    cfg, params = model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kw = {"paging": PagingConfig(page_size=PS)} if paged else {}
    with pytest.raises(NotImplementedError, match="S4"):
        ServeEngine(params, cfg, ServeConfig(kernel_backend="pallas", **kw))


def test_fused_kernels_interpret_only_off_tpu(monkeypatch):
    """``interpret=None`` (the fused kernels' default) means the Pallas
    interpreter on CPU and Mosaic on a TPU; explicit values pass."""
    from repro.kernels.platform import resolve_interpret
    from repro.kernels.zvg_matmul.fused import gated_row_matmul

    assert resolve_interpret(None) is True
    x = jax.random.normal(jax.random.key(1), (3, 8))
    w = jax.random.normal(jax.random.key(2), (8, 5))
    np.testing.assert_array_equal(gated_row_matmul(x, w),
                                  gated_row_matmul(x, w, interpret=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_backend_scope_is_decode_only(model):
    """Building and running a pallas engine never leaks the dispatch
    global: code outside the decode jit always sees "ref"."""
    assert mm.current_backend() == "ref"
    eng = _slot(model, "pallas")
    assert mm.current_backend() == "ref"
    _drain(eng, _prompts(2), max_new=2)
    assert mm.current_backend() == "ref"
    with mm.use_kernel_backend("pallas"):
        assert mm.current_backend() == "pallas"
    assert mm.current_backend() == "ref"


def test_accountant_sampling_composes_with_backend(model):
    """power_sample_every > 1 scales identically under both backends."""
    prompts = _prompts(4)
    ref = _slot(model, "ref", power_sample_every=2)
    pal = _slot(model, "pallas", power_sample_every=2)
    _assert_engines_identical(ref, pal, prompts)
