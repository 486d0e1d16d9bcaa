"""``chip_smoke.py`` on the CPU: its phases at SMOKES size, and its
refusal to report a result without a TPU.

On the chip the script runs the same phase functions at qwen1.5-0.5b's
published width; here they run at the smoke width, with the Pallas
counter kernel in interpret mode, so a broken check or a wrong path
shows up before any chip time is spent.
"""
import importlib.util
import os

import jax
import pytest

from repro.configs import SMOKES
from repro.models import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = SMOKES[mod.ARCH]
    return mod, cfg, lm.init_model(jax.random.key(0), cfg)


def test_serve_phase_smoke_width(smoke):
    mod, cfg, params = smoke
    reqs = mod.make_requests(0, cfg.vocab, n=6, prompt=(4, 40), new=(2, 6))
    out = mod.serve_phase(params, cfg, reqs, max_slots=4, cache_len=64)
    assert out["requests"] == 6
    assert out["greedy_equal_monitor_off"] == 3
    assert out["tokens"] == sum(budget for _, budget, _ in reqs)


def test_counters_phase_smoke_width(smoke):
    mod, cfg, params = smoke
    out = mod.counters_phase(params, cfg, seed=0)
    assert out["integer_counters_equal"] > 0


def test_make_requests_mix(smoke):
    mod, cfg, _ = smoke
    reqs = mod.make_requests(3, cfg.vocab)
    assert len(reqs) == 12
    assert all(16 <= len(toks) <= 512 and 8 <= budget <= 32
               for toks, budget, _ in reqs)
    assert [mod._is_greedy(s) for _, _, s in reqs] == [True, False] * 6
    assert reqs == mod.make_requests(3, cfg.vocab)


def test_refuses_without_tpu(smoke, capsys):
    """Off a TPU the script fails before any phase and prints no result
    line."""
    mod, _, _ = smoke
    assert mod.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert out.strip().splitlines()[-1].startswith("device: platform=cpu")
