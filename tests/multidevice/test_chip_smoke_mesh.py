"""``chip_smoke.py --chips 4``'s mesh phase at SMOKES size on four of the
virtual CPU devices: the tensor-parallel engine against the
single-device engine (prefill logits within the bf16 bound, prefill
power reports exact, parameters spread over the mesh)."""
import importlib.util
import os

import jax

from repro.configs import SMOKES
from repro.models import lm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_mesh_phase_smoke_width():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = SMOKES[mod.ARCH]
    params = lm.init_model(jax.random.key(0), cfg)
    reqs = mod.make_requests(0, cfg.vocab, n=6, prompt=(8, 64), new=(2, 6))
    out = mod.mesh_phase(params, cfg, reqs, cache_len=128)
    assert out["requests"] == 3
    assert out["prefill_reports_equal"] == 3
    assert out["logit_err_max"] <= mod.MESH_LOGIT_RTOL
