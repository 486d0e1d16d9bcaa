"""The plain references against the program they judge, at small sizes
on the CPU: the float32 forward pass against the program's model run in
float32, and the numpy accountant against the program's counter pass
and pricing."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.harness import spec, system, weights  # noqa: E402
from bench.reference import accountant as ref_acc  # noqa: E402


def tiny(name, **kw):
    c = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                    name + ".json"))
    c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, vocab_size=256, attn_block_k=16)
    c["num_key_value_heads"] = 4 if name.startswith("qwen") else 2
    c.update(kw)
    return c


def family(cfg):
    """``(family module, reference module)`` the configuration names."""
    return spec.load_family(cfg["family"])


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "granite-3-2b"])
def test_forward_matches_program_in_float32(name):
    from repro.models import lm
    cfg = tiny(name)
    fam, ref_model = family(cfg)
    arch = fam.arch_config(cfg).with_(compute_dtype="float32")
    params = weights.for_config(system.init_fn(arch), 2 ** 31 + 3, cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (1, 24)))
    h, _, _ = lm.apply_model(params, arch, {"tokens": toks})
    with jax.default_matmul_precision("highest"):
        want = lm.logits_fn(params, arch, h)[0]
    got = ref_model.forward(fam.plain_weights(params, cfg), cfg, toks[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4 * float(
                                   jnp.abs(want).max()))


def test_gaps_pick_the_served_token():
    cfg = tiny("qwen1.5-0.5b")
    fam, ref_model = family(cfg)
    arch = fam.arch_config(cfg)
    params = weights.for_config(system.init_fn(arch), 5, cfg)
    w = fam.plain_weights(params, cfg)
    seq = list(np.random.default_rng(1).integers(0, 256, 10))
    logits = np.asarray(ref_model.forward(w, cfg, jnp.asarray(seq)))
    best = list(seq[:6]) + [int(logits[5].argmax())]
    g = ref_model.token_gaps(w, cfg, best, 6, 16)["served"]
    assert g.shape == (1,) and g[0] == 0.0
    worst = list(seq[:6]) + [int(logits[5].argmin())]
    assert ref_model.token_gaps(w, cfg, worst, 6, 16)["served"][0] > 2.0


def _bf16_bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        jnp.asarray(x).astype(jnp.bfloat16), jnp.uint16))


def _operands(m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(m, k)).astype(np.float32)
    a[rng.random((m, k)) < 0.3] = 0.0           # zero runs for ZVG
    w = (rng.normal(size=(k, n)) / np.sqrt(k)).astype(np.float32)
    return jnp.asarray(a).astype(jnp.bfloat16), jnp.asarray(w)


def test_decode_counters_match_program_kernel():
    from repro.core import monitor
    from repro.serve import power
    a, w = _operands(4, 2048, 700)
    mine = ref_acc.decode_counters(_bf16_bits(a), _bf16_bits(w))
    got = power._ref_decode_counters(a, w, monitor.DEFAULT_MONITOR)
    for x, y in zip(mine, got):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("m", [1, 37, 300])
def test_prefill_energy_matches_program(m):
    from repro.core import monitor
    mcfg = monitor.DEFAULT_MONITOR
    a, w = _operands(m, 1024, 2816, seed=m)
    ms = 1 << (min(m, 256).bit_length() - 1)
    a2, w2 = monitor.subsample_operands(monitor._subsample(a, ms, 0), w,
                                        mcfg)
    flat = jax.device_get(monitor.stream_counters(a2, w2, mcfg))
    f = monitor.sampled_fraction_scale(m, 1024, 2816, mcfg, sampled_m=ms)
    site = ref_acc.SiteRef(_bf16_bits(w))
    mine = site.prefill(_bf16_bits(a))
    for d in ("baseline", "proposed"):
        for c in ref_acc.COMPONENTS + ("total",):
            want = float(flat[f"e/{d}/{c}"]) * f
            assert mine[d][c] == pytest.approx(want, rel=1e-5, abs=1e-3)


def test_decode_energy_matches_program():
    from repro.core import monitor
    from repro.serve import power
    mcfg = monitor.DEFAULT_MONITOR
    a, w = _operands(3, 1024, 1024, seed=9)
    wc, wz, nc, nz = power._ref_decode_counters(a, w, mcfg)
    per_row = jax.device_get(power._assemble_decode(wc, wz, nc, nz, mcfg,
                                                    256))
    f = monitor.sampled_fraction_scale(1, 1024, 1024, mcfg)
    mine = ref_acc.SiteRef(_bf16_bits(w)).decode(_bf16_bits(a))
    for d in ("baseline", "proposed"):
        want = sum(float(per_row[f"e/{d}/total"][r]) for r in range(3)) * f
        assert mine[d]["total"] == pytest.approx(want, rel=1e-5)
