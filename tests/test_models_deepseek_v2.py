"""DeepSeek-V2's model pieces: YaRN rope against the published formulas
(computed here independently), dropless held-expert routing against
capacity routing where nothing overflows, and the power accountant's
layer-0 sites when a dense layer leads scanned expert layers."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.deepseek_v2_lite import CONFIG as DSV2
from repro.configs.deepseek_v2_lite import SMOKE as DSV2_SMOKE
from repro.configs.qwen1_5_0_5b import SMOKE as QWEN_SMOKE
from repro.models import layers as L
from repro.models import lm
from repro.models import moe as M

#: DeepSeek-V2-Lite's published rope_scaling, rope dim and theta
YARN = DSV2.yarn
DIM, THETA = DSV2.mla.qk_rope_head_dim, DSV2.rope_theta


def _published_inv_freq():
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies, written
    out from the published formulas in numpy float64."""
    def corr(rot):
        return DIM * math.log(4096 / (2 * math.pi * rot)) / (2 * math.log(
            THETA))
    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)),
                                                  DIM - 1)
    i = np.arange(DIM // 2)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    extra = THETA ** (-2.0 * i / DIM)
    inter = extra / 40
    m = 1 - ramp
    return inter * (1 - m) + extra * m, (low, high)


def test_yarn_matches_the_published_formulas():
    assert YARN == L.YarnRope(factor=40.0, original_max_position=4096,
                              beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                              mscale_all_dim=0.707)
    want, (low, high) = _published_inv_freq()
    # c(32) = 64 ln(4096 / 64 pi) / 2 ln 1e4 = 10.47, c(1) = 22.5
    assert (low, high) == (10, 23)
    got = L.yarn_inv_freq(DIM, THETA, YARN)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == 1.0 and got[-1] == pytest.approx(want[-1])
    # mscale = 0.1 * 0.707 * ln 40 + 1; the cos/sin factor is 1 when
    # mscale == mscale_all_dim, the softmax scale gains mscale ** 2
    g = L.yarn_mscale(40.0, 0.707)
    assert g == pytest.approx(1.2608, abs=1e-4)
    assert g ** 2 == pytest.approx(1.59, abs=5e-3)
    assert L.yarn_cos_scale(YARN) == 1.0


def test_yarn_rope_rotates_by_the_published_angles():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 5, 2, DIM)).astype(np.float32)
    pos = np.array([[0, 1, 7, 1000, 4095]])
    got = np.asarray(L.apply_rope(jnp.asarray(x), jnp.asarray(pos), THETA,
                                  YARN))
    inv, _ = _published_inv_freq()
    ang = pos[0][:, None] * inv[None]                   # [S, DIM / 2]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[0, ..., :DIM // 2], x[0, ..., DIM // 2:]
    want = np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)


def test_dropless_equals_capacity_routing_when_nothing_overflows():
    """With capacity for every pick, capacity routing drops nothing and
    the dropless serving path computes the same layer."""
    cfg = M.MoEConfig(num_experts=8, top_k=3, expert_ff=16, num_shared=1,
                      shared_ff=16, capacity_factor=8.0, group_size=32)
    p = M.make_moe(jax.random.key(1), 24, cfg)
    x = jax.random.normal(jax.random.key(2), (2, 32, 24))
    with jax.default_matmul_precision("highest"):
        want, _ = M.apply_moe(p, x, cfg)
        got, aux = M.apply_moe(p, x, cfg, dropless=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) == 0.0


def test_a_held_share_keeps_the_router_whole():
    cfg = M.MoEConfig(num_experts=64, top_k=6, expert_ff=8, first_expert=8,
                      held=8)
    p = M.make_moe(jax.random.key(0), 16, cfg)
    assert p["router"].value.shape == (16, 64)
    assert p["w_gate"].value.shape == (8, 16, 8)
    x = jnp.ones((1, 4, 16))
    y, _ = M.apply_moe(p, x, cfg, dropless=True)
    assert y.shape == x.shape
    with pytest.raises(ValueError, match="capacity routing"):
        M.apply_moe(p, x, cfg)


def test_rows_past_the_held_groups_never_reach_the_output(monkeypatch):
    """``lax.ragged_dot`` leaves the rows past its groups undefined (on
    the TPU, whatever the buffer held): the pairs of experts held
    elsewhere must not reach a token's output even when those rows are
    NaN."""
    cfg = M.MoEConfig(num_experts=16, top_k=4, expert_ff=8, first_expert=4,
                      held=4)
    p = M.make_moe(jax.random.key(3), 16, cfg)
    x = jax.random.normal(jax.random.key(4), (2, 8, 16))
    with jax.default_matmul_precision("highest"):
        want, _ = M.apply_moe(p, x, cfg, dropless=True)
    ragged = jax.lax.ragged_dot

    def garbage_past_groups(lhs, rhs, sizes, **kw):
        out = ragged(lhs, rhs, sizes, **kw)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), out, jnp.nan)
    monkeypatch.setattr(jax.lax, "ragged_dot", garbage_past_groups)
    with jax.default_matmul_precision("highest"):
        got, _ = M.apply_moe(p, x, cfg, dropless=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("arch", ["deepseek", "qwen"])
def test_monitor_sites_are_layer_zero(arch):
    """The accountant watches the true layer 0: the unrolled head block
    when a dense layer leads the scanned expert layers (DeepSeek: its
    query projection flattened to [d, h * (nope + rope)] and its dense
    FFN up projection), else the first scanned block (qwen)."""
    cfg = DSV2_SMOKE if arch == "deepseek" else QWEN_SMOKE
    params = lm.init_model(jax.random.key(0), cfg)
    picks = lm.pick_monitor_weights(params)
    assert [s for s, _ in picks] == ["layer0/wq", "layer0/ffn_up"]
    if arch == "deepseek":
        blk = params["stack"]["head"][0]
        wq = blk["mixer"]["wq"].value
        want = [wq.reshape(wq.shape[0], -1), blk["ffn"]["up"].value]
        assert want[0].shape == (64, 4 * (8 + 4))
    else:
        blk = params["stack"]["groups"]["b0"]
        want = [blk["mixer"]["wq"].value[0], blk["ffn"]["up"].value[0]]
    for (_, w), v in zip(picks, want):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(v))


def test_registry_entry_is_the_published_routing_and_rope():
    moe = DSV2.moe
    assert (moe.num_experts, moe.top_k, moe.n_held) == (64, 6, 64)
    assert (moe.num_shared, moe.shared_ff, moe.expert_ff) == (2, 2816, 1408)
    assert DSV2.head == ("mla+mlp",) and DSV2.pattern == ("mla+moe",)
    assert dataclasses.replace(moe, held=8).n_held == 8
