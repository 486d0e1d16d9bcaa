"""Unit tests for model building blocks: attention variants, RG-LRU,
mLSTM chunkwise-vs-recurrent, MoE routing, RoPE/M-RoPE, losses."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models import xlstm as X

RNG = np.random.default_rng(0)


def _qkv(b=2, s=24, h=4, kv=2, d=8):
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kv, d)), jnp.float32)
    return q, k, v


# -------------------------------------------------------------- attention
@pytest.mark.parametrize("block_k", [4, 8, 24, 64])
def test_chunked_matches_full(block_k):
    q, k, v = _qkv()
    want = A.full_attention(q, k, v, causal=True)
    got = A.chunked_attention(q, k, v, causal=True, block_k=block_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunked_mla_value_dim():
    q, k, _ = _qkv(d=12)
    v = jnp.asarray(RNG.standard_normal((2, 24, 2, 6)), jnp.float32)
    out = A.chunked_attention(q, k, v, causal=True, block_k=8)
    assert out.shape == (2, 24, 4, 6)


def test_sliding_window_matches_masked_full():
    q, k, v = _qkv(s=32)
    want = A.full_attention(q, k, v, causal=True, window=8)
    got = A.sliding_window_attention(q, k, v, window=8, block_q=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_matches_full_last_position():
    q, k, v = _qkv(s=10)
    want = A.full_attention(q, k, v, causal=True)[:, -1:]
    # cache with extra space
    kc = jnp.pad(k, ((0, 0), (0, 6), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, 6), (0, 0), (0, 0)))
    got = A.decode_attention(q[:, -1:], kc, vc,
                             jnp.full((2,), 10, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h,kv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 1.5e-2)])
def test_decode_lane_dense_matches_f32_reference(h, kv, dtype, tol):
    """Decode attention over lane-dense ``[B, T, kv * hd]`` caches against
    the float32 reference over each row's live positions alone: row 0
    sits at position 0, row 1 at the horizon's last position, and every
    position past a row's length holds large junk the mask must drop."""
    b, t, hd = 3, 24, 16
    q, k, v = _qkv(b=b, s=t, h=h, kv=kv, d=hd)
    lengths = np.array([1, t, 11], np.int32)
    live = np.arange(t)[None, :, None, None] < lengths[:, None, None, None]
    kc = jnp.where(live, k, 1e3).reshape(b, t, kv * hd)
    vc = jnp.where(live, v, -1e3).reshape(b, t, kv * hd)
    got = A.decode_attention(q[:, -1:].astype(dtype), kc.astype(dtype),
                             vc.astype(dtype), jnp.asarray(lengths))
    assert got.shape == (b, 1, h, hd) and got.dtype == dtype
    for i, n in enumerate(lengths):
        want = A.full_attention(q[i:i + 1, -1:], k[i:i + 1, :n],
                                v[i:i + 1, :n], causal=False)
        np.testing.assert_allclose(np.asarray(got[i:i + 1], np.float32),
                                   np.asarray(want), rtol=tol, atol=tol)


def test_softcap_applied():
    q, k, v = _qkv()
    a = A.full_attention(q * 50, k * 50, v, causal=True)
    b = A.full_attention(q * 50, k * 50, v, causal=True, softcap=5.0)
    assert not np.allclose(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------- rope
def test_rope_relative_property():
    """RoPE inner products depend only on relative positions."""
    d = 16
    x = jnp.asarray(RNG.standard_normal((1, 2, 1, d)), jnp.float32)
    def ip(offset):
        pos = jnp.array([[0 + offset, 5 + offset]])
        r = L.apply_rope(x, pos)
        return float(jnp.vdot(r[0, 0, 0], r[0, 1, 0]))
    assert ip(0) == pytest.approx(ip(13), rel=1e-4)


def test_mrope_sections_rotate_independently():
    d = 16
    x = jnp.asarray(RNG.standard_normal((1, 3, 1, d)), jnp.float32)
    pos_t = jnp.stack([jnp.array([[0, 1, 2]]), jnp.zeros((1, 3), int),
                       jnp.zeros((1, 3), int)])
    pos_h = jnp.stack([jnp.zeros((1, 3), int), jnp.array([[0, 1, 2]]),
                       jnp.zeros((1, 3), int)])
    a = L.apply_mrope(x, pos_t, (4, 2, 2))
    b = L.apply_mrope(x, pos_h, (4, 2, 2))
    assert not np.allclose(np.asarray(a), np.asarray(b))
    # zero positions = identity
    zero = jnp.zeros((3, 1, 3), int)
    np.testing.assert_allclose(
        np.asarray(L.apply_mrope(x, zero, (4, 2, 2))), np.asarray(x),
        rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ rglru
def test_rglru_parallel_matches_sequential():
    d = 12
    p = R.make_rglru(jax.random.key(0), d)
    x = jnp.asarray(RNG.standard_normal((2, 17, d)), jnp.float32)
    y_par, h_par = R.apply_rglru(p, x)
    h = jnp.zeros((2, d), jnp.float32)
    outs = []
    for t in range(17):
        y_t, h = R.rglru_decode(p, h, x[:, t])
        outs.append(y_t)
    y_seq = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               rtol=2e-4, atol=2e-5)


def test_rglru_state_bounded():
    """|h| stays bounded (a < 1 contraction + sqrt(1-a^2) input scale)."""
    d = 8
    p = R.make_rglru(jax.random.key(1), d)
    x = jnp.asarray(RNG.standard_normal((1, 2048, d)) * 5, jnp.float32)
    _, h = R.apply_rglru(p, x)
    assert float(jnp.abs(h).max()) < 100.0


def test_conv1d_causal():
    p = R.make_conv1d(jax.random.key(0), 4, 4)
    x = jnp.asarray(RNG.standard_normal((1, 10, 4)), jnp.float32)
    y1 = R.apply_conv1d(p, x)
    x2 = x.at[:, 5:].set(0.0)
    y2 = R.apply_conv1d(p, x2)
    np.testing.assert_allclose(np.asarray(y1[:, :5]), np.asarray(y2[:, :5]),
                               rtol=1e-5)


# ------------------------------------------------------------------ xlstm
@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_mlstm_chunkwise_equals_recurrent(chunk):
    B, S, H, D = 2, 33, 2, 8
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    i = jnp.asarray(RNG.standard_normal((B, S, H)), jnp.float32)
    f = jnp.asarray(RNG.standard_normal((B, S, H)) + 4, jnp.float32)
    h1, _ = X.mlstm_memory_recurrent(q, k, v, i, f)
    h2, _ = X.mlstm_memory_chunkwise(q, k, v, i, f, chunk=chunk)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-3, atol=1e-4)


def test_mlstm_state_continuation():
    B, S, H, D = 1, 20, 2, 4
    args = [jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
            for _ in range(3)]
    gates = [jnp.asarray(RNG.standard_normal((B, S, H)), jnp.float32),
             jnp.asarray(RNG.standard_normal((B, S, H)) + 4, jnp.float32)]
    h_full, _ = X.mlstm_memory_recurrent(*args, *gates)
    h_a, st = X.mlstm_memory_recurrent(*[a[:, :12] for a in args],
                                       *[g[:, :12] for g in gates])
    h_b, _ = X.mlstm_memory_recurrent(*[a[:, 12:] for a in args],
                                      *[g[:, 12:] for g in gates], st)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([h_a, h_b], 1)), np.asarray(h_full),
        rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------------- moe
def test_moe_capacity_and_dispatch_shapes():
    logits = jnp.asarray(RNG.standard_normal((2, 8, 4)), jnp.float32)
    d, c, aux = M._topk_dispatch(logits, k=2, capacity=3)
    assert d.shape == (2, 8, 4, 3)
    # every token dispatched at most k times
    per_token = d.sum(axis=(2, 3))
    assert float(per_token.max()) <= 2.0
    # capacity respected exactly: <= 1 token per (expert, slot)
    per_slot = d.sum(axis=1)
    assert float(per_slot.max()) <= 1.0
    assert float(aux) > 0


@given(st.integers(1, 4), st.integers(2, 8))
@settings(max_examples=10, deadline=None)
def test_moe_no_drop_when_capacity_huge(k, e):
    if k > e:
        k = e
    logits = jnp.asarray(RNG.standard_normal((1, 16, e)), jnp.float32)
    d, _, _ = M._topk_dispatch(logits, k=k, capacity=16 * k)
    assert float(d.sum()) == pytest.approx(16 * k)


def test_moe_forward_and_zero_rows():
    cfg = M.MoEConfig(num_experts=4, top_k=2, expert_ff=16,
                      capacity_factor=0.5, group_size=8)
    p = M.make_moe(jax.random.key(0), 8, cfg)
    x = jnp.asarray(RNG.standard_normal((2, 16, 8)), jnp.bfloat16)
    y, aux = M.apply_moe(p, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(float(aux))
