"""Block composition and the scanned layer stack.

A block spec "<mixer>+<ffn>" composes a sequence mixer (attention variant or
recurrence) with a feed-forward (dense MLP or MoE) in pre-norm residual
form. The stack scans over repeated block *groups* (stacked params) with
per-group remat, plus an unrolled tail for non-divisible depths
(RecurrentGemma: 38 = 12 x (rec, rec, attn) + 2 x rec).

Execution modes (one code path each, shared params):
  train    -- parallel over S, no states, remat inside the scan body.
  prefill  -- parallel over S, also returns per-layer decode states.
  decode   -- S=1 step with carried states (KV caches / ring buffers /
              latent caches / recurrent states), stacked [G, ...].

Positions: ``[B, S]`` int32 (``[3, B, S]`` for M-RoPE). Decode steps use
S=1 positions; cache writes are *per batch row* (row i writes at
``positions[i, 0]``), which is what lets ``repro.serve`` co-batch requests
sitting at different sequence positions in one shared decode step.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from . import attention as A
from . import layers as L
from . import matmul as mm
from . import moe as M
from . import recurrent as R
from . import xlstm as X
from .config import ArchConfig


def parse_spec(spec: str) -> tuple[str, str]:
    if "+" in spec:
        mixer, ffn = spec.split("+")
    else:
        mixer, ffn = spec, "none"
    return mixer, ffn


# ----------------------------------------------------------------- builders
def make_attention(key, cfg: ArchConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_param(ks[0], d, h * hd, "embed", "heads"),
        "wk": L.dense_param(ks[1], d, kv * hd, "embed", "heads"),
        "wv": L.dense_param(ks[2], d, kv * hd, "embed", "heads"),
        "wo": L.dense_param(ks[3], h * hd, d, "heads", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = L.bias_param(h * hd, "heads")
        p["bk"] = L.bias_param(kv * hd, "heads")
        p["bv"] = L.bias_param(kv * hd, "heads")
    return p


def make_mla(key, cfg: ArchConfig) -> dict:
    mla = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = (mla.qk_nope_head_dim, mla.qk_rope_head_dim,
                  mla.v_head_dim)
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": L.dense_param(ks[0], d, mla.kv_lora_rank, "embed", None),
        "kv_norm": L.make_norm("rms", mla.kv_lora_rank),
        "w_kr": L.dense_param(ks[1], d, dr, "embed", None),
        "w_uk": L.Param(L.normal_init(
            ks[2], (mla.kv_lora_rank, h, dn), mla.kv_lora_rank ** -0.5),
            (None, "heads", None)),
        "w_uv": L.Param(L.normal_init(
            ks[3], (mla.kv_lora_rank, h, dv), mla.kv_lora_rank ** -0.5),
            (None, "heads", None)),
        "wo": L.dense_param(ks[4], h * dv, d, "heads", "embed"),
    }
    if mla.q_lora_rank:
        p["w_dq"] = L.dense_param(ks[5], d, mla.q_lora_rank, "embed", None)
        p["q_norm"] = L.make_norm("rms", mla.q_lora_rank)
        p["w_uq"] = L.Param(L.normal_init(
            ks[6], (mla.q_lora_rank, h, dn + dr), mla.q_lora_rank ** -0.5),
            (None, "heads", None))
    else:
        p["wq"] = L.Param(L.normal_init(
            ks[6], (d, h, dn + dr), d ** -0.5), ("embed", "heads", None))
    return p


def make_mixer(key, cfg: ArchConfig, mixer: str) -> dict:
    if mixer in ("attn", "local"):
        return make_attention(key, cfg)
    if mixer == "mla":
        return make_mla(key, cfg)
    if mixer == "rglru":
        return R.make_recurrent_block(key, cfg.d_model, cfg.rglru)
    if mixer == "mlstm":
        return X.make_mlstm(key, cfg.d_model, cfg.xlstm)
    if mixer == "slstm":
        return X.make_slstm(key, cfg.d_model, cfg.xlstm)
    raise ValueError(mixer)


def make_block(key, cfg: ArchConfig, spec: str) -> dict:
    mixer, ffn = parse_spec(spec)
    ks = jax.random.split(key, 2)
    p = {"norm1": L.make_norm(cfg.norm, cfg.d_model),
         "mixer": make_mixer(ks[0], cfg, mixer)}
    if ffn != "none":
        p["norm2"] = L.make_norm(cfg.norm, cfg.d_model)
        if ffn == "moe":
            p["ffn"] = M.make_moe(ks[1], cfg.d_model, cfg.moe)
        else:
            p["ffn"] = L.make_mlp(ks[1], cfg.d_model, cfg.d_ff,
                                  gated=cfg.mlp_gated)
    return p


# ------------------------------------------------------------------- helpers
def _decode_batch_pos(cfg: ArchConfig, positions) -> jax.Array:
    """Per-row cache-write index for a decode step, ``[B]`` int32. Rows may
    sit at different positions (continuous batching)."""
    p = positions[0] if cfg.pos == "mrope" else positions
    return p[:, 0].astype(jnp.int32)


def _rope_qk(cfg: ArchConfig, q, k, positions):
    if cfg.pos == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos == "mrope":
        q = L.apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = L.apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    return q, k


def _conv_tail(x: jax.Array, cw: int) -> jax.Array:
    """Last cw-1 timesteps of x, left-padded with zeros if needed."""
    return jnp.pad(x, ((0, 0), (max(cw - 1 - x.shape[1], 0), 0),
                       (0, 0)))[:, -(cw - 1):]


# ------------------------------------------------------------------ paging
def _page_targets(pages, positions, page_size):
    """Physical (page, offset) write targets for logical ``positions``.

    ``pages [B, MP]`` is the per-row page table (entry 0 = the reserved
    trash page), ``positions [B, S]`` the absolute logical positions. Out-
    of-range or negative (= right-padding sentinel) positions redirect to
    the trash page, so one scatter covers live rows, dead rows (all-trash
    tables), and padded chunk tails without ever touching a real page.
    """
    mp = pages.shape[1]
    pos = positions.astype(jnp.int32)
    pg = jnp.take_along_axis(pages, jnp.clip(pos // page_size, 0, mp - 1),
                             axis=1)
    ok = (pos >= 0) & (pos < mp * page_size)
    return jnp.where(ok, pg, 0), jnp.clip(pos, 0) % page_size


def _gather_pages(pool, pages, layer=None):
    """Dense per-row logical view of a page pool: ``pool [P, ps, ...]`` +
    ``pages [B, MP]`` -> ``[B, MP * ps, ...]`` (``pool [G, P, ps, ...]``
    and this block's ``layer`` for a stacked pool). Unallocated table
    entries point at the trash page; whatever junk they contribute sits at
    logical positions beyond the row's write coverage, which every caller
    masks by position -- the same argument that makes a dead slot row
    harmless in the dense cache."""
    b, mp = pages.shape
    view = pool[pages] if layer is None else pool[layer, pages]
    return view.reshape((b, mp * view.shape[2]) + view.shape[3:])


def _layer_of(cache, layer):
    """One layer's cache: ``cache`` itself, or its slice ``layer`` of a
    stacked ``[G, ...]`` leaf (read, never written back)."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)


# --------------------------------------------------------------- attention
def apply_attention(p, x, cfg: ArchConfig, *, local: bool, positions,
                    state=None, prefill=False, cache_len=0, pages=None,
                    layer=None):
    """Attention block. A full-attention cache (``local=False``) is
    lane-dense: ``(k, v)`` leaves ``[B, T, kv * hd]``, paged pools
    ``[P, ps, kv * hd]``. With ``layer`` (a traced index) the leaves are
    the whole stacked ``[G, ...]`` caches of the layer scan: the step's
    rows are scattered straight into them at ``[layer, row, position]``
    and the attention reads the layer's slice inside its products, so no
    layer slice is materialised, relaid out or written back."""
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    scale = cfg.attn_scale or None

    def proj(w, bname, nh):
        with jax.named_scope(w):
            y = mm.matmul(x, p[w].value.astype(x.dtype))
            if bname in p:
                y = y + p[bname].value.astype(x.dtype)
        return y.reshape(b, s, nh, hd)

    q = proj("wq", "bq", h)
    k = proj("wk", "bk", kv)
    v = proj("wv", "bv", kv)
    q, k = _rope_qk(cfg, q, k, positions)

    if state is not None and not local:         # ---- full-attention cache
        kc, vc = state
        if pages is not None:
            pg, off = _page_targets(pages, positions, kc.shape[-2])
            at = (pg, off)
        else:
            bpos = _decode_batch_pos(cfg, positions)
            at = (jnp.arange(b)[:, None], bpos[:, None])
        if layer is not None:
            at = (layer,) + at
        kc = kc.at[at].set(k.reshape(b, s, kv * hd).astype(kc.dtype))
        vc = vc.at[at].set(v.reshape(b, s, kv * hd).astype(vc.dtype))

        def attend(qq, kk, vv, lengths):
            return A.decode_attention(qq, kk, vv, lengths, scale=scale,
                                      softcap=cfg.attn_softcap,
                                      constrain_q=cfg.pos != "mrope")
        if pages is None:
            out = attend(q, _layer_of(kc, layer), _layer_of(vc, layer),
                         bpos + 1)
        elif s == 1:
            # Paged: a dense logical view gathered through the page table
            # goes through the slot path's attention unchanged -- masked
            # positions contribute exact zeros, so a single-token step is
            # bit-identical to the slot cache's (tests/test_serve_paging.py
            # pins it).
            bpos = _decode_batch_pos(cfg, positions)
            if mm.current_backend() == "pallas":
                # fuse the page-table gather into the attention pass
                # (scatter stays outside: the pools are the carried
                # state); the kernel takes [P, ps, kv, hd] pools
                from repro.kernels.zvg_matmul.fused import (
                    fused_paged_attention)

                def heads(pool):
                    pool = _layer_of(pool, layer)
                    return pool.reshape(pool.shape[:2] + (kv, hd))
                out = fused_paged_attention(q, heads(kc), heads(vc), pages,
                                            bpos + 1, attend)
            else:
                out = attend(q, _gather_pages(kc, pages, layer),
                             _gather_pages(vc, pages, layer), bpos + 1)
        else:                                     # chunked prefill
            kk, vv = (_gather_pages(c, pages, layer).reshape(b, -1, kv, hd)
                      for c in (kc, vc))
            out = A.paged_chunk_attention(q, kk, vv, positions, scale=scale,
                                          softcap=cfg.attn_softcap,
                                          constrain_q=cfg.pos != "mrope")
        out = out.reshape(b, s, h * hd)
        with jax.named_scope("wo"):
            out = mm.matmul(out, p["wo"].value.astype(x.dtype))
        return out, (kc, vc)

    if state is not None:                       # ---- ring-buffer decode
        bpos = _decode_batch_pos(cfg, positions)
        rows = jnp.arange(b)
        kc, vc, slots = state
        w_sz = kc.shape[1]
        slot = bpos % w_sz
        kc = kc.at[rows, slot].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[rows, slot].set(v[:, 0].astype(vc.dtype))
        slots = slots.at[rows, slot].set(bpos.astype(slots.dtype))
        out = _ring_decode(q, kc, vc, slots, bpos, cfg, scale)
        out = out.reshape(b, s, h * hd)
        with jax.named_scope("wo"):
            out = mm.matmul(out, p["wo"].value.astype(x.dtype))
        return out, (kc, vc, slots)

    if local:                                   # ---- parallel
        out = A.sliding_window_attention(q, k, v, window=cfg.window,
                                         scale=scale)
    else:
        out = A.chunked_attention(q, k, v, causal=True, scale=scale,
                                  softcap=cfg.attn_softcap,
                                  block_k=cfg.attn_block_k)
    with jax.named_scope("wo"):
        out = out.reshape(b, s, h * hd) @ p["wo"].value.astype(x.dtype)

    new_state = None
    if prefill:
        if local:
            w_sz = cfg.window
            take = min(s, w_sz)
            t = jnp.arange(s - take, s)
            ring = t % w_sz
            kc = jnp.zeros((b, w_sz, kv, hd), k.dtype).at[:, ring].set(
                k[:, -take:])
            vc = jnp.zeros((b, w_sz, kv, hd), v.dtype).at[:, ring].set(
                v[:, -take:])
            slots = jnp.full((b, w_sz), -1, jnp.int32).at[:, ring].set(
                jnp.broadcast_to(t, (b, take)))
            new_state = (kc, vc, slots)
        else:
            pad = ((0, 0), (0, cache_len - s), (0, 0))
            new_state = (jnp.pad(k.reshape(b, s, kv * hd), pad),
                         jnp.pad(v.reshape(b, s, kv * hd), pad))
    return out, new_state


def _ring_decode(q, kc, vc, slots, bpos, cfg, scale):
    """Decode attention over a ring-buffer window cache (slot order is
    irrelevant to softmax; validity comes from stored positions)."""
    b, _, h, hd = q.shape
    hkv = kc.shape[2]
    qg = A._group_q(q, hkv)
    scale = scale if scale is not None else hd ** -0.5
    scores = jnp.einsum("bqkrd,bskd->bkrqs", qg, kc
                        ).astype(jnp.float32) * scale
    valid = (slots >= 0) & (slots <= bpos[:, None]) \
        & (slots > bpos[:, None] - cfg.window)
    scores = jnp.where(valid[:, None, None, None], scores, A.NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", probs, vc)
    return out.reshape(b, 1, h, vc.shape[-1])


# --------------------------------------------------------------------- MLA
def mla_row_width(mla) -> int:
    """Words of one position of the latent decode cache: the latent and
    the rope key, padded up to whole 128-lane tiles."""
    return -(-(mla.kv_lora_rank + mla.qk_rope_head_dim) // 128) * 128


def apply_mla(p, x, cfg: ArchConfig, *, positions, state=None,
              prefill=False, cache_len=0, pages=None, layer=None):
    """Multi-head latent attention. The decode cache holds, per position,
    the normed latent and the shared rope key side by side in one row,
    zero-padded to whole 128-lane tiles: ``[B, T, mla_row_width]`` (paged
    pools ``[P, ps, mla_row_width]``). The TPU stores an array whose
    minor dim is not a multiple of 128 lanes position-minor, so a 64-wide
    rope-key leaf (or a 576-wide row) would be relaid out, a whole layer
    in and out, for every step's row write; a 640-wide row is stored as
    written, and one product over it gives both score terms. With
    ``layer`` (a traced index) the cache is the whole stacked ``[G,
    ...]`` carry of the layer scan: the step's rows are scattered into it
    at ``[layer, row, position]`` and the absorbed attention reads the
    layer's slice inside its products, as ``apply_attention`` does for
    full attention. Rope is YaRN-scaled when ``cfg.yarn`` is set, and the
    softmax scale with it."""
    mla = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    r = mla.kv_lora_rank
    dn, dr, dv = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    scale = (dn + dr) ** -0.5
    if cfg.yarn is not None:
        scale *= L.yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    rope = lambda t: L.apply_rope(t, positions, cfg.rope_theta, cfg.yarn)

    if mla.q_lora_rank:
        cq = L.apply_norm("rms", p["q_norm"],
                          mm.matmul(x, p["w_dq"].value.astype(x.dtype)))
        q = jnp.einsum("bsr,rhe->bshe", cq, p["w_uq"].value.astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].value.astype(x.dtype))
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:])

    ckv = L.apply_norm("rms", p["kv_norm"],
                       mm.matmul(x, p["w_dkv"].value.astype(x.dtype)))
    kr = rope(mm.matmul(x, p["w_kr"].value.astype(x.dtype))[:, :, None, :])
    pad = [(0, 0), (0, 0), (0, mla_row_width(mla) - r - dr)]
    rows = jnp.pad(jnp.concatenate([ckv, kr[:, :, 0]], axis=-1), pad)

    if state is not None:                       # ---- absorbed decode
        cache = state
        if pages is not None:                   # paged: pool-backed cache
            at = _page_targets(pages, positions, cache.shape[-2])
            kpos = jnp.arange(pages.shape[1] * cache.shape[-2])
            # per-query causal mask [B, S, T]; for S == 1 this broadcasts
            # to exactly the dense decode mask below (bit-identity)
            mask = (kpos[None, None, :]
                    <= positions.astype(jnp.int32)[:, :, None])[:, None]
        else:
            bpos = _decode_batch_pos(cfg, positions)
            at = (jnp.arange(b)[:, None], bpos[:, None])
            kpos = jnp.arange(cache.shape[-2])
            mask = (kpos[None, :] <= bpos[:, None])[:, None, None, :]
        if layer is not None:
            at = (layer,) + at
        cache = cache.at[at].set(rows.astype(cache.dtype))
        seen = (_gather_pages(cache, pages, layer) if pages is not None
                else _layer_of(cache, layer))          # [B, T, row]
        q_eff = jnp.einsum("bshe,rhe->bshr", q_nope,
                           p["w_uk"].value.astype(x.dtype))
        # keep the absorbed query latent-sharded like the cache so the
        # score contraction is partial-sum (no cache all-gather)
        q_eff = A._try_constrain(q_eff, (None, None, None, "model"))
        q_row = jnp.pad(jnp.concatenate(
            [q_eff, q_rope.astype(q_eff.dtype)], -1), [(0, 0)] + pad)
        with jax.named_scope("latent"):
            scores = jnp.einsum("bshr,btr->bhst", q_row, seen,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(mask, scores, A.NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            # the whole row, rope and pad lanes too, so that no
            # latent-only slice of the cache is made; their products
            # are dropped
            lat = jnp.einsum("bhst,btr->bshr", probs, seen)[..., :r]
        out = jnp.einsum("bshr,rhe->bshe", lat,
                         p["w_uv"].value.astype(x.dtype))
        out = mm.matmul(out.reshape(b, s, h * dv),
                        p["wo"].value.astype(x.dtype))
        return out, cache

    # ---- parallel: expand per-head keys/values
    k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["w_uk"].value.astype(x.dtype))
    value = jnp.einsum("bsr,rhe->bshe", ckv, p["w_uv"].value.astype(x.dtype))
    out = A.mla_attention(q_nope, q_rope, k_nope, kr, value, scale=scale,
                          block_k=cfg.attn_block_k)
    out = out.reshape(b, s, h * dv) @ p["wo"].value.astype(x.dtype)
    new_state = None
    if prefill:
        new_state = jnp.pad(rows, ((0, 0), (0, cache_len - s), (0, 0)))
    return out, new_state


# ------------------------------------------------------------------- mixers
def apply_mixer(p, x, cfg: ArchConfig, mixer: str, *, positions,
                state=None, prefill=False, cache_len=0, pages=None,
                layer=None):
    if pages is not None and mixer not in ("attn", "mla"):
        raise ValueError(
            f"paged decode supports position-masked cache mixers "
            f"(attn/mla) only, not {mixer!r}")
    if mixer in ("attn", "local"):
        return apply_attention(p, x, cfg, local=(mixer == "local"),
                               positions=positions, state=state,
                               prefill=prefill, cache_len=cache_len,
                               pages=pages, layer=layer)
    if mixer == "mla":
        return apply_mla(p, x, cfg, positions=positions, state=state,
                         prefill=prefill, cache_len=cache_len, pages=pages,
                         layer=layer)
    if mixer == "rglru":
        return R.apply_recurrent_block(p, x, state, want_state=prefill)
    if mixer == "mlstm":
        if state is not None:
            conv_buf, mem = state
            out, new_mem, conv_buf = _mlstm_decode(p, x, cfg, conv_buf, mem)
            return out, (conv_buf, new_mem)
        out, mem = X.apply_mlstm(p, x, cfg.xlstm)
        st = None
        if prefill:
            u = x @ p["up"].value.astype(x.dtype)
            st = (_conv_tail(u, cfg.xlstm.conv_width), mem)
        return out, st
    if mixer == "slstm":
        if state is not None:
            conv_buf, cell = state
            out, new_cell, conv_buf = _slstm_decode(p, x, cfg, conv_buf,
                                                    cell)
            return out, (conv_buf, new_cell)
        out, cell = X.apply_slstm(p, x, cfg.xlstm)
        st = (_conv_tail(x, cfg.xlstm.conv_width), cell) if prefill else None
        return out, st
    raise ValueError(mixer)


def _mlstm_decode(p, x, cfg, conv_buf, mem):
    """Single-step mLSTM with explicit conv buffer."""
    xlc = cfg.xlstm
    u = x @ p["up"].value.astype(x.dtype)               # [B,1,di]
    gate = jax.nn.silu(x @ p["up_gate"].value.astype(x.dtype))
    window = jnp.concatenate([conv_buf, u], axis=1)     # [B,cw,di]
    w = p["conv"]["w"].value.astype(x.dtype)
    c_t = jax.nn.silu(jnp.einsum("bwd,wd->bd", window, w)
                      + p["conv"]["b"].value.astype(x.dtype))[:, None]
    b, _, di = u.shape
    dh = di // xlc.heads
    q = (c_t @ p["wq"].value.astype(x.dtype)).reshape(b, 1, xlc.heads, dh)
    k = (c_t @ p["wk"].value.astype(x.dtype)).reshape(b, 1, xlc.heads, dh)
    k = k * (dh ** -0.5)
    v = (u @ p["wv"].value.astype(x.dtype)).reshape(b, 1, xlc.heads, dh)
    i_pre = (c_t @ p["wi"].value.astype(x.dtype)
             + p["bi"].value.astype(x.dtype)).astype(jnp.float32)
    f_pre = (c_t @ p["wf"].value.astype(x.dtype)
             + p["bf"].value.astype(x.dtype)).astype(jnp.float32)
    h, new_mem = X.mlstm_memory_recurrent(q, k, v, i_pre, f_pre, mem)
    hflat = h.reshape(b, 1, di)
    hflat = L.apply_norm("rms", p["norm"], hflat)
    hflat = hflat + p["skip_scale"].value.astype(x.dtype) * u
    out = (hflat * gate) @ p["down"].value.astype(x.dtype)
    return out, new_mem, window[:, 1:]


def _slstm_decode(p, x, cfg, conv_buf, cell):
    window = jnp.concatenate([conv_buf, x], axis=1)
    w = p["conv"]["w"].value.astype(x.dtype)
    xc = jax.nn.silu(jnp.einsum("bwd,wd->bd", window, w)
                     + p["conv"]["b"].value.astype(x.dtype))[:, None]
    b, _, d = x.shape
    nh = cfg.xlstm.heads
    dh = d // nh
    pre = (xc[:, 0] @ p["w"].value.astype(x.dtype)
           + p["b"].value.astype(x.dtype)).reshape(b, 4, nh, dh)
    c, n, h, m = cell
    rmat = p["r"].value.astype(jnp.float32)
    rec = jnp.einsum("bhd,hde->bhe", h, rmat).reshape(b, nh, 4, dh)
    z = pre.astype(jnp.float32) + rec.transpose(0, 2, 1, 3)
    zi, zf, zz, zo = z[:, 0], z[:, 1], z[:, 2], z[:, 3]
    m_new = jnp.maximum(zf + m, zi)
    i = jnp.exp(zi - m_new)
    f = jnp.exp(zf + m - m_new)
    c_new = f * c + i * jnp.tanh(zz)
    n_new = f * n + i
    h_new = jax.nn.sigmoid(zo) * c_new / jnp.maximum(n_new, 1.0)
    y = h_new.reshape(b, 1, d).astype(x.dtype)
    y = L.apply_norm("rms", p["norm"], y)
    uv = y @ p["up"].value.astype(x.dtype)
    u, v = jnp.split(uv, 2, axis=-1)
    y = (jax.nn.gelu(u) * v) @ p["down"].value.astype(x.dtype)
    return y, (c_new, n_new, h_new, m_new), window[:, 1:]


# -------------------------------------------------------------------- block
def apply_block(p, x, cfg: ArchConfig, spec: str, *, positions,
                state=None, prefill=False, cache_len=0,
                constrain=lambda a: a, pages=None, layer=None):
    mixer, ffn = parse_spec(spec)
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(cfg.norm, p["norm1"], x)
    # named scopes are the tracer's stable layer-naming hook: they land in
    # every equation's name stack, so repro.trace reports e.g.
    # "scan[3]/attn/wq" instead of a bare equation index
    with jax.named_scope(mixer):
        out, new_state = apply_mixer(p["mixer"], h, cfg, mixer,
                                     positions=positions, state=state,
                                     prefill=prefill, cache_len=cache_len,
                                     pages=pages, layer=layer)
    # constraining each residual add to the SP layout lets GSPMD lower the
    # row-parallel output reductions to reduce-scatters (see §Perf cell B)
    x = constrain(x + cfg.resid_mult * out)
    if ffn != "none":
        h = L.apply_norm(cfg.norm, p["norm2"], x)
        with jax.named_scope(ffn):
            if ffn == "moe":
                # serving (prefill, decode) routes dropless
                y, aux = M.apply_moe(p["ffn"], h, cfg.moe, cfg.act,
                                     dropless=prefill or state is not None)
            else:
                y = L.apply_mlp(p["ffn"], h, cfg.act)
        x = constrain(x + cfg.resid_mult * y)
    return x, new_state, aux


# -------------------------------------------------------------------- stack
def _writes_in_place(spec: str) -> bool:
    """Whether a scanned block's decode cache is written in place in the
    stacked ``[G, ...]`` carry (full attention and latent attention, see
    ``apply_attention`` and ``apply_mla``) instead of being sliced out
    and written back."""
    return parse_spec(spec)[0] in ("attn", "mla")


def make_stack(key, cfg: ArchConfig) -> dict:
    """Stacked group params [G, ...] + unrolled tail params."""
    def group_init(k):
        ks = jax.random.split(k, len(cfg.pattern))
        return {f"b{i}": make_block(ks[i], cfg, spec)
                for i, spec in enumerate(cfg.pattern)}

    gkeys = jax.random.split(key, cfg.n_groups + 2)
    groups = L.fix_stacked_axes(jax.vmap(group_init)(gkeys[:-2]))
    head_keys = jax.random.split(gkeys[-2], max(len(cfg.head), 1))
    head = [make_block(head_keys[i], cfg, spec)
            for i, spec in enumerate(cfg.head)]
    tail_keys = jax.random.split(gkeys[-1], max(len(cfg.tail), 1))
    tail = [make_block(tail_keys[i], cfg, spec)
            for i, spec in enumerate(cfg.tail)]
    return {"head": head, "groups": groups, "tail": tail}


def apply_stack(params, x, cfg: ArchConfig, *, positions, states=None,
                prefill=False, cache_len=0,
                constrain: Callable = lambda a: a, pages=None):
    """Run all layers. Returns (x, new_states | None, aux_sum)."""
    decode = states is not None

    def group_body(x, gparams, gstate, layer=None):
        new_states = {}
        aux_sum = jnp.zeros((), jnp.float32)
        for i, spec in enumerate(cfg.pattern):
            st = gstate[f"b{i}"] if decode else None
            with jax.named_scope(f"b{i}"):
                x, nst, aux = apply_block(
                    gparams[f"b{i}"], x, cfg, spec, positions=positions,
                    state=st, prefill=prefill, cache_len=cache_len,
                    pages=pages,
                    layer=layer if _writes_in_place(spec) else None)
            new_states[f"b{i}"] = nst
            aux_sum = aux_sum + aux
        x = constrain(x)
        return x, new_states, aux_sum

    body = group_body
    if cfg.remat and not (decode or prefill):
        body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)

    aux0 = jnp.zeros((), jnp.float32)
    new_head = []
    head_aux = aux0
    for i, spec in enumerate(cfg.head):
        st = states["head"][i] if decode else None
        with jax.named_scope(f"head{i}"):
            x, nst, aux = apply_block(params["head"][i], x, cfg, spec,
                                      positions=positions, state=st,
                                      prefill=prefill, cache_len=cache_len,
                                      constrain=constrain, pages=pages)
        head_aux = head_aux + aux
        new_head.append(nst)
    x = constrain(x)
    if cfg.scan_layers:
        if decode:
            # keep the stacked per-layer states in the scan CARRY, so the
            # (large) KV caches never pass through the scan's xs/ys double
            # buffers. Full- and latent-attention blocks take the whole
            # stacked cache and the layer index and scatter their rows
            # into it in place (apply_attention, apply_mla): a sliced-out
            # layer of a cache whose minor dim is 64, half a 128-lane
            # tile, cost a relayout copy in and out plus a whole-slice
            # write-back every step. Other states are sliced out and
            # written back in place.
            stacked = {f"b{i}" for i, spec in enumerate(cfg.pattern)
                       if _writes_in_place(spec)}

            def scan_fn(carry, inp):
                x, aux_acc, all_states = carry
                gparams, gi = inp
                gstate = {n: st if n in stacked else jax.tree.map(
                    lambda a: _layer_of(a, gi), st)
                    for n, st in all_states.items()}
                x, nst, aux = body(x, gparams, gstate, gi)
                all_states = {n: nst[n] if n in stacked else jax.tree.map(
                    lambda a, u: jax.lax.dynamic_update_index_in_dim(
                        a, u.astype(a.dtype), gi, 0), st, nst[n])
                    for n, st in all_states.items()}
                return (x, aux_acc + aux, all_states), None
            (x, aux_total, new_gstates), _ = jax.lax.scan(
                scan_fn, (x, head_aux, states["groups"]),
                (params["groups"], jnp.arange(cfg.n_groups)))
        else:
            def scan_fn(carry, gparams):
                x, aux_acc = carry
                x, nst, aux = body(x, gparams, None)
                return (x, aux_acc + aux), nst
            (x, aux_total), new_gstates = jax.lax.scan(
                scan_fn, (x, head_aux), params["groups"])
            if not prefill:
                new_gstates = None
    else:
        aux_total = head_aux
        new_g = []
        for gi in range(cfg.n_groups):
            gparams = jax.tree.map(lambda a: a[gi], params["groups"])
            gstate = (jax.tree.map(lambda a: a[gi], states["groups"])
                      if decode else None)
            x, nst, aux = body(x, gparams, gstate)
            aux_total = aux_total + aux
            new_g.append(nst)
        new_gstates = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_g)
                       if (decode or prefill) else None)

    new_tail = []
    for i, spec in enumerate(cfg.tail):
        st = states["tail"][i] if decode else None
        with jax.named_scope(f"tail{i}"):
            x, nst, aux = apply_block(params["tail"][i], x, cfg, spec,
                                      positions=positions, state=st,
                                      prefill=prefill, cache_len=cache_len,
                                      constrain=constrain, pages=pages)
        aux_total = aux_total + aux
        new_tail.append(nst)
    x = constrain(x)

    new_states = None
    if decode or prefill:
        new_states = {"head": new_head, "groups": new_gstates,
                      "tail": new_tail}
    return x, new_states, aux_total
