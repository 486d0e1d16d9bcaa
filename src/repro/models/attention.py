"""Attention variants: GQA (full / chunked-flash / sliding window), decode
with KV caches, and Multi-head Latent Attention (MLA).

GQA is computed in *grouped* form -- q reshaped to [B, S, Hkv, rep, D] and
einsummed directly against the un-repeated K/V. Materializing repeated KV
(broadcast+reshape) triggers involuntary resharding under SPMD with sharded
head dims and wastes cache bandwidth; the grouped einsum keeps K/V in their
stored layout.

Memory discipline: training/prefill attention is *chunked* (online softmax
over KV blocks, lax.scan) so peak activation memory is O(S * Bk) instead of
O(S^2) -- required for the 32k prefill and 512k cells of the dry-run.

Known, documented FLOP overhead: the chunked-causal scan computes the upper
triangle and masks it (2x the causal-useful score FLOPs). This is inherent
to dense-HLO implementations; a Mosaic flash kernel removes it on real TPU.
The roofline report carries this factor explicitly (MODEL_FLOPS vs
HLO_FLOPs). Sliding-window attention instead gathers per-block KV windows,
so its overhead is (Bq + W) / W, not S / W.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _group_q(q: jax.Array, hkv: int) -> jax.Array:
    """[B, S, H, D] -> [B, S, Hkv, rep, D]."""
    b, s, h, d = q.shape
    return q.reshape(b, s, hkv, h // hkv, d)


def _try_constrain(x: jax.Array, spec) -> jax.Array:
    """Best-effort sharding constraint: no-op when no mesh is in scope or
    the axes do not exist (unit tests, host meshes). The sentinel "dp"
    resolves to ("pod", "data"), then "data", then replicated."""
    from jax.sharding import PartitionSpec as P
    for dpv in (("pod", "data"), "data", None):
        s = [dpv if e == "dp" else e for e in spec]
        try:
            return jax.lax.with_sharding_constraint(x, P(*s))
        except Exception:                                # noqa: BLE001
            continue
    return x


def tp_heads_constrain(x: jax.Array) -> jax.Array:
    """Pin a projected [B, S, H, D] tensor to (batch=dp, heads=model).

    Under sequence parallelism the residual stream is S-sharded; leaving
    the SP->TP transition to GSPMD makes it all-gather the full residual
    (f32, d_model wide) BEFORE the projections. Constraining the projection
    OUTPUTS to head-sharding moves the seq gather after the projection,
    onto tensors a TP-factor smaller (project-then-gather, Korthikanti et
    al.). (§Perf cell B; benefits every attention arch.)"""
    return _try_constrain(x, ("dp", None, "model", None))


def full_attention(q, k, v, *, causal=True, scale=None, softcap=0.0,
                   positions_q=None, positions_k=None, window=0):
    """Reference O(S^2)-memory attention. [B,S,H,D] operands.

    Used for smoke tests and as the oracle for the chunked path.
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    qg = _group_q(q, hkv)
    scale = scale if scale is not None else d ** -0.5
    scores = jnp.einsum("bqkrd,bskd->bkrqs", qg, k).astype(jnp.float32)
    scores = scores * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    pq = positions_q if positions_q is not None else jnp.arange(sq)
    pk = positions_k if positions_k is not None else jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= pq[:, None] >= pk[None, :]
    if window > 0:
        mask &= pq[:, None] - pk[None, :] < window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


def chunked_attention(q, k, v, *, causal=True, scale=None, softcap=0.0,
                      block_k: int = 1024):
    """Flash-style attention: scan over KV blocks with online softmax.

    [B,S,H,D] -> [B,S,H,Dv]; peak memory O(B*H*S*block_k) scores per step.
    """
    b, sq, h, d = q.shape
    dv = v.shape[-1]                 # may differ from d (e.g. MLA)
    hkv = k.shape[2]
    rep = h // hkv
    qg = _group_q(q, hkv)
    scale = scale if scale is not None else d ** -0.5
    sk = k.shape[1]
    bk = min(block_k, sk)
    nb = -(-sk // bk)
    pad = nb * bk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nb, bk, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, bk, hkv, dv).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(sq)

    def step(carry, blk):
        m, l, acc = carry
        kj, vj, j = blk
        s_blk = jnp.einsum("bqkrd,bskd->bkrqs", qg, kj
                           ).astype(jnp.float32) * scale
        if softcap > 0:
            s_blk = jnp.tanh(s_blk / softcap) * softcap
        kpos = j * bk + jnp.arange(bk)
        mask = kpos[None, :] < sk
        if causal:
            mask = mask & (qpos[:, None] >= kpos[None, :])
        s_blk = jnp.where(mask[None, None, None], s_blk, NEG_INF)
        m_new = jnp.maximum(m, s_blk.max(axis=-1))
        p = jnp.exp(s_blk - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkrqs,bskd->bkrqd", p.astype(q.dtype), vj).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, rep, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]        # [B,kv,rep,S,Dv]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv).astype(q.dtype)


def sliding_window_attention(q, k, v, *, window: int, scale=None,
                             block_q: int = 1024):
    """Local causal attention via gathered per-block KV windows.

    Each q block of size Bq attends to its gathered [W + Bq] KV neighborhood
    -- FLOP overhead (W + Bq)/W instead of the S/W of a full masked scan.
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    bq = min(block_q, s)
    nb = -(-s // bq)
    pad = nb * bq - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    span = window + bq
    base = jnp.arange(nb)[:, None] * bq - window
    idx = base + jnp.arange(span)[None, :]              # [nb, span]
    valid = idx >= 0
    idx = jnp.clip(idx, 0, nb * bq - 1)
    kw = k[:, idx]                                      # [B, nb, span, Hkv, D]
    vw = v[:, idx]
    qb = _group_q(q.reshape(b, nb * bq, h, d), hkv).reshape(
        b, nb, bq, hkv, h // hkv, d)
    scores = jnp.einsum("bnqkrd,bnskd->bnkrqs", qb, kw).astype(jnp.float32)
    scores = scores * scale
    qpos = jnp.arange(nb * bq).reshape(nb, bq)
    kpos = idx
    mask = (qpos[:, :, None] >= kpos[:, None, :]) \
        & (qpos[:, :, None] - kpos[:, None, :] < window) \
        & valid[:, None, :] & (kpos[:, None, :] < s)
    scores = jnp.where(mask[None, :, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnkrqs,bnskd->bnqkrd", probs, vw)
    return out.reshape(b, nb * bq, h, v.shape[-1])[:, :s]


def paged_chunk_attention(q, k_cache, v_cache, positions_q, *, scale=None,
                          softcap=0.0, constrain_q: bool = True):
    """Multi-token causal decode for paged chunk prefill: ``q [B, C, H, D]``
    against gathered dense cache views ``[B, Smax, Hkv, D]`` (page pool
    rows re-assembled in logical order). Query ``i`` sits at absolute
    position ``positions_q[b, i]`` and attends to cache positions ``<=``
    it -- the chunk's own keys were scattered into the pool before the
    gather, so intra-chunk causality and the paged history are covered by
    one mask. Negative query positions mark right-padding: their rows are
    fully masked (finite garbage out -- softmax of a constant row), and
    callers never read them."""
    b, sq, h, d = q.shape
    hkv = k_cache.shape[2]
    qg = _group_q(q, hkv)
    if constrain_q:
        qg = _try_constrain(qg, (None, None, None, None, "model"))
    scale = scale if scale is not None else d ** -0.5
    scores = jnp.einsum("bqkrd,bskd->bkrqs", qg, k_cache
                        ).astype(jnp.float32) * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    kpos = jnp.arange(k_cache.shape[1])
    mask = kpos[None, None, :] <= positions_q[:, :, None]    # [B, C, Smax]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", probs, v_cache)
    return out.reshape(b, sq, h, v_cache.shape[-1])


def decode_attention(q, k_cache, v_cache, cache_len, *, scale=None,
                     softcap=0.0, constrain_q: bool = True):
    """Single-token decode: q ``[B, 1, H, D]`` against lane-dense
    ``[B, Smax, Hkv * D]`` caches (a ``[B, Smax, Hkv, D]`` view is
    flattened to that) holding ``cache_len`` valid entries.

    Both products contract or keep the cache's flat feature dim whole, so
    the cache is read in the layout it is stored in (a ``[.., Hkv, D]``
    view with D = 64 fills half a 128-lane tile and makes the compiler
    relayout the whole cache every step). The scores multiply the cache
    by a block-diagonal query ``[B, H, Hkv * D]`` (head h holds its
    values in its KV group's block, zeros elsewhere); the output
    multiplies the probabilities by the whole value row and keeps each
    head's own block. That is Hkv times the useful MXU work, all of it
    on data already read, and exact: the off-block products are zeros.
    Both products accumulate in float32.
    """
    b, _, h, d = q.shape
    k_cache = k_cache.reshape(k_cache.shape[:2] + (-1,))
    v_cache = v_cache.reshape(v_cache.shape[:2] + (-1,))
    hkv = k_cache.shape[-1] // d
    rep = h // hkv
    own = jnp.eye(hkv, dtype=bool)[None, :, None, :, None]  # [1,k,1,j,1]
    qbd = jnp.where(own, _group_q(q, hkv)[:, 0, :, :, None, :],
                    jnp.zeros((), q.dtype)).reshape(b, h, hkv * d)
    # Match q's layout to the cache's (flat feature dim sharded on the
    # model axis): the scores contraction then runs on partial shards + a
    # small all-reduce instead of GSPMD all-gathering the far larger KV
    # cache every step. (§Perf cell A.) Gated off for M-RoPE queries,
    # whose frequency-gather interacts badly with a forced feature
    # sharding (measured: 800 GiB of per-layer cache all-to-alls).
    if constrain_q:
        qbd = _try_constrain(qbd, (None, None, "model"))
    scale = scale if scale is not None else d ** -0.5
    scores = jnp.einsum("bhf,btf->bht", qbd, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    kpos = jnp.arange(k_cache.shape[1])
    mask = kpos[None, :] < cache_len[:, None]           # [B, Smax]
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    full = jnp.einsum("bht,btf->bhf", probs, v_cache,
                      preferred_element_type=jnp.float32)
    dv = v_cache.shape[-1] // hkv
    out = jnp.diagonal(full.reshape(b, hkv, rep, hkv, dv), axis1=1, axis2=3)
    return out.transpose(0, 3, 1, 2).reshape(b, 1, h, dv).astype(q.dtype)


# ----------------------------------------------------------------- MLA ----
@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2 family)."""
    kv_lora_rank: int = 512
    q_lora_rank: int = 0            # 0 = full-rank q projection
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


def mla_attention(q_nope, q_rope, k_nope, k_rope, value, *, causal=True,
                  scale=None, block_k: int = 1024):
    """MLA score path: per-head nope+rope concatenated queries/keys.

    q_nope/k_nope: [B,S,H,Dn]; q_rope: [B,S,H,Dr]; k_rope: [B,S,1,Dr]
    (shared across heads); value: [B,S,H,Dv]. ``scale`` defaults to
    ``(Dn + Dr) ** -0.5``.
    """
    h = q_nope.shape[2]
    k_rope = jnp.broadcast_to(
        k_rope, k_rope.shape[:2] + (h, k_rope.shape[-1]))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    if scale is None:
        scale = (q_nope.shape[-1] + q_rope.shape[-1]) ** -0.5
    return chunked_attention(q, k, value, causal=causal, scale=scale,
                             block_k=block_k)
