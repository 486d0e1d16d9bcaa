"""Language-model wrapper: embeddings, output heads, loss, and the
train / prefill / decode step builders used by the launcher and the dry-run.

Input modalities (per the assignment):
  tokens -- ``{"tokens": i32[B, S]}`` ordinary LMs.
  embeds -- ``{"embeds": bf16[B, S, D], "positions": i32[3, B, S]}``
            Qwen2-VL backbone; the vision frontend is a stub that supplies
            precomputed patch embeddings + 3-component M-RoPE positions.
  codes  -- ``{"codes": i32[B, K, S]}`` MusicGen backbone over EnCodec
            codebooks; embeddings of the K streams are summed, and K output
            heads predict the next code per stream.

Cross-entropy is computed in *sequence chunks* (scan) so the full [B, S, V]
logits tensor never materializes -- required for 150k-vocab models at 4k+
sequence lengths.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

# imported at module level on purpose: repro.core builds bit-mask constants
# with jnp ops at import time, which must not first happen inside a jit
# trace (the constants would become tracers); see _monitor_metrics
from repro.core import monitor as _pm_monitor
from repro.core import systolic as _pm_systolic

from . import layers as L
from . import transformer as T
from .config import ArchConfig

Constrain = Callable[[jax.Array], jax.Array]
_id = lambda x: x


# ----------------------------------------------------------------- params
def init_model(key, cfg: ArchConfig) -> dict:
    ks = jax.random.split(key, 4)
    p: dict = {}
    if cfg.inputs == "tokens":
        p["embed"] = L.Param(
            L.normal_init(ks[0], (cfg.vocab, cfg.d_model), 1.0),
            ("vocab", "embed"))
    elif cfg.inputs == "codes":
        p["embed"] = L.Param(
            L.normal_init(ks[0], (cfg.codebooks, cfg.vocab, cfg.d_model),
                          1.0), (None, "vocab", "embed"))
    p["stack"] = T.make_stack(ks[1], cfg)
    p["final_norm"] = L.make_norm(cfg.norm, cfg.d_model)
    if cfg.inputs == "codes":
        p["heads"] = L.Param(
            L.normal_init(ks[2], (cfg.codebooks, cfg.d_model, cfg.vocab),
                          cfg.d_model ** -0.5), (None, "embed", "vocab"))
    elif not cfg.tie_embeddings:
        p["unembed"] = L.dense_param(ks[2], cfg.d_model, cfg.vocab,
                                     "embed", "vocab")
    return p


# ------------------------------------------------------------------ embed
def embed_inputs(params, cfg: ArchConfig, inputs: dict,
                 dtype=jnp.bfloat16):
    """Returns (x [B,S,D], positions)."""
    if cfg.inputs == "embeds":
        x = inputs["embeds"].astype(dtype)
        positions = inputs["positions"]
        return x * cfg.emb_mult, positions
    if cfg.inputs == "codes":
        codes = inputs["codes"]                     # [B, K, S]
        emb = params["embed"].value.astype(dtype)   # [K, V, D]
        x = jnp.sum(jax.vmap(
            lambda e, c: e[c], in_axes=(0, 1), out_axes=1)(emb, codes),
            axis=1)                                 # [B, S, D]
        b, s = codes.shape[0], codes.shape[2]
        positions = inputs.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        if cfg.pos == "sinusoidal":
            x = x + L.sinusoidal_positions(positions, cfg.d_model
                                           ).astype(dtype)
        return x * cfg.emb_mult, positions
    tokens = inputs["tokens"]
    b, s = tokens.shape
    x = params["embed"].value.astype(dtype)[tokens]
    positions = inputs.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return x * cfg.emb_mult, positions


def _head_weights(params, cfg: ArchConfig, dtype):
    if cfg.inputs == "codes":
        return params["heads"].value.astype(dtype)      # [K, D, V]
    if cfg.tie_embeddings:
        return params["embed"].value.astype(dtype).T    # [D, V]
    return params["unembed"].value.astype(dtype)


def logits_fn(params, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    """h [..., D] -> logits [..., V] (or [..., K, V] for codes)."""
    w = _head_weights(params, cfg, h.dtype)
    with jax.named_scope("lm_head"):
        if cfg.inputs == "codes":
            out = jnp.einsum("...d,kdv->...kv", h, w)
        else:
            from . import matmul as mm
            out = mm.matmul(h, w)
    out = out.astype(jnp.float32) * cfg.logit_mult
    if cfg.logit_softcap > 0:
        out = jnp.tanh(out / cfg.logit_softcap) * cfg.logit_softcap
    return out


# ------------------------------------------------------------------- apply
def apply_model(params, cfg: ArchConfig, inputs: dict, *, states=None,
                prefill=False, cache_len=0, constrain: Constrain = _id):
    """Forward to final hidden states. Returns (h, new_states, aux).

    When ``inputs`` carries ``"pages"`` (a ``[B, MP]`` per-row page table,
    see :mod:`repro.serve.paging`), ``states`` is interpreted as a paged
    KV pool (``[P, page_size, ...]`` leaves) instead of per-row dense
    caches; attention then scatters writes through the table and gathers
    dense views for the score computation.
    """
    with jax.named_scope("embed"):
        x, positions = embed_inputs(params, cfg, inputs,
                                    dtype=jnp.dtype(cfg.compute_dtype))
    x = constrain(x)
    x, new_states, aux = T.apply_stack(
        params["stack"], x, cfg, positions=positions, states=states,
        prefill=prefill, cache_len=cache_len, constrain=constrain,
        pages=inputs.get("pages"))
    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    return x, new_states, aux


# -------------------------------------------------------------------- loss
def _chunked_ce(params, cfg: ArchConfig, h: jax.Array, targets: jax.Array,
                mask: jax.Array, chunk: int = 512):
    """Mean next-token CE without materializing [B, S, V].

    h: [B, S, D]; targets: [B, S] (or [B, K, S] for codes); mask: [B, S].
    """
    b, s, d = h.shape
    c = min(chunk, s)
    nb = -(-s // c)
    pad = nb * c - s
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        targets = jnp.pad(targets, [(0, 0)] * (targets.ndim - 1)
                          + [(0, pad)])
    hs = jnp.moveaxis(h.reshape(b, nb, c, d), 1, 0)
    ms = jnp.moveaxis(mask.reshape(b, nb, c), 1, 0)
    ts = jnp.moveaxis(targets.reshape(targets.shape[:-1] + (nb, c)), -2, 0)

    def chunk_loss(carry, xs):
        hc, tc, mc = xs
        lg = logits_fn(params, cfg, hc)            # [B,c,V] or [B,c,K,V]
        lse = jax.nn.logsumexp(lg, axis=-1)
        # label logit via iota-compare mask-reduce: fuses into the reduce
        # loop (never materializes a [.., V] one-hot) and stays sharded
        # under a vocab-partitioned V axis (take_along_axis would gather)
        def label_select(logits, targets):
            iota = jax.lax.broadcasted_iota(targets.dtype, logits.shape,
                                            logits.ndim - 1)
            return jnp.where(iota == targets[..., None], logits, 0.0
                             ).sum(axis=-1)

        if cfg.inputs == "codes":
            tc_ = jnp.moveaxis(tc, 1, -1)          # [B,c,K]
            lab = label_select(lg, tc_)
            ce = (lse - lab).sum(-1) / cfg.codebooks
        else:
            lab = label_select(lg, tc)
            ce = lse - lab
        return (carry[0] + (ce * mc).sum(), carry[1] + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        chunk_loss, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ts, ms))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ArchConfig, batch: dict,
            constrain: Constrain = _id):
    """Next-token LM loss. batch carries the model inputs (+ optional
    "mask"). Returns (loss, metrics)."""
    h, _, aux = apply_model(params, cfg, batch, constrain=constrain)
    if cfg.inputs == "codes":
        tokens = batch["codes"]                     # [B,K,S]
        targets = tokens[..., 1:]
        hshift = h[:, :-1]
        mask = batch.get("mask", jnp.ones(tokens[:, 0].shape))[:, 1:]
    elif cfg.inputs == "embeds":
        tokens = batch["labels"]                    # [B,S]
        targets = tokens[:, 1:]
        hshift = h[:, :-1]
        mask = batch.get("mask", jnp.ones(tokens.shape))[:, 1:]
    else:
        tokens = batch["tokens"]
        targets = tokens[:, 1:]
        hshift = h[:, :-1]
        mask = batch.get("mask", jnp.ones(tokens.shape))[:, 1:]
    ce = _chunked_ce(params, cfg, hshift, targets, mask.astype(jnp.float32))
    loss = ce + 0.01 * aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}


# ----------------------------------------------------------- step builders
def make_train_step(cfg: ArchConfig, optimizer, constrain: Constrain = _id,
                    grad_accum: int = 1, monitor: bool = False):
    """(params, opt_state, batch, step) -> (params, opt_state, metrics)."""

    def compute_grads(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, cfg, batch, constrain)
        return grads, metrics

    def train_step(params, opt_state, batch, step):
        if grad_accum > 1:
            def micro(carry, mb):
                g_acc, m_acc = carry
                g, m = compute_grads(params, mb)
                return (jax.tree.map(jnp.add, g_acc, g),
                        jax.tree.map(jnp.add, m_acc, m)), None

            def split(a):
                # micro-batch along the batch axis: axis 0 normally, axis 1
                # for leading-component leaves like M-RoPE positions [3,B,S]
                ax = 0 if a.shape[0] % grad_accum == 0 else 1
                n = a.shape[ax] // grad_accum
                shape = a.shape[:ax] + (grad_accum, n) + a.shape[ax + 1:]
                return jnp.moveaxis(a.reshape(shape), ax, 0)
            micro_batches = jax.tree.map(split, batch)
            g0 = jax.tree.map(jnp.zeros_like, params)
            m0 = {"loss": 0.0, "ce": 0.0, "aux": 0.0}
            m0 = jax.tree.map(jnp.float32, m0)
            (grads, metrics), _ = jax.lax.scan(micro, (g0, m0),
                                               micro_batches)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            metrics = jax.tree.map(lambda m: m / grad_accum, metrics)
        else:
            grads, metrics = compute_grads(params, batch)

        updates, opt_state = optimizer.update(grads, opt_state, params,
                                              step)
        params = jax.tree.map(jnp.add, params, updates)
        metrics["grad_norm"] = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        if monitor:
            metrics.update(_monitor_metrics(params, cfg, batch))
        return params, opt_state, metrics

    return train_step


def pick_monitor_weights(params) -> list[tuple[str, jax.Array]]:
    """Representative layer-0 weights for power monitoring: the first
    input projection of the first block (whatever the mixer family calls
    it), plus the FFN up projection when the block has one. The first
    block is the unrolled ``head`` block when the stack has one (e.g. a
    leading dense layer before scanned expert layers), else the first
    scanned block. The single selection rule shared by train-step
    monitoring (:func:`_monitor_metrics`) and the serving engine's
    per-request accountant -- so serving power reports and training
    power metrics always watch the same sites."""
    stack = params["stack"]
    if stack["head"]:
        blk = stack["head"][0]
    elif jax.tree.leaves(stack["groups"]):
        blk = jax.tree.map(lambda a: a[0], stack["groups"])["b0"]
    else:                                       # unrolled-only stacks
        blk = stack["tail"][0]
    out = []
    mix = blk["mixer"]
    for wname in ("wq", "in_x", "up", "w_dkv"):
        if wname in mix:
            w = mix[wname].value
            if w.ndim == 3:
                w = w.reshape(w.shape[0], -1)
            out.append((f"layer0/{wname}", w))
            break
    ffn = blk.get("ffn")
    if ffn is not None and "up" in ffn:
        out.append(("layer0/ffn_up", ffn["up"].value))
    return out


def _monitor_metrics(params, cfg: ArchConfig, batch) -> dict:
    """Paper's PowerMonitor on representative (activation, weight) pairs:
    the embedded inputs against layer-0 projection weights, streamed
    through an MXU-geometry systolic array."""
    monitor, systolic = _pm_monitor, _pm_systolic
    x, _ = embed_inputs(params, cfg, batch)
    x2 = x.reshape(-1, x.shape[-1])[:256]
    (_, w), *_ = pick_monitor_weights(params)
    mcfg = monitor.MonitorConfig(geometry=systolic.MXU_SA)
    m = monitor.monitor_matmul(x2, w[:, :256], mcfg)
    return {f"power/{k}": v for k, v in m.items()
            if k not in monitor.SIZE_KEYS}


def make_decode_state(cfg: ArchConfig, batch: int, cache_len: int,
                      dtype=jnp.bfloat16) -> dict:
    """Zero-initialized decode states matching ``apply_stack``'s structure.

    Full-attention (``attn``) K and V are lane-dense ``[B, T, kv * hd]``
    (scanned groups ``[G, B, T, kv * hd]``): with hd = 64 a ``[.., kv,
    hd]`` leaf fills half a 128-lane tile, so the compiler stores it
    T-minor and relays out each layer's slice to write a row; the flat
    layout is the one both the row write and the attention read use
    (``transformer.apply_attention``). Latent attention (``mla``) keeps
    one row a position, ``[B, T, mla_row_width]``: the latent, the
    64-wide rope key beside it, zeros to a whole number of lane tiles
    (``transformer.apply_mla``). Other mixers' states keep their own
    shapes. The dry-run turns this into ShapeDtypeStructs via
    ``jax.eval_shape``.
    """
    def block_state(spec: str):
        mixer, _ = T.parse_spec(spec)
        kv, hd = cfg.n_kv_heads, cfg.hd
        if mixer == "attn":
            return (jnp.zeros((batch, cache_len, kv * hd), dtype),
                    jnp.zeros((batch, cache_len, kv * hd), dtype))
        if mixer == "local":
            w = cfg.window
            return (jnp.zeros((batch, w, kv, hd), dtype),
                    jnp.zeros((batch, w, kv, hd), dtype),
                    jnp.full((batch, w), -1, jnp.int32))
        if mixer == "mla":
            return jnp.zeros((batch, cache_len, T.mla_row_width(cfg.mla)),
                             dtype)
        if mixer == "rglru":
            w = cfg.rglru.lru_width or cfg.d_model
            return (jnp.zeros((batch, cfg.rglru.conv_width - 1, w), dtype),
                    jnp.zeros((batch, w), jnp.float32))
        if mixer == "mlstm":
            x = cfg.xlstm
            di = int(cfg.d_model * x.mlstm_proj_factor)
            dh = di // x.heads
            return (jnp.zeros((batch, x.conv_width - 1, di), dtype),
                    (jnp.zeros((batch, x.heads, dh, dh), jnp.float32),
                     jnp.zeros((batch, x.heads, dh), jnp.float32),
                     jnp.zeros((batch, x.heads), jnp.float32)))
        if mixer == "slstm":
            x = cfg.xlstm
            dh = cfg.d_model // x.heads
            z = lambda: jnp.zeros((batch, x.heads, dh), jnp.float32)
            return (jnp.zeros((batch, x.conv_width - 1, cfg.d_model),
                              dtype),
                    (z(), jnp.ones((batch, x.heads, dh), jnp.float32),
                     z(), z()))
        raise ValueError(mixer)

    def group_state():
        return {f"b{i}": block_state(spec)
                for i, spec in enumerate(cfg.pattern)}

    g = group_state()
    groups = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_groups,) + a.shape), g)
    head = [block_state(spec) for spec in cfg.head]
    tail = [block_state(spec) for spec in cfg.tail]
    return {"head": head, "groups": groups, "tail": tail}


def make_prefill_step(cfg: ArchConfig, cache_len: int,
                      constrain: Constrain = _id):
    """(params, inputs) -> (last_logits, states)."""
    def prefill_step(params, inputs):
        h, states, _ = apply_model(params, cfg, inputs, prefill=True,
                                   cache_len=cache_len,
                                   constrain=constrain)
        logits = logits_fn(params, cfg, h[:, -1])
        return logits, states

    return prefill_step


def make_slot_prefill_step(cfg: ArchConfig, cache_len: int,
                           constrain: Constrain = _id):
    """(params, inputs, length) -> (logits at ``length-1``, states).

    Prefill for the serving engine's slot admission: ``inputs`` carries a
    *right-padded* prompt of static bucket length ``S >= length`` (a traced
    scalar), and the returned logits are taken at the last REAL position,
    not the last padded one. Causality makes right padding safe for the
    cache too: position ``p``'s hidden state never reads positions ``> p``,
    and every padded cache row is overwritten by a decode write before any
    later step's mask admits it. (Recurrent mixers carry state *through*
    padded tokens, so they require ``S == length``; the engine buckets only
    attention-family architectures.)
    """
    def slot_prefill_step(params, inputs, length):
        h, states, _ = apply_model(params, cfg, inputs, prefill=True,
                                   cache_len=cache_len,
                                   constrain=constrain)
        h_last = jax.lax.dynamic_slice_in_dim(
            h, jnp.maximum(length - 1, 0), 1, axis=1)[:, 0]
        logits = logits_fn(params, cfg, h_last)
        return logits, states

    return slot_prefill_step


def make_chunk_prefill_step(cfg: ArchConfig, constrain: Constrain = _id):
    """(params, pool_states, inputs, length) -> (logits, pool_states).

    One chunk of a PAGED prefill (see :mod:`repro.serve.paging`): the
    inputs carry a batch-1 token window ``[1, C]`` at absolute
    ``positions [1, C]`` plus the row's page table ``pages [1, MP]``.
    The chunk's KV is scattered into the pool pages and its attention
    reads the gathered paged history (earlier chunks, shared prefix
    pages), so long prompts stream through admission C tokens at a time
    instead of stalling it. Right-padding inside the final chunk uses
    position ``-1`` as a sentinel: those writes land on the trash page
    and those queries are fully masked. Returned logits are taken at
    absolute position ``length - 1`` -- meaningful only on the chunk
    that contains it (the last one); callers ignore the rest.
    """
    def chunk_prefill_step(params, states, inputs, length):
        h, states, _ = apply_model(params, cfg, inputs, states=states,
                                   constrain=constrain)
        start = inputs["positions"][0, 0]
        idx = jnp.clip(length - 1 - start, 0, h.shape[1] - 1)
        h_last = jax.lax.dynamic_slice_in_dim(h, idx, 1, axis=1)[:, 0]
        logits = logits_fn(params, cfg, h_last)
        return logits, states

    return chunk_prefill_step


def make_embed_step(cfg: ArchConfig):
    """(params, inputs) -> embedded activations ``x [B, S, D]``.

    The serving engine's power-accounting path: the per-step operand the
    monitor streams is the embedded input, and jitting the lookup (rather
    than dispatching ``embed_inputs`` eagerly every sampled step) both
    cuts per-step overhead and gives the mesh engine a single place to
    pin replicated out_shardings -- the gathered activations feed the
    accountant bit-identically to the single-device engine.
    """
    def embed_step(params, inputs):
        x, _ = embed_inputs(params, cfg, inputs)
        return x

    return embed_step


def make_decode_step(cfg: ArchConfig, constrain: Constrain = _id):
    """(params, states, inputs{token/codes/embeds, positions}) ->
    (logits, states)."""
    def decode_step(params, states, inputs):
        h, states, _ = apply_model(params, cfg, inputs, states=states,
                                   constrain=constrain)
        logits = logits_fn(params, cfg, h[:, -1])
        return logits, states

    return decode_step
