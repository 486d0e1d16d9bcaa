"""DeepSeek-V2 decoders: multi-head latent attention (MLA) with YaRN rope
in every layer, a dense SwiGLU FFN in the first ``first_k_dense_replace``
layers and routed plus shared SwiGLU experts in the rest (DeepSeek-V2,
-V2-Lite).

The program side of the family: the configuration file's published keys
mapped onto the program, its parameter tree laid out for the float32
reference (``bench/reference/deepseek_v2.py``), the two sites the power
accountant prices, and the work counts the per-layer metrics rest on.

The file may give this chip's share of an expert-parallel deployment:
``n_routed_experts`` is then the number of experts held here, the first
of them ``first_held_expert``, and ``published["n_routed_experts"]`` the
router's width. Every count is a LOWER bound on the work an honest
implementation does, so that a share of a peak built on it cannot pass
100% unless the time is wrong.
"""
from __future__ import annotations

from bench import counts
from repro.configs import ARCHS
from repro.models.config import MLAConfig, MoEConfig, YarnRope

#: the routing the program computes (softmax over every router logit,
#: greedy top-k, gates the raw probabilities); a file that states other
#: routing is refused rather than run as something else
_ROUTING = {"scoring_func": "softmax", "topk_method": "greedy",
            "norm_topk_prob": False, "routed_scaling_factor": 1,
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1}


def router_width(cfg: dict) -> int:
    """Experts the router scores: the published count."""
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file: the registry
    entry named by ``arch`` with every size taken from the file, so the
    file is what runs."""
    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is fixed at 1e-6; "
                         "the configuration file must state what runs")
    bad = {k: cfg[k] for k, v in _ROUTING.items() if cfg[k] != v}
    if (bad or cfg["q_lora_rank"] or cfg["attention_bias"]
            or cfg["tie_word_embeddings"]
            or cfg["rope_scaling"]["type"] != "yarn"):
        raise ValueError(f"routing or attention the program does not "
                         f"compute: {bad or cfg}")
    rs = cfg["rope_scaling"]
    dense = int(cfg["first_k_dense_replace"])
    kw = dict(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head=("mla+mlp",) * dense, pattern=("mla+moe",), tail=(),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=0,
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(num_experts=router_width(cfg),
                      top_k=cfg["num_experts_per_tok"],
                      expert_ff=cfg["moe_intermediate_size"],
                      num_shared=cfg["n_shared_experts"],
                      shared_ff=(cfg["moe_intermediate_size"]
                                 * cfg["n_shared_experts"]),
                      first_expert=int(cfg.get("first_held_expert", 0)),
                      held=cfg["n_routed_experts"]),
        rope_theta=float(cfg["rope_theta"]),
        yarn=YarnRope(factor=float(rs["factor"]),
                      original_max_position=int(
                          rs["original_max_position_embeddings"]),
                      beta_fast=float(rs["beta_fast"]),
                      beta_slow=float(rs["beta_slow"]),
                      mscale=float(rs["mscale"]),
                      mscale_all_dim=float(rs["mscale_all_dim"])),
        tie_embeddings=False, compute_dtype="bfloat16")
    if "attn_block_k" in cfg:
        kw["attn_block_k"] = int(cfg["attn_block_k"])
    return ARCHS[cfg["arch"]].with_(**kw)


def _interleave_rope(w, dr: int):
    """The program's rope columns (the last ``dr`` of ``w``), which it
    rotates as half-split pairs ``(i, i + dr/2)``, reordered into the
    published layout, whose pairs are adjacent ``(2i, 2i + 1)`` and which
    the published model (and the reference) de-interleaves before its
    half-split rotation: column ``2i`` is the program's ``i``, column
    ``2i + 1`` its ``i + dr/2``."""
    import jax.numpy as jnp
    rope = w[..., -dr:]
    half = dr // 2
    pairs = jnp.stack([rope[..., :half], rope[..., half:]], axis=-1)
    return jnp.concatenate(
        [w[..., :-dr], pairs.reshape(rope.shape)], axis=-1)


def _block(blk: dict, dr: int) -> dict:
    val = lambda p: p.value
    mix, ffn = blk["mixer"], blk["ffn"]
    out = {name: val(mix[name]) for name in
           ("w_dkv", "w_uk", "w_uv", "wo")}
    out["wq"] = _interleave_rope(val(mix["wq"]), dr)
    out["w_kr"] = _interleave_rope(val(mix["w_kr"]), dr)
    out["kv_norm"] = val(mix["kv_norm"]["scale"])
    out["norm1"] = val(blk["norm1"]["scale"])
    out["norm2"] = val(blk["norm2"]["scale"])
    if "router" in ffn:
        out.update({name: val(ffn[name]) for name in
                    ("router", "w_gate", "w_up", "w_down")})
        out.update({f"s_{name}": val(ffn["shared"][name])
                    for name in ("gate", "up", "down")})
    else:
        out.update({name: val(ffn[name]) for name in ("gate", "up", "down")})
    return out


def plain_weights(params, cfg: dict) -> dict:
    """The program's tree as the reference's plain layout: ``embed``,
    ``unembed``, ``final_norm``, the dense leading layers ``dense`` (a
    list) and the expert layers ``moe`` stacked on axis 0. The rope
    columns of ``wq`` and ``w_kr`` are put in the published interleaved
    order (``_interleave_rope``): the reference de-interleaves them as
    the published model does, and so computes what the program does."""
    dr = cfg["qk_rope_head_dim"]
    stack = params["stack"]
    return {"embed": params["embed"].value,
            "unembed": params["unembed"].value,
            "final_norm": params["final_norm"]["scale"].value,
            "dense": [_block(b, dr) for b in stack["head"]],
            "moe": _block(stack["groups"]["b0"], dr)}


def _program_rope(w, dr: int):
    """Inverse of ``_interleave_rope``: the program's column order."""
    import jax.numpy as jnp
    rope = w[..., -dr:]
    return jnp.concatenate([w[..., :-dr], rope[..., 0::2], rope[..., 1::2]],
                           axis=-1)


def monitor_sites(plain: dict) -> list:
    """The accountant's two sites, layer 0's query projection (``[d, h *
    (nope + rope)]`` as ``lm.pick_monitor_weights`` flattens it, its
    columns in the program's order) and its dense FFN up projection."""
    blk = plain["dense"][0]
    wq = _program_rope(blk["wq"], blk["w_kr"].shape[-1])
    return [wq.reshape(wq.shape[0], -1), blk["up"]]


def embed_multiplier(cfg: dict) -> float:
    """The scale of the embedded tokens the accountant prices."""
    return 1.0


# ----------------------------------------------------------- work counts
def _attn_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (d * h * (dn + dr) + d * r + d * dr + r * h * dn + r * h * dv
            + h * dv * d)


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layers(cfg: dict) -> tuple[int, int]:
    """(dense layers, expert layers)."""
    dense = int(cfg["first_k_dense_replace"])
    return dense, cfg["num_hidden_layers"] - dense


def dense_layer_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (_attn_params(cfg) + cfg["kv_lora_rank"] + 2 * d
            + 3 * d * cfg["intermediate_size"])


def moe_layer_params(cfg: dict) -> int:
    """One expert layer as held here: attention, the shared experts, the
    router (all its outputs) and the held experts."""
    d = cfg["hidden_size"]
    return (_attn_params(cfg) + cfg["kv_lora_rank"] + 2 * d
            + cfg["n_shared_experts"] * _expert_params(cfg)
            + d * router_width(cfg)
            + cfg["n_routed_experts"] * _expert_params(cfg))


def n_params(cfg: dict) -> int:
    """All parameters held here: layers, embedding, head, final norm."""
    dense, moe = _layers(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (dense * dense_layer_params(cfg) + moe * moe_layer_params(cfg)
            + 2 * v * d + d)


def kv_bytes_per_position(cfg: dict) -> int:
    """bf16 latent and rope key of one position over all layers (the
    words the cache must hold; the program pads each row to whole lane
    tiles, which a lower bound leaves out)."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2)


def routed_share(cfg: dict) -> float:
    """Routed experts computed here per token: the held share of the
    top-k, ``top_k * held / router width`` (6 * 8 / 64 = 0.75)."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / router_width(cfg))


def _token_matmul_params(cfg: dict) -> float:
    dense, moe = _layers(cfg)
    d = cfg["hidden_size"]
    per_moe = (_attn_params(cfg) + d * router_width(cfg)
               + (cfg["n_shared_experts"] + routed_share(cfg))
               * _expert_params(cfg))
    return (dense * (_attn_params(cfg) + 3 * d * cfg["intermediate_size"])
            + moe * per_moe)


def _attn_flops_per_position(cfg: dict) -> float:
    """Scores and values of one query against one position, all layers
    (the expanded form: ``nope + rope`` wide keys, ``v`` wide values)."""
    h = cfg["num_attention_heads"]
    width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
             + cfg["v_head_dim"])
    return cfg["num_hidden_layers"] * 2.0 * h * width


def token_flops(cfg: dict, context: int, head: bool) -> float:
    """Model FLOPs of one token that attends ``context`` positions: two
    per weight it uses (routed experts at the held share of its top-k),
    the attention, and the vocabulary head only where logits are
    taken."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (2.0 * _token_matmul_params(cfg)
            + _attn_flops_per_position(cfg) * context
            + (2.0 * d * v if head else 0.0))


def prefill_flops(cfg: dict, start: int, length: int) -> float:
    """Prompt positions ``[start, length)`` computed, logits at the last."""
    if length <= start:
        return 0.0
    n = length - start
    ctx_sum = (start + 1 + length) * n / 2.0       # sum of (p + 1)
    return (token_flops(cfg, 0, False) * n
            + _attn_flops_per_position(cfg) * ctx_sum
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def decode_step_bytes(cfg: dict, live_positions: int, rows: int) -> float:
    """Bytes one decode step needs: every parameter read once at bf16,
    the ``live_positions`` cache entries read and one new entry per row
    written. Every held expert's weights are counted once a step: with
    ``R`` rows routed, a held expert goes unhit with probability
    ``(1 - top_k / router width) ** R`` ((58/64) ** 64 = 0.2% when every
    slot holds a distinct request), the only way the count can exceed
    what a step must read."""
    return (2.0 * n_params(cfg)
            + kv_bytes_per_position(cfg) * (live_positions + rows))


def expert_step_bytes(cfg: dict, rows: int) -> float:
    """Bytes the expert layers' ``moe`` scope needs in one decode step:
    the router, shared and held experts' weights once at bf16, and each
    of ``rows`` rows' activations in and out of every expert layer."""
    _, moe = _layers(cfg)
    d = cfg["hidden_size"]
    weights = (d * router_width(cfg)
               + (cfg["n_shared_experts"] + cfg["n_routed_experts"])
               * _expert_params(cfg))
    return moe * (2.0 * weights + 2 * 2.0 * d * rows)


def latent_step_bytes(cfg: dict, live_positions: int, rows: int) -> float:
    """Bytes the attention's ``mla`` scope needs in one decode step: the
    attention weights of every layer once at bf16, the live latent
    positions read and one new position per row written."""
    return (cfg["num_hidden_layers"] * 2.0 * _attn_params(cfg)
            + kv_bytes_per_position(cfg) * (live_positions + rows))


def monitored_sites(cfg: dict) -> list[tuple[int, int]]:
    """``(K, N)`` of the accountant's two layer-0 sites: the query
    projection and the dense FFN up projection."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return [(d, h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])),
            (d, cfg["intermediate_size"])]


def counter_bytes_decode(cfg: dict, batch: int) -> int:
    """Counter-kernel bytes of one monitored decode step."""
    return counts.counter_bytes_decode(monitored_sites(cfg), batch)


def counter_bytes_prefill(cfg: dict, rows: int) -> int:
    """Counter-kernel bytes of one prompt's accounting."""
    return counts.counter_bytes_prefill(monitored_sites(cfg), rows)
