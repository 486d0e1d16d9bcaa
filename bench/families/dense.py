"""Dense decoders: attention with grouped key/value heads, rotary
positions, RMSNorm and a gated SiLU MLP in every layer, the head tied or
untied (qwen1.5, granite-3).

The program side of the family: the configuration file's published keys
mapped onto the program, its parameter tree laid out for the float32
reference (``bench/reference/dense.py``), the two sites the power
accountant prices, and the work counts the per-layer metrics rest on.

Every count is a LOWER bound on the work an honest implementation does,
so that a share of a peak built on it cannot pass 100% unless the time
is wrong: parameters read once per step at bf16, only live cache
positions, only the words the counter pass must read and write.
"""
from __future__ import annotations

from bench import counts
from repro.configs import ARCHS


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file: the registry
    entry named by ``arch`` with every size taken from the file, so the
    file is what runs."""
    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is fixed at 1e-6; "
                         "the configuration file must state what runs")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kw = dict(
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=bool(cfg.get("qkv_bias") or cfg.get("attention_bias")),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        emb_mult=float(cfg.get("embedding_multiplier", 1.0)),
        resid_mult=float(cfg.get("residual_multiplier", 1.0)),
        attn_scale=float(cfg.get("attention_multiplier", 0.0)),
        logit_mult=1.0 / float(cfg.get("logits_scaling", 1.0)),
        head_dim=int(cfg.get("head_dim", 0)),
        pattern=("attn+mlp",), compute_dtype="bfloat16")
    if "attn_block_k" in cfg:
        kw["attn_block_k"] = int(cfg["attn_block_k"])
    return ARCHS[cfg["arch"]].with_(**kw)


def plain_weights(params, cfg: dict) -> dict:
    """The program's tree as the reference's plain layout: ``embed``,
    ``final_norm`` and per-layer arrays stacked on axis 0 (the program
    scans one block per group, ``pattern == ("attn+mlp",)``)."""
    val = lambda p: p.value
    blk = params["stack"]["groups"]["b0"]
    mix, ffn = blk["mixer"], blk["ffn"]
    layers = {name: val(mix[name]) for name in mix}
    layers.update({name: val(ffn[name]) for name in ffn})
    layers["norm1"] = val(blk["norm1"]["scale"])
    layers["norm2"] = val(blk["norm2"]["scale"])
    return {"embed": val(params["embed"]),
            "final_norm": val(params["final_norm"]["scale"]),
            "layers": layers}


def monitor_sites(plain: dict) -> list:
    """The accountant's two sites, layer 0's query projection and MLP up
    projection, from the plain layout."""
    layers = plain["layers"]
    return [layers["wq"][0], layers["up"][0]]


def embed_multiplier(cfg: dict) -> float:
    """The scale of the embedded tokens, the activations the accountant
    prices at both sites."""
    return float(cfg.get("embedding_multiplier", 1.0))


# ----------------------------------------------------------- work counts
def _dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return (cfg["num_hidden_layers"], d, h, kv, hd,
            cfg["intermediate_size"], cfg["vocab_size"])


def layer_params(cfg: dict) -> int:
    """Parameters of one decoder layer."""
    _, d, h, kv, hd, f, _ = _dims(cfg)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if cfg.get("qkv_bias") or cfg.get("attention_bias"):
        attn += h * hd + 2 * kv * hd
    return attn + 3 * d * f + 2 * d


def matrix_params(cfg: dict) -> int:
    """Parameters of the weight matrices and the embedding (norm scales
    and biases left out)."""
    n_layers, d, h, kv, hd, f, v = _dims(cfg)
    head = 0 if cfg.get("tie_word_embeddings") else v * d
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return n_layers * per_layer + v * d + head


def n_params(cfg: dict) -> int:
    """All parameters: layers, embedding (the head too when untied) and
    the final norm."""
    n_layers, d, _, _, _, _, v = _dims(cfg)
    head = 0 if cfg.get("tie_word_embeddings") else v * d
    return n_layers * layer_params(cfg) + v * d + head + d


def kv_bytes_per_position(cfg: dict) -> int:
    """bf16 keys and values of one position over all layers."""
    n_layers, _, _, kv, hd, _, _ = _dims(cfg)
    return n_layers * 2 * kv * hd * 2


def token_flops(cfg: dict, context: int, head: bool) -> float:
    """Model FLOPs of one token that attends ``context`` positions:
    two per weight of the layers' matrices, the attention scores and
    values, and the vocabulary head only where logits are taken."""
    n_layers, d, h, kv, hd, f, v = _dims(cfg)
    mats = n_layers * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f)
    attn = n_layers * 4 * h * hd * context
    return 2.0 * mats + attn + (2.0 * d * v if head else 0.0)


def prefill_flops(cfg: dict, start: int, length: int) -> float:
    """Prompt positions ``[start, length)`` computed, logits at the last."""
    if length <= start:
        return 0.0
    n_layers, d, h, kv, hd, f, v = _dims(cfg)
    n = length - start
    ctx_sum = (start + 1 + length) * n / 2.0       # sum of (p + 1)
    return (token_flops(cfg, 0, False) * n + n_layers * 4 * h * hd * ctx_sum
            + 2.0 * d * v)


def decode_step_bytes(cfg: dict, live_positions: int, rows: int) -> float:
    """Bytes one decode step needs: every parameter read once at bf16
    (the tied embedding counted once, as the head), the ``live_positions``
    cache entries read and one new entry per row written."""
    return (2.0 * n_params(cfg)
            + kv_bytes_per_position(cfg) * (live_positions + rows))


def monitored_sites(cfg: dict) -> list[tuple[int, int]]:
    """``(K, N)`` of the accountant's two layer-0 sites: the query
    projection and the MLP up projection."""
    _, d, h, _, hd, f, _ = _dims(cfg)
    return [(d, h * hd), (d, f)]


def counter_bytes_decode(cfg: dict, batch: int) -> int:
    """Counter-kernel bytes of one monitored decode step."""
    return counts.counter_bytes_decode(monitored_sites(cfg), batch)


def counter_bytes_prefill(cfg: dict, rows: int) -> int:
    """Counter-kernel bytes of one prompt's accounting."""
    return counts.counter_bytes_prefill(monitored_sites(cfg), rows)
