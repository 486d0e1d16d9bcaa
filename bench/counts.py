"""Work counts: parameters, model FLOPs, the bytes a decode step needs,
and the power-counter kernel's bytes, all from a configuration file's
published keys (dense attention + gated-MLP family).

Every count is a LOWER bound on the work an honest implementation does,
so that a share of a peak built on it cannot pass 100% unless the time
is wrong: parameters read once per step at bf16, only live cache
positions, only the words the counter pass must read and write.
"""
from __future__ import annotations

#: sampling caps and array geometry of the accountant's monitor (the
#: program's default ``MonitorConfig``): stream length K, weight columns
#: N, prompt rows M, and the 16x16 systolic array it models
MON_MAX_DEPTH = 1024
MON_MAX_COLS = 256
MON_MAX_ROWS = 256
MON_GEOM = 16
#: counter rows per lane: the west (input) edge carries raw, mantissa,
#: zeros and the three zero-gated rows; the north (weight) edge raw,
#: mantissa, zeros and mantissa-BIC data + invert-line rows
WEST_ROWS = 6
NORTH_ROWS = 5


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


def _sub(n: int, cap: int) -> int:
    return min(n, cap)


def _pad(n: int) -> int:
    return -(-n // MON_GEOM) * MON_GEOM


def monitored_sites(cfg: dict) -> list[tuple[int, int]]:
    """``(K, N)`` of the accountant's two layer-0 sites: the query
    projection and the MLP up projection."""
    _, d, h, _, hd, f, _ = _dims(cfg)
    return [(d, h * hd), (d, f)]


def _edge_bytes(t: int, lanes: int, rows: int, batch: int = 1) -> int:
    """uint16 words read plus int32 per-lane counters and per-cycle zero
    counts written by one counter pass over a ``[t, lanes]`` stream."""
    return batch * (2 * t * lanes + 4 * (rows * lanes + t))


def counter_bytes_decode(cfg: dict, batch: int) -> int:
    """Counter-kernel bytes of one monitored decode step: per site the
    weight stream and the ``batch`` single-row input streams."""
    out = 0
    for k, n in monitored_sites(cfg):
        ks = _sub(k, MON_MAX_DEPTH)
        out += _edge_bytes(ks, _pad(_sub(n, MON_MAX_COLS)), NORTH_ROWS)
        out += _edge_bytes(ks, MON_GEOM, WEST_ROWS, batch)
    return out


def counter_bytes_prefill(cfg: dict, rows: int) -> int:
    """Counter-kernel bytes of one prompt's accounting (``rows`` prompt
    rows computed): per site the weight stream and the sampled rows'
    input stream, rows sampled to the power of two at most 256."""
    if rows < 1:
        return 0
    ms = 1 << (min(rows, MON_MAX_ROWS).bit_length() - 1)
    out = 0
    for k, n in monitored_sites(cfg):
        ks = _sub(k, MON_MAX_DEPTH)
        out += _edge_bytes(ks, _pad(_sub(n, MON_MAX_COLS)), NORTH_ROWS)
        out += _edge_bytes(ks, _pad(ms), WEST_ROWS)
    return out
