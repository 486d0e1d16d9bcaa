"""The power-counter kernel's bytes, which no architecture changes:
given the ``(K, N)`` of the sites the accountant prices (each family's
``monitored_sites``), the words a counter pass must read and write.

A LOWER bound on what an honest kernel moves (padding excluded), so that
a share of the roofline built on it cannot pass 100% unless the time is
wrong. The model's own counts live with its family, ``bench/families/``.
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


def _sub(n: int, cap: int) -> int:
    return min(n, cap)


def _pad(n: int) -> int:
    return -(-n // MON_GEOM) * MON_GEOM


def _edge_bytes(t: int, lanes: int, rows: int, batch: int = 1) -> int:
    """uint16 words read plus int32 per-lane counters and per-cycle zero
    counts written by one counter pass over a ``[t, lanes]`` stream."""
    return batch * (2 * t * lanes + 4 * (rows * lanes + t))


def counter_bytes_decode(sites, batch: int) -> int:
    """Counter-kernel bytes of one monitored decode step: per ``(K, N)``
    site the weight stream and the ``batch`` single-row input streams."""
    out = 0
    for k, n in sites:
        ks = _sub(k, MON_MAX_DEPTH)
        out += _edge_bytes(ks, _pad(_sub(n, MON_MAX_COLS)), NORTH_ROWS)
        out += _edge_bytes(ks, MON_GEOM, WEST_ROWS, batch)
    return out


def counter_bytes_prefill(sites, rows: int) -> int:
    """Counter-kernel bytes of one prompt's accounting (``rows`` prompt
    rows computed): per ``(K, N)`` site the weight stream and the sampled
    rows' input stream, rows sampled to the power of two at most 256."""
    if rows < 1:
        return 0
    ms = 1 << (min(rows, MON_MAX_ROWS).bit_length() - 1)
    out = 0
    for k, n in sites:
        ks = _sub(k, MON_MAX_DEPTH)
        out += _edge_bytes(ks, _pad(_sub(n, MON_MAX_COLS)), NORTH_ROWS)
        out += _edge_bytes(ks, _pad(ms), WEST_ROWS)
    return out
