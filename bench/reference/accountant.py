"""Plain numpy reference of the per-request streaming-power accounting:
the paper's output-stationary 16x16 systolic array, fed one request's
operand streams, counted bit by bit and priced for the two designs the
accountant reports (``baseline``: no coding; ``proposed``: zero-value
clock gating on the input edge, bus-invert coding of the weight
mantissas on the weight edge).

Written from the model's description: streams are bf16 words, a
stream's first word toggles against an all-zero bus, a zero word (sign
ignored) holds the gated register, BIC inverts the 7 mantissa bits when
more than half of them would toggle. Sampling caps, scale-back factors
and energy constants are those the accountant states for its default
monitor. Everything is float64 or exact integers.
"""
from __future__ import annotations

import numpy as np

R = C = 16                      # array rows / columns
MAX_DEPTH, MAX_COLS, MAX_ROWS = 1024, 256, 256
MANT = 0x007F

# energy model (fJ)
E_REG_BIT, E_WIRE_BIT, E_CLK_BIT = 6.0, 18.0, 1.8
E_MULT, E_ADD = 450.0, 400.0
MULT_STATIC_FRAC, MULT_PP_FRAC, ADD_STATIC_FRAC = 0.01, 0.80, 0.01
ACC_TOGGLE_BITS = UNLOAD_TOGGLE_BITS = 12.8
REG_BITS_PER_PE, GATEABLE_BITS_PER_PE = 72.0, 42.0
E_ZDET, E_ENC, E_DEC_XOR_BIT = 8.0, 60.0, 0.8
MANT_FRAC, MANT_BITS, BUS_BITS = 7.0 / 16.0, 7.0, 16.0
E_CTRL_CYCLE, CLK_LEAF_FRAC = 160.0, 0.18
E_STREAM_BIT = E_REG_BIT + E_WIRE_BIT
COMPONENTS = ("streaming", "clock", "control", "mult", "add", "acc",
              "unload", "overhead")

_POP = np.array([bin(i).count("1") for i in range(1 << 16)], np.int64)


def _pop(x):
    return _POP[x.astype(np.int64) & 0xFFFF]


def _prev(s, init=0):
    return np.concatenate([np.full_like(s[:1], init), s[:-1]], axis=0)


def _held(s):
    t = np.arange(s.shape[0])[:, None]
    idx = np.maximum.accumulate(np.where((s & 0x7FFF) != 0, t, -1), axis=0)
    return np.where(idx >= 0, np.take_along_axis(s, np.maximum(idx, 0), 0),
                    0).astype(s.dtype)


def _bic(s, mask=MANT):
    """Encoded-bus data toggles and invert-line toggles per lane."""
    width = bin(mask).count("1")
    prev_tx = np.zeros(s.shape[1], np.int64)
    prev_inv = np.zeros(s.shape[1], bool)
    data = np.zeros(s.shape[1], np.int64)
    invt = np.zeros(s.shape[1], np.int64)
    for x in s.astype(np.int64):
        inv = _pop((x ^ prev_tx) & mask) * 2 > width
        tx = np.where(inv, x ^ mask, x)
        data += _pop(tx ^ prev_tx)
        invt += inv != prev_inv
        prev_tx, prev_inv = tx, inv
    return data, invt


def west_counters(s) -> dict:
    """Per-lane counters of an input stream ``uint16[T, L]``."""
    z = (s & 0x7FFF) == 0
    h = _held(s)
    return {"raw": _pop(s ^ _prev(s)).sum(0),
            "mant_raw": _pop((s ^ _prev(s)) & MANT).sum(0),
            "zeros": z.sum(0),
            "zvg": _pop(h ^ _prev(h)).sum(0),
            "mant_zvg": _pop((h ^ _prev(h)) & MANT).sum(0),
            "iszero": (z ^ _prev(z, False)).sum(0)}


def north_counters(s) -> dict:
    """Per-lane counters of a weight stream ``uint16[T, L]``."""
    z = (s & 0x7FFF) == 0
    data, inv = _bic(s)
    return {"raw": _pop(s ^ _prev(s)).sum(0),
            "mant_raw": _pop((s ^ _prev(s)) & MANT).sum(0),
            "zeros": z.sum(0),
            "bic/007f/data": data, "bic/007f/inv": inv}


WEST_ROWS = ("raw", "mant_raw", "zeros", "zvg", "mant_zvg", "iszero")
NORTH_ROWS = ("raw", "mant_raw", "zeros", "bic/007f/data", "bic/007f/inv")


def sub_idx(n: int, cap: int) -> np.ndarray:
    """The accountant's evenly strided sample of ``cap`` of ``n``
    indices (``floor(i * n / cap)`` in float32), or all of them."""
    if n <= cap:
        return np.arange(n)
    return np.floor(np.arange(cap, dtype=np.float32)
                    * np.float32(n / cap)).astype(np.int64)


def _pad_lanes(s, mult):
    return np.pad(s, ((0, 0), (0, (-s.shape[1]) % mult)))


def _price(w: dict, n: dict, az, nz, m: int, k: int, ns: int) -> dict:
    """Energies (fJ) of both designs from the two edges' lane sums."""
    mp, np_ = m + (-m) % R, ns + (-ns) % C
    tm, tn = mp // R, np_ // C
    zeros, overlap = float(az.sum()), float((az * nz).sum())
    pe_slots = float(mp * np_ * k)
    active = 1.0 - zeros / (mp * k)
    nonzero = pe_slots - np_ * zeros
    cycles = max(float(tm * tn * (k + R + C - 2)), 1.0)
    unload = tm * tn * C * R * (R + 1) / 2.0
    out = {}
    for name in ("baseline", "proposed"):
        prop = name == "proposed"
        w_tog = (w["zvg"] + w["iszero"]) if prop else w["raw"]
        n_tog = (n["bic/007f/data"] + n["bic/007f/inv"]) if prop else n["raw"]
        h_tog, v_tog = tn * C * w_tog, tm * R * n_tog
        a_tog = np_ * (w["zvg"] if prop else w["raw"])
        a_mant = np_ * (w["mant_zvg"] if prop else w["mant_raw"])
        b_tog = active * mp * n["raw"]
        b_mant = active * mp * n["mant_raw"]
        gated = np_ * zeros if prop else 0.0
        overhead = 0.0
        if prop:
            overhead = (E_ZDET * tn * mp * k + E_ENC * tm * np_ * k
                        + E_DEC_XOR_BIT * MANT_FRAC * b_tog)
        n_pe = R * C
        dyn = (1.0 - MULT_STATIC_FRAC) * E_MULT
        e = {"streaming": E_STREAM_BIT * (h_tog + v_tog),
             "clock": (E_CLK_BIT * REG_BITS_PER_PE * n_pe * cycles
                       - E_CLK_BIT * GATEABLE_BITS_PER_PE * CLK_LEAF_FRAC
                       * gated),
             "control": E_CTRL_CYCLE * n_pe * cycles,
             "mult": (MULT_STATIC_FRAC * E_MULT * (pe_slots - gated)
                      + MULT_PP_FRAC * dyn * (a_mant + b_mant) / MANT_BITS
                      + (1 - MULT_PP_FRAC) * dyn * (a_tog + b_tog)
                      / BUS_BITS),
             "add": E_ADD * (ADD_STATIC_FRAC * (pe_slots - gated)
                             + (1 - ADD_STATIC_FRAC) * nonzero),
             "acc": E_REG_BIT * ACC_TOGGLE_BITS * nonzero,
             "unload": E_STREAM_BIT * UNLOAD_TOGGLE_BITS * unload,
             "overhead": overhead}
        e["total"] = sum(e[c] for c in COMPONENTS)
        out[name] = e
    return out


def _sums(counters: dict, rows) -> dict:
    return {r: float(np.asarray(counters[r]).sum()) for r in rows}


class SiteRef:
    """One monitored site: its weight ``[K, N]`` (bf16 words) sampled
    and counted once."""

    def __init__(self, w_bits):
        k, n = w_bits.shape
        self.k, self.n = k, n
        self.ki = sub_idx(k, MAX_DEPTH)
        wb = w_bits[self.ki][:, sub_idx(n, MAX_COLS)]
        self.ks, self.ns = wb.shape
        north = _pad_lanes(wb, C)
        self.north = north_counters(north)
        self.nz = ((north & 0x7FFF) == 0).sum(1)
        self.n_sums = _sums(self.north, NORTH_ROWS)

    def prefill(self, a_bits) -> dict:
        """Energies of one prompt's rows ``[m, K]`` at this site."""
        m = a_bits.shape[0]
        ms = 1 << (min(m, MAX_ROWS).bit_length() - 1)
        a = a_bits[sub_idx(m, ms)][:, self.ki]
        west = _pad_lanes(a.T, R)
        az = ((west & 0x7FFF) == 0).sum(1)
        e = _price(_sums(west_counters(west), WEST_ROWS), self.n_sums,
                   az, self.nz, ms, self.ks, self.ns)
        f = (m / ms) * (self.k / self.ks) * (self.n / self.ns)
        return {d: {c: v * f for c, v in comps.items()}
                for d, comps in e.items()}

    def decode(self, rows_bits) -> dict:
        """Summed energies of single-row decode steps ``[steps, K]``."""
        a = rows_bits[:, self.ki]                          # [steps, Ks]
        lane = west_counters(a.T)                          # per step
        zero = west_counters(np.zeros((self.ks, 1), a.dtype))
        f = (self.k / self.ks) * (self.n / self.ns)
        total: dict = {}
        for j in range(a.shape[0]):
            w = {r: float(lane[r][j]) + (R - 1) * float(zero[r][0])
                 for r in WEST_ROWS}
            az = ((a[j] & 0x7FFF) == 0).astype(np.int64) + (R - 1)
            e = _price(w, self.n_sums, az, self.nz, 1, self.ks, self.ns)
            for d, comps in e.items():
                acc = total.setdefault(d, dict.fromkeys(comps, 0.0))
                for c, v in comps.items():
                    acc[c] += v * f
        return total


def request_energy(sites: list[SiteRef], prompt_bits, decode_bits) -> dict:
    """``{design: {component: fJ}}`` of one request: its computed prompt
    rows at every site, then every decode step's input row."""
    total: dict = {}
    for site in sites:
        parts = [site.prefill(prompt_bits)] if len(prompt_bits) else []
        if len(decode_bits):
            parts.append(site.decode(decode_bits))
        for part in parts:
            for d, comps in part.items():
                acc = total.setdefault(d, dict.fromkeys(comps, 0.0))
                for c, v in comps.items():
                    acc[c] += v
    return total


def decode_counters(a_bits, w_bits) -> tuple:
    """The integer counters of one decode step's counter pass, in the
    layout the accountant's kernel returns: per batch row the input
    stream's ``[6, R]`` table and ``[Ks]`` zero counts, and the weight
    stream's ``[5, Np]`` table and ``[Ks]`` zero counts."""
    site = SiteRef(w_bits)
    a = a_bits[:, site.ki]
    wc, wz = [], []
    for row in a:
        west = np.zeros((site.ks, R), a.dtype)
        west[:, 0] = row
        c = west_counters(west)
        wc.append(np.stack([c[r] for r in WEST_ROWS]))
        wz.append(((west & 0x7FFF) == 0).sum(1))
    nc = np.stack([site.north[r] for r in NORTH_ROWS])
    return np.stack(wc), np.stack(wz), nc, site.nz
