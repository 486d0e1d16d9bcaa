"""Shared pieces of the traffic generators.

Every kind draws its request SIZES from fixed quantiles of the mix's
distributions, not from the run's seed: each seed then serves the same
multiset of sizes and gaps, in another order, so the seed changes which
tokens and which request lands where, never how much work a run holds.
The seed drives the order (per block), the token contents and which
requests are greedy.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Arrival:
    """One request as the generator hands it to the measured loop."""
    due: float                 # seconds after the generator's start
    prompt: list[int]
    max_new: int
    greedy: bool
    meta: dict = dataclasses.field(default_factory=dict)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent seeded stream; any non-negative whole ``seed``."""
    return np.random.default_rng([int(seed), *stream])


def quantiles(dist: dict, n: int) -> list[int]:
    """``n`` sizes at the midpoint quantiles ``(i + 0.5) / n`` of
    ``dist``, clipped to ``[min, max]`` and rounded up to ``quantum``.

    ``dist`` kinds: ``lognormal`` (``median``, ``sigma``), ``uniform``.
    """
    lo, hi = int(dist["min"]), int(dist["max"])
    q = int(dist.get("quantum", 1))
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(p))
        elif dist["dist"] == "uniform":
            v = lo + p * (hi - lo)
        else:
            raise ValueError(f"unknown size distribution {dist['dist']!r}")
        v = min(max(v, lo), hi)
        v = int(math.ceil(v / q) * q)
        out.append(min(v, hi))
    return out


def exp_gaps(rate: float, n: int) -> list[float]:
    """``n`` exponential inter-arrival gaps at midpoint quantiles,
    rescaled so that their mean is exactly ``1 / rate``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (rate * sum(raw))
    return [g * scale for g in raw]


def balanced_flags(n: int, share: float, rng: np.random.Generator
                   ) -> list[bool]:
    """``round(n * share)`` True flags among ``n``, in seeded order."""
    k = int(round(n * share))
    flags = np.array([True] * k + [False] * (n - k))
    rng.shuffle(flags)
    return [bool(f) for f in flags]


def tokens(rng: np.random.Generator, vocab: int, n: int) -> list[int]:
    """``n`` token ids uniform over the vocabulary."""
    return [int(t) for t in rng.integers(0, vocab, n)]


def pairing(n: int, shape_seed: int) -> list[int]:
    """Fixed permutation pairing the i-th prompt quantile with an output
    quantile (independent lengths), the same for every run."""
    return [int(i) for i in np.random.default_rng(shape_seed).permutation(n)]
