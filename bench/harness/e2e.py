"""End-to-end arithmetic over token stamps: plain functions of host
times, so a synthetic timeline can test them.

A request is ``(due, stamps)``: when it was due and the host time of each
of its tokens. The window is ``[w0, w1]``.

* ``out_tok_s``  -- tokens stamped inside the window over its length.
* ``itl_p95_ms`` -- 95th percentile over every gap between successive
  tokens of a request whose later token is stamped inside the window.
* ``ttft_p90_ms`` -- 90th percentile over every request DUE inside the
  window of (first token - due); a stall before submission counts.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated ``q``-th percentile (0..100) of ``values``."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tokens_in(requests, w0: float, w1: float) -> int:
    return sum(1 for _, st in requests for t in st if w0 < t <= w1)


def out_tok_s(requests, w0: float, w1: float) -> float:
    return tokens_in(requests, w0, w1) / (w1 - w0)


def gaps_in(requests, w0: float, w1: float) -> list[float]:
    return [b - a for _, st in requests for a, b in zip(st, st[1:])
            if w0 < b <= w1]


def itl_p95_ms(requests, w0: float, w1: float) -> float:
    return 1e3 * percentile(gaps_in(requests, w0, w1), 95)


def ttfts(requests, w0: float, w1: float) -> list[float]:
    """First-token delays of requests due in ``[w0, w1)``; one never
    served counts as infinitely late."""
    return [(st[0] - due) if st else math.inf
            for due, st in requests if w0 <= due < w1]


def ttft_p90_ms(requests, w0: float, w1: float) -> float:
    return 1e3 * percentile(ttfts(requests, w0, w1), 90)
