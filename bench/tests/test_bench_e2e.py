"""End-to-end arithmetic on synthetic timelines: a stall injected into
the window must lower out_tok_s and raise itl_p95_ms, and TTFT counts
from when a request was due."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401

import math  # noqa: E402

import pytest  # noqa: E402

from bench.harness import e2e  # noqa: E402


def timeline(stall_at=None, stall=0.0, rows=8, step=0.02, t_end=10.0):
    """``rows`` requests decoding in lock step from t=0, one token per
    step, with an optional stall of ``stall`` seconds at ``stall_at``."""
    stamps, t = [], 0.0
    while t < t_end:
        t += step
        if stall_at is not None and t >= stall_at and stall:
            t += stall
            stall = 0.0
        stamps.append(t)
    return [(0.0, list(stamps)) for _ in range(rows)]


def test_steady_timeline():
    reqs = timeline()
    assert e2e.out_tok_s(reqs, 1.0, 9.0) == pytest.approx(8 / 0.02, rel=0.01)
    assert e2e.itl_p95_ms(reqs, 1.0, 9.0) == pytest.approx(20.0, rel=1e-6)


def test_stall_moves_throughput_and_tail():
    base = timeline()
    # stalls of 50 ms every 10 steps: 10% of the gaps
    stalled = [(0.0, [t + 0.05 * int(t / 0.2) for t in st])
               for _, st in base]
    assert e2e.out_tok_s(stalled, 1.0, 9.0) < 0.9 * e2e.out_tok_s(
        base, 1.0, 9.0)
    assert e2e.itl_p95_ms(stalled, 1.0, 9.0) > 20.0 * 1.5
    one = timeline(stall_at=5.0, stall=1.0)
    assert e2e.out_tok_s(one, 1.0, 9.0) < e2e.out_tok_s(base, 1.0, 9.0)
    assert max(e2e.gaps_in(one, 1.0, 9.0)) == pytest.approx(1.02)


def test_ttft_counts_from_due():
    reqs = [(1.0, [1.5, 1.6]), (2.0, [2.1]), (3.0, [4.0]), (20.0, [20.1])]
    assert sorted(e2e.ttfts(reqs, 0.0, 10.0)) == pytest.approx(
        [0.1, 0.5, 1.0])
    late = reqs + [(5.0, [])]
    assert math.isinf(max(e2e.ttfts(late, 0.0, 10.0)))
    assert e2e.ttft_p90_ms(reqs, 0.0, 10.0) == pytest.approx(
        1e3 * (0.5 + 0.8 * 0.5))


def test_percentile_interpolates():
    assert e2e.percentile([1, 2, 3, 4, 5], 50) == 3
    assert e2e.percentile([1, 2], 95) == pytest.approx(1.95)
    assert math.isnan(e2e.percentile([], 90))
