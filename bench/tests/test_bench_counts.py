"""Work counts pinned to hand-worked numbers, read through the dense
family (``bench/families/dense.py``) the configuration files name, and
the peaks table."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401

import pytest  # noqa: E402

from bench.harness import cell, spec  # noqa: E402


def _cfg(name, **kw):
    c = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                    name + ".json"))
    c.update(kw)
    return c


counts, _ = spec.load_family(_cfg("qwen1.5-0.5b")["family"])
assert counts is spec.load_family(_cfg("granite-3-2b")["family"])[0]


def test_qwen_counts():
    c = _cfg("qwen1.5-0.5b")
    # 151936*1024 + 24*(4*1024^2 + 3*1024*2816)
    assert counts.matrix_params(c) == 463_863_808
    assert round(counts.matrix_params(c) / 1e6, 1) == 463.9
    assert counts.kv_bytes_per_position(c) == 24 * 2 * 16 * 64 * 2 == 98_304


def test_granite_counts_published_depth():
    c = _cfg("granite-3-2b", num_hidden_layers=40)
    # 49155*2048 + 40*(2*2048^2 + 2*2048*512 + 3*2048*8192)
    assert counts.matrix_params(c) == 2_533_365_760
    assert round(counts.matrix_params(c) / 1e6, 1) == 2533.4
    assert counts.kv_bytes_per_position(c) == 81_920


def test_all_params_add_norms_and_biases():
    c = _cfg("qwen1.5-0.5b")
    extra = 24 * (2 * 1024 + 3 * 1024) + 1024
    assert counts.n_params(c) == counts.matrix_params(c) + extra


def test_flops_and_bytes():
    c = _cfg("qwen1.5-0.5b")
    mats = 2.0 * (counts.matrix_params(c) - 151936 * 1024)
    assert counts.token_flops(c, 0, False) == mats
    assert counts.token_flops(c, 10, True) == pytest.approx(
        mats + 24 * 4 * 16 * 64 * 10 + 2 * 1024 * 151936)
    # a prompt of 4 positions: contexts 1..4, logits at the last only
    assert counts.prefill_flops(c, 0, 4) == pytest.approx(
        4 * mats + 24 * 4 * 16 * 64 * 10 + 2 * 1024 * 151936)
    assert counts.prefill_flops(c, 4, 4) == 0.0
    assert counts.decode_step_bytes(c, 100, 2) == pytest.approx(
        2 * counts.n_params(c) + 98_304 * 102)


def test_counter_bytes():
    c = _cfg("qwen1.5-0.5b")
    # per site: weights [1024, 256] with 5 rows, 32 single-row inputs
    # [1024, 16] with 6 rows; two sites
    north = 2 * 1024 * 256 + 4 * (5 * 256 + 1024)
    west = 32 * (2 * 1024 * 16 + 4 * (6 * 16 + 1024))
    assert counts.counter_bytes_decode(c, 32) == 2 * (north + west)
    # 300 prompt rows sample to 256
    assert counts.counter_bytes_prefill(c, 300) == 2 * (
        north + 2 * 1024 * 256 + 4 * (6 * 256 + 1024))
    assert counts.counter_bytes_prefill(c, 0) == 0


def test_peaks_table():
    p = cell.peaks_for("TPU v5 lite", spec.BENCH_DIR)
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cell.peaks_for("TPU v9 imaginary", spec.BENCH_DIR)
