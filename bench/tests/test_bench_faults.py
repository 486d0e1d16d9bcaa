"""The whole harness on the CPU at a tiny size, past the check for a
chip: a sound run comes out correct, and a run with the timed path
broken underneath comes out not correct -- a served token altered where
it is sampled, the counter kernel's counts altered, an accountant that
drops decode steps."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401
import _tiny  # noqa: E402

import pytest  # noqa: E402

CELL = "qwen05b.offline.monitored"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("checkout"))


#: seconds of window in which each tiny cell finishes greedy requests
WINDOW = {CELL: 1.0, "qwen05b.offline.plain": 1.0,
          "granite2b.sessions.monitored": 3.0}


@pytest.mark.parametrize("cell", list(WINDOW))
def test_sound_cell_is_correct(root, cell):
    r = _tiny.run(cell, root, seconds=WINDOW[cell])
    assert r["correct"], r["checks"]
    assert all(c["value"] is not None for c in r["checks"].values())


def test_sound_run_is_correct(root):
    r = _tiny.run(CELL, root)
    assert r["correct"], r["checks"]
    cfg = _tiny.tiny_cell(CELL).config
    assert set(r["checks"]) == set(cfg["limits"])
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", list(WINDOW))
def test_altered_token_is_caught(root, monkeypatch, cell):
    from repro.serve import sampling
    orig = sampling.sample_tokens

    def altered(key, logits, temperature, top_k):
        return (orig(key, logits, temperature, top_k) + 1) % logits.shape[-1]
    monkeypatch.setattr(sampling, "sample_tokens", altered)
    r = _tiny.run(cell, root, seconds=WINDOW[cell])
    assert not r["correct"]
    assert any(c["value"] is not None and c["value"] > c["limit"]
               for n, c in r["checks"].items() if n.startswith("logit_gap"))


def test_altered_counters_are_caught(root, monkeypatch):
    from repro.serve import power
    orig = power._ref_decode_counters

    def altered(A, W, mcfg):
        wc, wz, nc, nz = orig(A, W, mcfg)
        return wc.at[:, 0, 0].add(1), wz, nc, nz
    monkeypatch.setattr(power, "_ref_decode_counters", altered)
    r = _tiny.run(CELL, root)
    assert not r["correct"]
    assert r["checks"]["counter_mismatch"]["value"] > 0
    assert r["checks"]["energy_rel_err"]["value"] > \
        r["checks"]["energy_rel_err"]["limit"]


def test_dropped_decode_accounting_is_caught(root, monkeypatch):
    from repro.serve import power
    orig = power.PowerAccountant.record_decode
    calls = {"n": 0}

    def dropping(self, slots, acts, weight, site):
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            return None
        return orig(self, slots, acts, weight, site)
    monkeypatch.setattr(power.PowerAccountant, "record_decode", dropping)
    r = _tiny.run(CELL, root)
    assert not r["correct"]
    assert r["checks"]["energy_rel_err"]["value"] > \
        r["checks"]["energy_rel_err"]["limit"]
