"""What decides ``correct``: the served tokens, the accountant's integer
counters and its per-request energies, each against a plain reference
that takes only the benchmark's weights and the requests' tokens. The
served tokens are judged by the configuration's family's float32
reference (``bench/reference/<family>.py``), the energies at the sites
the family names (``monitor_sites``).

Numbers read (each compared where the configuration file gives it a
limit):

* ``logit_gap`` -- over a seeded sample of greedy requests finished in
  the window (the longest among them): the widest gap, in logit standard
  deviations, by which a served token's logit lies below the best logit
  of the float32 reference run over the prompt and the served tokens.
* ``logit_gap_mean`` -- the mean of those gaps over every served token
  of the sample.
* ``counter_mismatch`` (monitored traffic) -- integer counters of a
  seeded sample of the window's decode counter passes that differ from
  the reference's count of the same operand streams. Exact: limit 0.
* ``energy_rel_err`` (monitored traffic) -- the sampled requests' energy
  per design (total and streaming) against the reference's, relative.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import accountant as ref_acc

SAMPLE = 8


def sample_tracks(tracks, w0: float, w1: float, seed: int) -> list:
    """Greedy requests finished inside the window and never preempted:
    the longest, then a seeded sample of the rest, ``SAMPLE`` in all."""
    done = [t for t in tracks if t.arrival.greedy and w0 < t.finished <= w1
            and t.req.preemptions == 0 and len(t.req.generated) > 1]
    if not done:
        return []
    done.sort(key=lambda t: t.req.uid)
    longest = max(done, key=lambda t: t.req.prompt_len + len(t.req.generated))
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([seed, 13])
    pick = rng.permutation(len(rest))[:SAMPLE - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def _bits(x) -> np.ndarray:
    """bf16 words of an array, rounded on the host."""
    return np.asarray(x, np.float32).astype(jnp.bfloat16).view(np.uint16)


@jax.jit
def _rows(embed, idx):
    return embed[idx]


def _embed_bits(embed, tokens, mult: float, pad_to: int) -> np.ndarray:
    """bf16 words of the embedded tokens: rows gathered on the chip at
    one fixed shape, then rounded to bf16 and scaled (a bf16 product)
    on the host."""
    idx = np.zeros(pad_to, np.int32)
    idx[:len(tokens)] = tokens
    x = np.asarray(_rows(embed, jnp.asarray(idx)))[:len(tokens)]
    x = x.astype(jnp.bfloat16).astype(np.float32) * np.float32(mult)
    return x.astype(jnp.bfloat16).view(np.uint16)


def gap_readings(reference, weights, cfg: dict, sample, pad_to: int,
                 control: bool = False) -> dict:
    """``{"served": {"logit_gap": widest, "logit_gap_mean": mean}}`` over
    every served token of the sample, judged by the ``reference``
    module; with ``control`` the same for the tokens the float8 control
    (``"control"``) and the bfloat16 witness (``"witness"``) put first
    at the same positions."""
    per: dict[str, list] = {}
    for t in sample:
        seq = t.req.prompt + t.req.generated
        g = reference.token_gaps(weights, cfg, seq, t.req.prompt_len,
                                 pad_to, control=control)
        for k, v in g.items():
            per.setdefault(k, []).append(v)
    out = {}
    for k, vs in per.items():
        v = np.concatenate(vs)
        out[k] = {"logit_gap": float(v.max()),
                  "logit_gap_mean": float(v.mean())}
    return out


def energy_rel_err(family, weights, cfg: dict, sample, prefill_log,
                   pad_to: int) -> float:
    sites = [ref_acc.SiteRef(_bits(w)) for w in family.monitor_sites(weights)]
    mult = family.embed_multiplier(cfg)
    worst = 0.0
    for t in sample:
        req = t.req
        (lo, length), = prefill_log[req.uid]
        prompt = _embed_bits(weights["embed"], req.prompt[lo:length], mult,
                             pad_to)
        dec = _embed_bits(weights["embed"], req.generated[:-1], mult, pad_to)
        ref = ref_acc.request_energy(sites, prompt, dec)
        for design in ("baseline", "proposed"):
            for comp in ("total", "streaming"):
                r = ref[design][comp]
                p = float(req.power.energy[design][comp])
                worst = max(worst, abs(p - r) / abs(r))
    return worst


def counter_mismatch(captured) -> int:
    bad = 0
    for a, w, out in captured:
        mine = ref_acc.decode_counters(_bits(a), _bits(w))
        for x, y in zip(mine, out):
            y = np.asarray(y)
            bad += int(np.sum(np.asarray(x, np.int64) != y.astype(np.int64)))
    return bad


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number the configuration gives a limit against it: ``(all
    within, {name: {value, limit}})``. A number without a limit is a
    reading, not compared; a compared number that is missing or not
    finite fails, and so does a run that compares nothing."""
    out, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            continue
        limit = limits[name]
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok and bool(out), out
