"""The system under test, built from a configuration file, and the
benchmark-side instruments put on the built engine.

Nothing here changes what the engine computes. The instruments are thin
wrappers set on the engine INSTANCE after it is built: they time the
power accountant's calls on the host, count the prompt tokens each
admission computes, note each decode step's live cache positions, and
keep a seeded sample of the counter kernel's decode outputs for the
reference to check.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.models import lm
from repro.serve import ServeConfig
from repro.serve import power as serve_power
from repro.serve.paging import PagingConfig


def serve_config(cfg: dict, traffic: dict, seed: int) -> ServeConfig:
    e = cfg["engine"]
    common = dict(cache_len=int(e["cache_len"]), seed=seed % (2 ** 31),
                  power_monitor=bool(traffic["power_monitor"]),
                  power_sample_every=int(traffic.get("power_sample_every",
                                                     1)))
    if e["mode"] == "slot":
        return ServeConfig(max_slots=int(e["max_slots"]), **common)
    if e["mode"] == "paged":
        return ServeConfig(paging=PagingConfig(
            page_size=int(e["page_size"]), num_pages=int(e["num_pages"]),
            max_rows=int(e["max_rows"]),
            prefill_chunk=int(e["prefill_chunk"]),
            prefix_cache=bool(e["prefix_cache"])), **common)
    raise ValueError(f"unknown engine mode {e['mode']!r}")


def init_fn(arch):
    return lambda key: lm.init_model(key, arch)


class HostTimer:
    """Host seconds spent inside wrapped calls (outermost call only)."""

    def __init__(self):
        self.seconds = 0.0
        self.depth = 0
        self.on = False

    def wrap(self, fn):
        def timed(*a, **kw):
            if self.depth or not self.on:
                self.depth += 1
                try:
                    return fn(*a, **kw)
                finally:
                    self.depth -= 1
            self.depth += 1
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                self.seconds += time.perf_counter() - t0
                self.depth -= 1
        return timed


class Instruments:
    """Wrappers on one engine; ``on`` gates what is counted."""

    def __init__(self, engine, spans, seed: int, capture: int = 6):
        self.engine = engine
        self.spans = spans
        self.acc_timer = HostTimer()
        self._on = False
        self.prompt_tokens = 0          # prompt positions computed
        self.admissions = 0
        self.prefill_log: dict[int, list[tuple[int, int]]] = {}
        self.prefills: list[tuple[int, int]] = []    # (reused, length)
        self.acct_rows: list[int] = []      # prompt rows priced per call
        self.decode_log: list[tuple[int, int]] = []   # (rows, sum(p + 1))
        self.captured: list[tuple] = []
        self._cap_n = capture
        self._cap_seen = 0
        self._cap_rng = np.random.default_rng([seed, 11])
        self._orig_counters = None
        self._wrap()

    @property
    def on(self) -> bool:
        return self._on

    @on.setter
    def on(self, flag: bool) -> None:
        self._on = self.acc_timer.on = bool(flag)

    # ------------------------------------------------------------ wrapping
    def _wrap(self):
        eng = self.engine
        if eng.accountant is not None:
            acc = eng.accountant
            for name in ("tick", "record_decode", "mark_sampled",
                         "record_prefill", "finish", "begin"):
                setattr(acc, name, self._acc_span(getattr(acc, name)))
            eng._embed = self._acc_span(eng._embed)
            eng._record_prefill_power = self._log_prefill(
                self._acc_span(eng._record_prefill_power))
        eng._decode = self._log_decode(eng._decode)
        if hasattr(eng, "_try_admit"):
            eng._try_admit = self._log_paged_admit(eng._try_admit)
            if eng.prefix is not None:
                eng.prefix.match = self._log_match(eng.prefix.match)
        else:
            eng._admit = self._log_slot_admit(eng._admit)

    def _acc_span(self, fn):
        timed = self.acc_timer.wrap(fn)

        def call(*a, **kw):
            if self.acc_timer.depth:
                return timed(*a, **kw)
            with self.spans.span("accountant"):
                return timed(*a, **kw)
        return call

    def _log_prefill(self, fn):
        def call(slot, toks, lo, length):
            req = self.engine._running[slot]
            self.prefill_log.setdefault(req.uid, []).append(
                (int(lo), int(length)))
            if self.on:
                self.acct_rows.append(int(length) - int(lo))
            return fn(slot, toks, lo, length)
        return call

    def _log_decode(self, fn):
        cache = self.engine.cache

        def call(*a):
            if self.on:
                live = cache.live_slots()
                self.decode_log.append(
                    (len(live), int(sum(int(cache.positions[s]) + 1
                                        for s in live))))
            return fn(*a)
        return call

    def _log_slot_admit(self, fn):
        def call(req):
            if self.on:
                self.prompt_tokens += req.prompt_len
                self.admissions += 1
                self.prefills.append((0, req.prompt_len))
            return fn(req)
        return call

    def _log_paged_admit(self, fn):
        self._matched = 0

        def call(req, retired):
            self._matched = 0
            n = len(req.prompt) + max(len(req.generated) - 1, 0)
            ok = fn(req, retired)
            if ok and self.on:
                self.prompt_tokens += n - self._matched
                self.admissions += 1
                self.prefills.append((self._matched, n))
            return ok
        return call

    def _log_match(self, fn):
        ps = self.engine.scfg.paging.page_size

        def call(seq, max_pages):
            pages = fn(seq, max_pages)
            self._matched = len(pages) * ps
            return pages
        return call

    # ------------------------------------------------- counter kernel sample
    def capture_counters(self):
        """Keep a seeded reservoir sample of the decode counter calls
        made while ``on``: the operands and the integer counters the
        program's kernel returned for them."""
        orig = serve_power._ref_decode_counters
        self._orig_counters = orig

        def call(A, W, mcfg):
            out = orig(A, W, mcfg)
            if self.on:
                self._cap_seen += 1
                item = (A, W, out)
                if len(self.captured) < self._cap_n:
                    self.captured.append(item)
                else:
                    j = int(self._cap_rng.integers(0, self._cap_seen))
                    if j < self._cap_n:
                        self.captured[j] = item
            return out

        serve_power._ref_decode_counters = call

    def release(self):
        if self._orig_counters is not None:
            serve_power._ref_decode_counters = self._orig_counters
            self._orig_counters = None


def free_engine(engine) -> None:
    """Drop the engine's device state (the KV cache) so that the
    reference runs in the memory the cache held."""
    states = engine.cache.states
    engine.cache.states = None
    for leaf in jax.tree.leaves(states):
        leaf.delete()
