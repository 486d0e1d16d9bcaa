"""Closed loop: a fixed number of clients, each submitting its next
request as soon as its last one retires, so the queue never empties.

Parameters: ``clients``; ``cycle`` (how many prompt/output quantile
pairs one client cycles through); ``prompt`` and ``output`` size
distributions; ``greedy_share``, ``temperature``, ``top_k``;
``shape_seed`` (the fixed prompt/output pairing).

Client ``c``'s ``j``-th request takes pair ``(offset[c] + j) mod cycle``
with seeded offsets that are a permutation when ``clients == cycle``: at
every round each pair is in flight exactly once, so what a window holds
does not hang on the seed.
"""
from __future__ import annotations

from bench.traffic.kinds.common import Arrival, balanced_flags, pairing, quantiles, rng_for, tokens


class ClosedLoop:
    open_loop = False

    def __init__(self, params: dict, seed: int, vocab: int, ctx: dict):
        self.p = params
        self.vocab = vocab
        n = int(params["cycle"])
        prompts = quantiles(params["prompt"], n)
        outs = quantiles(params["output"], n)
        pair = pairing(n, int(params.get("shape_seed", 0)))
        self.sizes = [(prompts[i], outs[pair[i]]) for i in range(n)]
        rng = rng_for(seed, 1)
        self.greedy = balanced_flags(n, float(params["greedy_share"]), rng)
        self.offset = [int(x) for x in rng.permutation(
            max(n, int(params["clients"])))[:int(params["clients"])]]
        self.rng = rng_for(seed, 2)
        self.count = [0] * int(params["clients"])

    def _next(self, client: int, now: float) -> Arrival:
        j = self.count[client]
        self.count[client] += 1
        i = (self.offset[client] + j) % len(self.sizes)
        plen, new = self.sizes[i]
        return Arrival(due=now, prompt=tokens(self.rng, self.vocab, plen),
                       max_new=new, greedy=self.greedy[i],
                       meta={"client": client})

    def start(self, duration: float) -> list[Arrival]:
        return [self._next(c, 0.0) for c in range(len(self.count))]

    def on_finish(self, arrival: Arrival, served: list[int],
                  now: float) -> list[Arrival]:
        return [self._next(arrival.meta["client"], now)]

    def prefill_shapes(self, duration: float, page_size: int | None
                       ) -> set[tuple[int, int]]:
        """Every ``(prompt length, reused prefix length)`` a run can
        admit: distinct prompts, no shared prefixes."""
        return {(plen, 0) for plen, _ in self.sizes}
