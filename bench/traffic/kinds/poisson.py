"""Open loop of single-turn requests with Poisson arrivals.

Parameters: ``rate`` (requests/s); ``block`` (requests per block: each
block holds every prompt/output quantile and every gap quantile once,
in seeded order); ``prompt`` and ``output`` size distributions;
``greedy_share``, ``temperature``, ``top_k``; ``shape_seed``.

``prompt`` may instead be ``{"dist": "turns_of", "traffic": <mix>}``:
the whole-turn prompt lengths of a ``sessions`` mix, so the two cells
see the same lengths and differ only in what they share.
"""
from __future__ import annotations

from bench.traffic.kinds.common import (Arrival, balanced_flags, exp_gaps, pairing, quantiles,
                     rng_for, tokens)


class Poisson:
    open_loop = True

    def __init__(self, params: dict, seed: int, vocab: int, ctx: dict):
        self.p = params
        self.vocab = vocab
        n = int(params["block"])
        if params["prompt"]["dist"] == "turns_of":
            other = ctx["load_traffic"](params["prompt"]["traffic"])
            kind = ctx["load_kind"](other["kind"])
            prompts = kind.turn_prompt_lengths(other, n, ctx)
        else:
            prompts = quantiles(params["prompt"], n)
        outs = quantiles(params["output"], n)
        pair = pairing(n, int(params.get("shape_seed", 0)))
        self.sizes = [(prompts[i], outs[pair[i]]) for i in range(n)]
        self.gaps = exp_gaps(float(params["rate"]), n)
        self.seed = seed

    def start(self, duration: float) -> list[Arrival]:
        """The whole schedule up to ``duration``, block by block."""
        out: list[Arrival] = []
        t, b = 0.0, 0
        tok_rng = rng_for(self.seed, 3)
        while t <= duration:
            rng = rng_for(self.seed, 4, b)
            order = rng.permutation(len(self.sizes))
            gaps = rng.permutation(len(self.gaps))
            greedy = balanced_flags(len(self.sizes),
                                    float(self.p["greedy_share"]), rng)
            for k in range(len(order)):
                t += self.gaps[gaps[k]]
                plen, new = self.sizes[order[k]]
                out.append(Arrival(due=t,
                                   prompt=tokens(tok_rng, self.vocab, plen),
                                   max_new=new, greedy=greedy[k]))
            b += 1
        return out

    def on_finish(self, arrival: Arrival, served: list[int],
                  now: float) -> list[Arrival]:
        return []

    def prefill_shapes(self, duration: float, page_size: int | None
                       ) -> set[tuple[int, int]]:
        return {(plen, 0) for plen, _ in self.sizes}
