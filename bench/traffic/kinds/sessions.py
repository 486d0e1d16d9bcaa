"""Open loop of multi-turn chat and agent sessions that share system
prompts.

Sessions start with Poisson arrivals at ``session_rate``. Each picks one
of ``system_prompts.count`` system prompts (lengths at fixed quantiles,
popularity Zipf(``zipf``)), runs ``turns.min``..``turns.max`` turns, and
each turn's prompt is the system prompt, the whole history (earlier user
turns and the answers served to them) and a new user turn. The next turn
is due when the answer is complete plus an exponential think time of
mean ``think_mean_s``. A session ends early when its next prompt plus
the largest output would pass the engine's horizon.

Sizes come per block of ``block`` sessions: every block holds the same
multiset of system-prompt picks, turn counts, user-turn and output
lengths, think times and start gaps, in seeded order.
"""
from __future__ import annotations

import dataclasses

from bench.traffic.kinds.common import (Arrival, balanced_flags, exp_gaps, quantiles, rng_for,
                     tokens)


@dataclasses.dataclass
class _Session:
    start: float
    sys_id: int
    users: list[int]
    outputs: list[int]
    thinks: list[float]
    greedy: bool


def _zipf_counts(n_items: int, s: float, total: int) -> list[int]:
    """Largest-remainder split of ``total`` picks over Zipf(s) ranks."""
    w = [1.0 / (r + 1) ** s for r in range(n_items)]
    exact = [total * x / sum(w) for x in w]
    counts = [int(e) for e in exact]
    rest = sorted(range(n_items), key=lambda i: counts[i] - exact[i])
    for i in rest[:total - sum(counts)]:
        counts[i] += 1
    return counts


def _plan(p: dict, seed: int, duration: float, stream: int
          ) -> tuple[list[int], list[_Session]]:
    sp = p["system_prompts"]
    sys_lens = quantiles(sp, int(sp["count"]))
    k = int(p["block"])
    tmin, tmax = int(p["turns"]["min"]), int(p["turns"]["max"])
    turn_counts = [tmin + i % (tmax - tmin + 1) for i in range(k)]
    users = quantiles(p["user"], k * tmax)
    outs = quantiles(p["output"], k * tmax)
    thinks = exp_gaps(1.0 / float(p["think_mean_s"]), k * (tmax - 1))
    gaps = exp_gaps(float(p["session_rate"]), k)
    sys_pick = [i for i, c in enumerate(
        _zipf_counts(len(sys_lens), float(sp["zipf"]), k)) for _ in range(c)]
    sessions: list[_Session] = []
    t, b = 0.0, 0
    while t <= duration:
        rng = rng_for(seed, stream, b)
        perm = lambda xs: [xs[i] for i in rng.permutation(len(xs))]
        picks, counts = perm(sys_pick), perm(turn_counts)
        u, o, th, g = perm(users), perm(outs), perm(thinks), perm(gaps)
        greedy = balanced_flags(k, float(p["greedy_share"]), rng)
        for i in range(k):
            t += g[i]
            n = counts[i]
            sessions.append(_Session(
                start=t, sys_id=picks[i], users=u[i * tmax:i * tmax + n],
                outputs=o[i * tmax:i * tmax + n],
                thinks=th[i * (tmax - 1):(i + 1) * (tmax - 1)],
                greedy=greedy[i]))
        b += 1
    return sys_lens, sessions


def _turn_lengths(sys_len: int, s: _Session, horizon: int, out_max: int
                  ) -> list[int]:
    """Prompt length of each turn the session runs (answers at their
    planned length), stopping at the horizon guard."""
    lens, cur = [], sys_len
    for t, user in enumerate(s.users):
        cur += user
        if t and cur + out_max > horizon:
            break
        lens.append(cur)
        cur += s.outputs[t]
    return lens


class Sessions:
    open_loop = True

    def __init__(self, params: dict, seed: int, vocab: int, ctx: dict):
        self.p = params
        self.vocab = vocab
        self.seed = seed
        self.horizon = int(ctx["horizon"])
        self.out_max = int(params["output"]["max"])
        self.temperature = float(params["temperature"])

    def start(self, duration: float) -> list[Arrival]:
        self.sys_lens, self.plan = _plan(self.p, self.seed, duration, 5)
        rng = rng_for(self.seed, 6)
        self.sys_tokens = [tokens(rng, self.vocab, n) for n in self.sys_lens]
        self.rng = rng_for(self.seed, 7)
        out = []
        for sid, s in enumerate(self.plan):
            prompt = self.sys_tokens[s.sys_id] + tokens(
                self.rng, self.vocab, s.users[0])
            out.append(Arrival(due=s.start, prompt=prompt,
                               max_new=s.outputs[0], greedy=s.greedy,
                               meta={"session": sid, "turn": 0}))
        return out

    def on_finish(self, arrival: Arrival, served: list[int],
                  now: float) -> list[Arrival]:
        s = self.plan[arrival.meta["session"]]
        t = arrival.meta["turn"] + 1
        if t >= len(s.users):
            return []
        prompt = (arrival.prompt + list(served)
                  + tokens(self.rng, self.vocab, s.users[t]))
        if len(prompt) + self.out_max > self.horizon:
            return []
        return [Arrival(due=now + s.thinks[t - 1], prompt=prompt,
                        max_new=s.outputs[t], greedy=s.greedy,
                        meta={"session": arrival.meta["session"],
                              "turn": t})]

    def prefill_shapes(self, duration: float, page_size: int | None
                       ) -> set[tuple[int, int]]:
        """``(prompt length, reused prefix)`` of every turn the planned
        sessions can run: the prefix missed, the system prompt reused,
        and the previous turn's prompt pages reused."""
        sys_lens, plan = _plan(self.p, self.seed, duration, 5)
        ps = page_size or 0
        shapes = set()
        for s in plan:
            lens = _turn_lengths(sys_lens[s.sys_id], s, self.horizon,
                                 self.out_max)
            for t, n in enumerate(lens):
                shapes.add((n, 0))
                if not ps:
                    continue
                cap = (n - 1) // ps
                shapes.add((n, ps * min(sys_lens[s.sys_id] // ps, cap)))
                if t:
                    shapes.add((n, ps * min(lens[t - 1] // ps, cap)))
        return shapes

    @staticmethod
    def turn_prompt_lengths(params: dict, n: int, ctx: dict) -> list[int]:
        """``n`` quantiles of the whole-turn prompt lengths these
        sessions produce (fixed plan, answers at their planned length)."""
        sys_lens, plan = _plan(params, int(params.get("shape_seed", 0)),
                               200.0 / float(params["session_rate"]), 0)
        lens = sorted(n_ for s in plan for n_ in _turn_lengths(
            sys_lens[s.sys_id], s, int(ctx["horizon"]),
            int(params["output"]["max"])))
        return [lens[min(int((i + 0.5) / n * len(lens)), len(lens) - 1)]
                for i in range(n)]
