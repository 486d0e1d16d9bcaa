"""The traffic generators: seeded determinism, the same sizes for every
seed, clips, the horizon guard and shared system-prompt prefixes."""
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401

import pytest  # noqa: E402

from bench.harness import spec  # noqa: E402
from bench.traffic.kinds import common  # noqa: E402

VOCAB = 1000


def _gen(name, seed, horizon=2048):
    traffic = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                          name + ".json"))
    kind = spec.load_kind(traffic["kind"])
    ctx = {"horizon": horizon,
           "load_traffic": lambda n: spec.load_json(os.path.join(
               spec.BENCH_DIR, "traffic", n + ".json")),
           "load_kind": spec.load_kind}
    return traffic, kind(traffic, seed, VOCAB, ctx)


def _first(gen, duration):
    return [(a.due, a.prompt, a.max_new, a.greedy)
            for a in gen.start(duration)]


@pytest.mark.parametrize("mix", ["offline_monitored", "sessions_monitored",
                                 "unshared_monitored"])
def test_same_seed_same_requests(mix):
    _, g1 = _gen(mix, 2 ** 31 + 5)
    _, g2 = _gen(mix, 2 ** 31 + 5)
    _, g3 = _gen(mix, 2 ** 31 + 6)
    a, b, c = _first(g1, 30.0), _first(g2, 30.0), _first(g3, 30.0)
    assert a == b
    assert [x[1] for x in a] != [x[1] for x in c]


def test_quantiles_clip_and_round():
    d = {"dist": "lognormal", "median": 384, "sigma": 0.9, "min": 32,
         "max": 1536, "quantum": 32}
    q = common.quantiles(d, 64)
    assert min(q) >= 32 and max(q) <= 1536
    assert all(v % 32 == 0 for v in q)
    assert q == sorted(q)
    assert abs(q[32] - 384) <= 64


def test_closed_loop_sizes_do_not_hang_on_the_seed():
    """Each round of the 64 clients holds every size pair once."""
    sizes = []
    for seed in (1, 2):
        _, g = _gen("offline_monitored", seed)
        first = g.start(0.0)
        sizes.append(sorted((len(a.prompt), a.max_new) for a in first))
        assert sum(a.greedy for a in first) == 32
    assert sizes[0] == sizes[1]


def test_open_loop_blocks_hold_the_same_sizes_and_gaps():
    traffic, g1 = _gen("unshared_monitored", 11)
    _, g2 = _gen("unshared_monitored", 12)
    a1, a2 = g1.start(60.0), g2.start(60.0)
    k = traffic["block"]
    sizes = lambda arr: collections.Counter(
        (len(a.prompt), a.max_new) for a in arr[:k])
    gaps = lambda arr: sorted(round(y.due - x.due, 9)
                              for x, y in zip(arr[:k - 1], arr[1:k]))
    assert sizes(a1) == sizes(a2)
    assert abs(sum(b.due - a.due for a, b in zip(a1[:k - 1], a1[1:k]))
               - sum(b.due - a.due for a, b in zip(a2[:k - 1], a2[1:k]))) \
        < 2 * max(gaps(a1))


def test_session_blocks_hold_the_same_prompts_and_turns():
    traffic, g1 = _gen("sessions_monitored", 11)
    _, g2 = _gen("sessions_monitored", 12)
    g1.start(60.0)
    g2.start(60.0)
    k = traffic["block"]
    assert collections.Counter(s.sys_id for s in g1.plan[:k]) == \
        collections.Counter(s.sys_id for s in g2.plan[:k])
    assert sorted(len(s.users) for s in g1.plan[:k]) == \
        sorted(len(s.users) for s in g2.plan[:k])


def test_sessions_share_page_aligned_system_prompts():
    _, g = _gen("sessions_monitored", 7)
    first = g.start(60.0)
    by_sys = collections.defaultdict(list)
    for a in first:
        by_sys[g.plan[a.meta["session"]].sys_id].append(a.prompt)
    shared = {sid: v for sid, v in by_sys.items() if len(v) > 1}
    assert shared, "no system prompt reused"
    for sid, prompts in shared.items():
        n = g.sys_lens[sid]
        assert n % 16 == 0 and 512 <= n <= 1024
        assert all(p[:n] == prompts[0][:n] for p in prompts)
        # the first user turn is fresh: sharing stops at the system prompt
        assert len({tuple(p[n:n + 16]) for p in prompts}) == len(prompts)


def test_sessions_next_turn_extends_history_and_honours_horizon():
    traffic, g = _gen("sessions_monitored", 3, horizon=2048)
    first = g.start(120.0)
    out_max = traffic["output"]["max"]
    for a in first[:40]:
        served = list(range(a.max_new))
        chain = [a]
        while True:
            nxt = g.on_finish(chain[-1], served, 0.0)
            if not nxt:
                break
            (b,) = nxt
            assert b.prompt[:len(chain[-1].prompt)] == chain[-1].prompt
            assert b.prompt[len(chain[-1].prompt):][:len(served)] == served
            assert len(b.prompt) + out_max <= 2048
            chain.append(b)
            served = list(range(b.max_new))


def test_horizon_guard_ends_sessions_early():
    _, g = _gen("sessions_monitored", 3, horizon=1400)
    cut = 0
    for a in g.start(120.0):
        s = g.plan[a.meta["session"]]
        nxt = g.on_finish(a, [0] * a.max_new, 0.0)
        if not nxt and len(s.users) > 1:
            cut += 1
        for b in nxt:
            assert len(b.prompt) + 256 <= 1400
    assert cut > 0


def test_unshared_prompts_follow_session_turn_lengths():
    traffic, g = _gen("unshared_monitored", 5)
    lens = sorted(len(p) for p, _ in [(a.prompt, 0)
                                      for a in g.start(30.0)])
    assert min(lens) >= 512 + 16
    assert max(lens) <= 2048
    assert all(n % 16 == 0 for n in lens)
