"""The measured loop: offers the traffic to the engine, pumps its steps,
and stamps every token with the host time at which it came back.

Open-loop arrivals are submitted when due (late if a step was running;
the lateness is recorded and every request is timed from when it was
DUE); closed-loop clients submit their next request as soon as the last
one retires. The engine's ``step`` returns after the sampled tokens are
on the host, so a token's stamp is when it was available.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
import time

import jax

from repro.serve import Request, SamplingParams


class Spans:
    """Host spans around the calls into each layer; in a traced run they
    are written into the profiler's trace on the device's clock."""

    def __init__(self):
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.tracing:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        else:
            yield


@dataclasses.dataclass
class Track:
    """Host-side record of one submitted request."""
    arrival: object
    req: Request
    due: float                       # absolute perf_counter seconds
    stamps: list = dataclasses.field(default_factory=list)
    finished: float = -1.0


class Loop:
    def __init__(self, engine, generator, traffic: dict, spans: Spans):
        self.engine = engine
        self.gen = generator
        self.traffic = traffic
        self.spans = spans
        self.tracks: list[Track] = []
        self.live: dict[int, Track] = {}       # uid -> track, unfinished
        self.heap: list = []
        self._tie = itertools.count()
        self.lateness: list[float] = []
        self.t0 = 0.0
        self.cutoff = float("inf")     # no arrival due after this is sent

    def _sampling(self, greedy: bool) -> SamplingParams:
        if greedy:
            return SamplingParams()
        return SamplingParams(temperature=float(self.traffic["temperature"]),
                              top_k=int(self.traffic["top_k"]))

    def _push(self, arrivals) -> None:
        for a in arrivals:
            heapq.heappush(self.heap, (self.t0 + a.due, next(self._tie), a))

    def start(self, horizon_s: float) -> None:
        self.t0 = time.perf_counter()
        self._push(self.gen.start(horizon_s))

    def _submit_due(self, now: float) -> None:
        limit = min(now, self.cutoff)
        while self.heap and self.heap[0][0] <= limit:
            due, _, a = heapq.heappop(self.heap)
            with self.spans.span("submit"):
                req = self.engine.submit(Request(
                    prompt=a.prompt, max_new_tokens=a.max_new,
                    sampling=self._sampling(a.greedy)))
            tr = Track(a, req, due)
            self.tracks.append(tr)
            self.live[req.uid] = tr
            if self.gen.open_loop:
                self.lateness.append(time.perf_counter() - due)

    def _idle(self) -> bool:
        eng = self.engine
        return not (eng.scheduler.n_pending or eng.cache.n_live
                    or getattr(eng, "_jobs", None))

    def step_once(self) -> None:
        """Submit what is due, then one engine step (or wait for the
        next arrival when there is no work)."""
        now = time.perf_counter()
        self._submit_due(now)
        if self._idle():
            if self.heap and self.heap[0][0] <= self.cutoff:
                with self.spans.span("wait"):
                    time.sleep(max(0.0, min(self.heap[0][0] - now, 0.05)))
            return
        with self.spans.span("step"):
            retired = self.engine.step()
        t = time.perf_counter()
        done = []
        for uid, tr in self.live.items():
            n = len(tr.req.generated)
            while len(tr.stamps) < n:
                tr.stamps.append(t)
        for req in retired:
            tr = self.live.pop(req.uid, None)
            if tr is None:
                continue
            tr.finished = t
            done.append(tr)
        for tr in done:
            self._push_next(tr, t)

    def _push_next(self, tr: Track, t: float) -> None:
        nxt = self.gen.on_finish(tr.arrival, tr.req.generated, t - self.t0)
        self._push(nxt)

    def run_until(self, t_end: float) -> None:
        while time.perf_counter() < t_end:
            self.step_once()

    def drain_first_tokens(self, due_before: float, limit_s: float) -> None:
        """After the window: stop new arrivals and keep stepping until
        every request due before ``due_before`` has its first token."""
        self.cutoff = due_before
        stop = time.perf_counter() + limit_s

        def waiting():
            return (any(not tr.stamps and tr.due < due_before
                        for tr in self.live.values())
                    or bool(self.heap and self.heap[0][0] < due_before))
        while waiting() and time.perf_counter() < stop:
            self.step_once()
