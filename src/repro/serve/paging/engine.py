"""PagedServeEngine: the serving engine over a block-paged KV cache.

Construction is transparent: ``ServeEngine(params, cfg, scfg)`` returns
this subclass whenever ``scfg.paging`` is set. The step loop keeps the
base engine's structure (admit -> one shared decode -> retire) and swaps
the capacity model underneath it:

* **admission** allocates PAGES for the actual prompt (plus any resumed
  tokens), not a ``cache_len``-sized slot -- the admission bound is
  live tokens, so many short requests run where the slot engine would
  hold ``num_slots``;
* **chunked prefill** streams prompts longer than ``prefill_chunk``
  through admission one chunk per engine step, each chunk scattering its
  KV into the row's pages and attending to the paged history, so a long
  prompt never stalls the decode batch for a full-prompt prefill;
* **shared prefixes** (``prefix_cache=True``) are matched page-by-page in
  a refcounted trie; a hit installs read-only pages at the front of the
  row's table and prefill starts at the first unshared position.
  Copy-on-write is structural: forking copies table entries, never page
  data;
* **page pressure preempts**: when no page is free and no cached prefix
  page is evictable, the lowest-priority latest-admitted victim is
  evicted -- its pages are reclaimed, its accountant state suspended, and
  the request re-queued at the FRONT of its class for re-prefill of
  prompt + generated-so-far (greedy-token-exact resume, the
  prefill/decode equivalence the slot engine's tests already pin).

Power accounting stays EXACT under all of it (the tentpole contract):

* the full prompt is streamed through ``record_prefill`` ONCE at
  admission regardless of chunking -- BIC/ZVG counters are stream
  statistics over consecutive rows, so recording the rows in one call
  keeps them bit-identical to the slot engine's accounting;
* a prefix reuser records only the suffix rows it actually computed: the
  FIRST PAYER keeps the energy of the shared pages it paid for
  (see docs/serving.md for why the pinned-first-payer rule was chosen
  over splitting retroactively);
* preemption suspends the accumulator and the re-prefill records
  additional rows -- recomputation is honestly paid-for energy;
* per-request reports are booked into the serve-wide capture only at
  retirement, so retired-request energies still sum bit-exactly to
  ``trace_report()``.

Restrictions: paged serving supports position-masked cache mixers only
(``attn`` / ``mla``) and ``cfg.pos != "mrope"`` (the paged decode path
derives its position scatter/gather from scalar positions).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.transformer import parse_spec

from ..engine import ServeEngine, _PAD_SAFE_MIXERS
from ..request import Request, RequestStatus
from ..spans import span
from .cache import PagedKVCache
from .prefix import PrefixCache
from .scheduler import ClassScheduler


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _pow2_at_least(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


@dataclasses.dataclass
class _ChunkJob:
    """Host-side progress of one streaming prefill (one per reserved
    row): ``seq`` is the full token sequence being prefilled (prompt, or
    prompt + generated-so-far on resume), ``next`` the first position the
    next chunk will compute."""
    req: Request
    seq: list[int]
    next: int
    resume: bool


class PagedServeEngine(ServeEngine):
    """ServeEngine over a page pool; see the module docstring."""

    def __init__(self, params, cfg, scfg, mesh=None):
        super().__init__(params, cfg, scfg, mesh)
        chunk_fn = lm.make_chunk_prefill_step(cfg)
        if mesh is None:
            # like decode, a chunk rewrites pool pages in place
            self._chunk = jax.jit(chunk_fn, donate_argnums=(1,))
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(mesh, P())
            self._chunk = jax.jit(
                chunk_fn,
                in_shardings=(self.param_shardings, self.cache.shardings,
                              rep, rep),
                out_shardings=(rep, self.cache.shardings),
                donate_argnums=(1,))
        pcfg = scfg.paging
        self.prefix = (PrefixCache(pcfg.page_size)
                       if pcfg.prefix_cache else None)
        self._jobs: dict[int, _ChunkJob] = {}      # row -> chunk prefill
        self._suspended: dict[int, object] = {}    # uid -> _SlotAcc
        self.stats.update(preemptions=0, chunk_calls=0,
                          prefix_hit_requests=0, peak_admitted=0)

    def _build_state(self):
        pcfg = self.scfg.paging
        mixers = {parse_spec(s)[0] for s in
                  (*self.cfg.pattern, *self.cfg.head, *self.cfg.tail)}
        if not mixers <= _PAD_SAFE_MIXERS:
            raise ValueError(
                f"paged serving supports position-masked cache mixers "
                f"(attn/mla) only; {self.cfg.name} uses "
                f"{sorted(mixers - _PAD_SAFE_MIXERS)}")
        if self.cfg.pos == "mrope":
            raise ValueError("paged serving does not support mrope")
        self._batch = pcfg.max_rows
        self.cache = PagedKVCache(
            self.cfg, pcfg.max_rows, self.scfg.cache_len, pcfg.page_size,
            pcfg.num_pages, dtype=jnp.dtype(self.cfg.compute_dtype),
            mesh=self.mesh)
        self.scheduler = ClassScheduler(
            self.scfg.cache_len, pcfg.classes, page_size=pcfg.page_size,
            usable_pages=pcfg.num_pages - 1)

    # ----------------------------------------------------------- admission
    def _admission_phase(self, retired: list[Request]) -> None:
        for row in sorted(self._jobs):
            with span("admit", uid=self._jobs[row].req.uid):
                self._pump_chunk(row, retired)
        while self.cache.n_free and self.scheduler.n_pending:
            req = self.scheduler.pop_admissible(1)[0]
            with span("admit", uid=req.uid):
                admitted = self._try_admit(req, retired)
            if not admitted:
                # head-of-class blocked on pages: stop admitting (its
                # seniority is preserved; capacity frees as rows retire)
                self.scheduler.requeue_front(req)
                break
        self.stats["peak_admitted"] = max(self.stats["peak_admitted"],
                                          self.cache.n_live)

    def _try_admit(self, req: Request, retired: list[Request]) -> bool:
        pcfg = self.scfg.paging
        ps = pcfg.page_size
        resume = bool(req.generated)
        # resume re-embeds everything but the pending token, which stays
        # the decode input it already was at preemption time
        seq = (req.prompt + req.generated[:-1]) if resume else req.prompt
        length = len(seq)
        shared: list[int] = []
        if self.prefix is not None:
            # leave >= 1 unshared token so prefill has a real last
            # position to take first-token logits from
            shared = self.prefix.match(seq, (length - 1) // ps)
        start = len(shared) * ps
        owned = self._acquire_pages(_ceil_div(length, ps) - len(shared),
                                    req, admission=True)
        if owned is None:
            if shared:
                self.prefix.release(shared)
            return False
        row = self.cache.allocate()
        req.slot = row
        req.status = RequestStatus.RUNNING
        req.start_step = self.stats["steps"]
        self.cache.set_table(row, shared + owned, len(shared))
        self._running[row] = req
        if shared:
            self.stats["prefix_hit_requests"] += 1
        bucket = max(self._bucket(length), length)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :length] = seq
        if self.accountant is not None:
            with span("accountant"):
                acc = self._suspended.pop(req.uid, None)
                if acc is not None:
                    self.accountant.resume(row, acc)
                else:
                    self.accountant.begin(row, req.uid, req.prompt_len)
                # the WHOLE computed extent in one call, even when prefill
                # itself streams in chunks: BIC/ZVG counters are row-stream
                # statistics, additive only over one contiguous recording
                self._record_prefill_power(row, toks, start, length)
        if start == 0 and (pcfg.prefill_chunk == 0
                           or length <= pcfg.prefill_chunk):
            # dense path: the exact admission the slot engine runs, then
            # an exact reshape of the dense states into this row's pages
            logits, states1 = self._prefill(
                self.params, {"tokens": jnp.asarray(toks)},
                np.int32(length))
            self.cache.scatter_prefill(row, states1, _ceil_div(length, ps))
            self._finish_prefill(row, logits, retired)
        else:
            self._jobs[row] = _ChunkJob(req, seq, start, resume)
            self._pump_chunk(row, retired)
        return True

    def _pump_chunk(self, row: int, retired: list[Request]) -> None:
        """Run one prefill chunk for a reserved row; activates the row
        when the chunk contains the sequence's last position."""
        job = self._jobs[row]
        length = len(job.seq)
        width = (self.scfg.paging.prefill_chunk
                 or _pow2_at_least(length - job.next))
        start, end = job.next, min(job.next + width, length)
        toks = np.zeros((1, width), np.int32)
        poss = np.full((1, width), -1, np.int32)   # -1 pads -> trash page
        toks[0, :end - start] = job.seq[start:end]
        poss[0, :end - start] = np.arange(start, end, dtype=np.int32)
        logits, self.cache.states = self._chunk(
            self.params, self.cache.states,
            {"tokens": jnp.asarray(toks), "positions": jnp.asarray(poss),
             "pages": jnp.asarray(self.cache.tables[row:row + 1])},
            np.int32(length))
        self.stats["chunk_calls"] += 1
        job.next = end
        if end >= length:
            self._jobs.pop(row)
            self._finish_prefill(row, logits, retired)

    def _finish_prefill(self, row: int, logits,
                        retired: list[Request]) -> None:
        req = self._running[row]
        if req.generated:                          # resumed after preemption
            self._temp[row] = req.sampling.temperature
            self._topk[row] = req.sampling.top_k
            self.cache.activate(row, req.generated[-1],
                                req.prompt_len + len(req.generated) - 1)
        else:
            first = self._sample_first(req, logits)
            self.cache.activate(row, first, req.prompt_len)
            req.generated.append(first)
            self.stats["tokens"] += 1
            if self.prefix is not None:
                self._insert_prefix(req, row)
        self._maybe_retire(req, retired)

    def _insert_prefix(self, req: Request, row: int) -> None:
        """Register the request's fully-written prompt pages for reuse
        (ownership moves to the prefix cache; the row keeps reading them
        as leading shared table entries)."""
        ps = self.scfg.paging.page_size
        n_full = req.prompt_len // ps
        n_held = int(self.cache.n_shared[row])
        if n_full <= n_held:
            return
        tbl = self.cache.tables[row]
        absorbed = self.prefix.insert(
            req.prompt, [int(p) for p in tbl[:n_held]],
            [int(p) for p in tbl[n_held:n_full]])
        self.cache.n_shared[row] = n_held + absorbed

    # ------------------------------------------------------ page pressure
    def _prio(self, req: Request) -> int:
        return self.scheduler.classes[req.klass].priority

    def _acquire_pages(self, n: int, req: Request,
                       admission: bool) -> list[int] | None:
        """``n`` pages for ``req``, escalating: free list -> evict
        unreferenced prefix pages (LRU) -> preempt a victim. Admission
        only ever preempts STRICTLY lower priority (an arrival never
        displaces its equals); decode-time pressure may take an
        equal-priority later-started victim because the requester cannot
        otherwise make progress. None = caller must yield."""
        while True:
            if self.cache.n_free_pages >= n:
                return self.cache.allocate_pages(n)
            if self.prefix is not None:
                page = self.prefix.pop_evictable()
                if page != -1:
                    self.cache.free_pages([page])
                    continue
            victim = self._pick_victim(req, admission)
            if victim is None:
                return None
            self._preempt(victim)

    def _pick_victim(self, req: Request, admission: bool) -> int | None:
        rp = self._prio(req)
        cands = []
        for row, cand in self._running.items():
            if cand is req:
                continue
            p = self._prio(cand)
            if p > rp or (admission and p >= rp):
                continue
            cands.append((p, -cand.start_step, row))
        return min(cands)[2] if cands else None

    def _preempt(self, row: int) -> None:
        """Evict a running (or mid-prefill) row: reclaim its pages,
        suspend its accounting, re-queue it at the front of its class."""
        req = self._running.pop(row)
        self._jobs.pop(row, None)
        if self.accountant is not None:
            self._suspended[req.uid] = self.accountant.suspend(row)
        owned, shared = self.cache.release(row)
        if owned:
            self.cache.free_pages(owned)
        if shared:
            self.prefix.release(shared)
        self._temp[row] = 0.0
        self._topk[row] = 0
        req.slot = -1
        req.preemptions += 1
        self.stats["preemptions"] += 1
        self.scheduler.requeue_front(req)

    def _decode_ready(self, retired: list[Request]) -> list[int]:
        """Back every live row's next write position with a page before
        the shared decode; highest-priority earliest-admitted rows secure
        theirs first, so pressure lands on the rows preemption would pick
        anyway."""
        rows = sorted(self.cache.live_slots(),
                      key=lambda r: (-self._prio(self._running[r]),
                                     self._running[r].start_step))
        for row in rows:
            if not self.cache.live[row]:       # preempted by an earlier row
                continue
            if not self.cache.next_write_unbacked(row):
                continue
            got = self._acquire_pages(1, self._running[row],
                                      admission=False)
            if got is None:
                self._preempt(row)             # self-yield: sole candidate
            else:
                self.cache.grow_table(row, got[0])
        return self.cache.live_slots()

    # ----------------------------------------------------------- lifecycle
    def _release_slot(self, slot: int) -> None:
        owned, shared = self.cache.release(slot)
        if owned:
            self.cache.free_pages(owned)
        if shared:
            self.prefix.release(shared)

    def cancel(self, uid: int) -> bool:
        """Cancel anywhere in the lifecycle: queued requests are dropped,
        running / mid-prefill ones are retired as "cancelled" with every
        owned page freed and shared pages released, and a request
        cancelled while preempted books its suspended (already spent)
        energy so the sum-to-trace invariant survives."""
        for row, req in list(self._running.items()):
            if req.uid == uid:
                self._jobs.pop(row, None)
                self._retire(req, "cancelled", [])
                return True
        req = self.scheduler.find(uid)
        if req is None:
            return False
        self.scheduler.cancel(uid)
        acc = self._suspended.pop(uid, None)
        if acc is not None and self.accountant is not None:
            req.power = self.accountant.finish_detached(
                acc, len(req.generated))
        return True
