"""Block-paged KV-cache manager: one global page pool + per-row tables.

The pool reuses ``lm.make_decode_state`` with ``batch = num_pages`` and
``cache_len = page_size``: every leaf is ``[P, page_size, ...]`` (scanned
groups ``[G, P, page_size, ...]``), i.e. the slot cache's layout with the
page axis in the slot axis's role. Full-attention K/V pools are
lane-dense ``[P, page_size, kv * hd]`` like the slot cache's rows (see
``serve.cache``); the Pallas paged kernel gets a ``[P, page_size, kv,
hd]`` view of them at its call. A request's logical position ``p`` lives
at physical ``(table[p // page_size], p % page_size)``; the decode
step carries the table as a ``[B, MP]`` input and the model scatters
writes / gathers dense logical views through it (see
``models.transformer._page_targets`` / ``_gather_pages``).

Page 0 is the reserved TRASH page: never allocated, the redirect target
for dead rows' decode writes and padded chunk tails. Table entry 0 thus
doubles as "unallocated" -- gathers through it read junk that position
masks discard, exactly the dead-slot-row argument of the dense cache.

Rows are the decode-batch dimension: allocation is lowest-free-first (the
same discipline as ``SlotCache``, which is what lets the differential
suite run both engines with identical row assignment and PRNG row
consumption). A row is ``reserved`` while a chunked prefill streams into
its pages and only becomes ``live`` (decoded) when the prompt completes.

Mesh mode mirrors the slot cache: pool leaves live as
``runtime.sharding.paged_cache_shardings`` NamedShardings (page axis over
the data axes, one trailing feature dim over "model") and the prefill
scatter is re-jitted with those out_shardings, always donating the pool.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.config import ArchConfig

TRASH = 0          # reserved pool page: write redirect target, never owned


def _scatter_pages_body(states, upd, pages, n_used):
    """Write a batch-1 dense prefill state (``cache_len = MP * page_size``
    positions) into the pool pages listed in ``pages [MP]``; entries at
    index >= ``n_used`` redirect to the trash page (their content is
    prefill padding)."""
    idx = jnp.where(jnp.arange(pages.shape[0]) < n_used, pages, TRASH)

    def at_axis(axis):
        def f(s, u):
            if axis == 0:                       # pool [P, ps, ...]
                u = u[0]
                u = u.reshape((idx.shape[0], s.shape[1]) + u.shape[1:])
                return s.at[idx].set(u.astype(s.dtype))
            u = u[:, 0]                         # pool [G, P, ps, ...]
            u = u.reshape((u.shape[0], idx.shape[0], s.shape[2])
                          + u.shape[2:])
            return s.at[:, idx].set(u.astype(s.dtype))
        return f

    return {
        "head": jax.tree.map(at_axis(0), states["head"], upd["head"]),
        "groups": jax.tree.map(at_axis(1), states["groups"],
                               upd["groups"]),
        "tail": jax.tree.map(at_axis(0), states["tail"], upd["tail"]),
    }


#: single-device scatter, shared across engine instances; the pool (arg 0)
#: is donated -- admission rewrites the target pages in place
_scatter_pages = jax.jit(_scatter_pages_body, donate_argnums=(0,))


class PagedKVCache:
    """Page pool + row allocator + per-row page tables.

    Per row the host tracks: live/reserved flags, the next cache write
    position, the pending input token, the page table (``0`` = trash =
    unallocated), and which table entries are *owned* vs *shared* (held
    via the prefix cache; shared pages are read-only and are released
    back to the prefix cache, never freed directly).
    """

    def __init__(self, cfg: ArchConfig, max_rows: int, cache_len: int,
                 page_size: int, num_pages: int, dtype=None, mesh=None):
        if max_rows < 1:
            raise ValueError(f"max_rows must be >= 1: {max_rows}")
        if cache_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide cache_len {cache_len}")
        self.cfg = cfg
        self.max_rows = max_rows
        self.cache_len = cache_len
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_pages_per_row = cache_len // page_size
        kw = {} if dtype is None else {"dtype": dtype}
        self.states = lm.make_decode_state(cfg, num_pages, page_size, **kw)
        self.mesh = mesh
        if mesh is not None:
            from repro.runtime import sharding as rsh
            self.shardings = rsh.paged_cache_shardings(mesh, self.states)
            self.states = jax.device_put(self.states, self.shardings)
            self._scatter = jax.jit(_scatter_pages_body,
                                    out_shardings=self.shardings,
                                    donate_argnums=(0,))
        else:
            self.shardings = None
            self._scatter = _scatter_pages
        self._free_rows: list[int] = list(range(max_rows - 1, -1, -1))
        self._free_pages: list[int] = list(range(num_pages - 1, 0, -1))
        self.live = np.zeros(max_rows, bool)        # decoding
        self.reserved = np.zeros(max_rows, bool)    # prefill in flight
        self.positions = np.zeros(max_rows, np.int32)
        self.tokens = np.zeros(max_rows, np.int32)
        self.tables = np.full((max_rows, self.max_pages_per_row), TRASH,
                              np.int32)
        self.n_shared = np.zeros(max_rows, np.int32)  # leading shared pages
        self.allocations = 0         # row allocations (reuse stat)
        self.page_allocations = 0    # page allocations (churn stat)

    # ------------------------------------------------------------- rows
    @property
    def n_free(self) -> int:
        return len(self._free_rows)

    @property
    def n_live(self) -> int:
        """Rows in use -- decoding or mid-prefill (drives ``run()``)."""
        return self.max_rows - len(self._free_rows)

    def live_slots(self) -> list[int]:
        """Rows participating in the shared decode step, in row order."""
        return [i for i in range(self.max_rows) if self.live[i]]

    def allocate(self) -> int:
        """Pop the lowest free row (reserved until activate/release)."""
        if not self._free_rows:
            raise RuntimeError("no free row")
        row = self._free_rows.pop()
        self.reserved[row] = True
        self.allocations += 1
        return row

    def release(self, row: int) -> tuple[list[int], list[int]]:
        """Free a row; returns ``(owned_pages, shared_pages)`` in table
        order -- the caller frees the owned pages (:meth:`free_pages`)
        and hands the shared ones back to the prefix cache."""
        if not (self.live[row] or self.reserved[row]):
            raise RuntimeError(f"row {row} is not in use")
        ns = int(self.n_shared[row])
        held = [int(p) for p in self.tables[row] if p != TRASH]
        owned, shared = held[ns:], held[:ns]
        self.live[row] = False
        self.reserved[row] = False
        self.positions[row] = 0
        self.tokens[row] = 0
        self.tables[row] = TRASH
        self.n_shared[row] = 0
        self._free_rows.append(row)
        self._free_rows.sort(reverse=True)
        return owned, shared

    # ------------------------------------------------------------ pages
    @property
    def n_free_pages(self) -> int:
        return len(self._free_pages)

    def allocate_pages(self, n: int) -> list[int]:
        """Pop ``n`` free pages (lowest-first); all-or-nothing."""
        if n > len(self._free_pages):
            raise RuntimeError(
                f"need {n} pages, {len(self._free_pages)} free")
        out = [self._free_pages.pop() for _ in range(n)]
        self.page_allocations += len(out)
        return out

    def free_pages(self, pages: list[int]) -> None:
        for p in pages:
            if p == TRASH:
                raise RuntimeError("freeing the trash page")
            if p in self._free_pages:
                raise RuntimeError(f"double free of page {p}")
            self._free_pages.append(p)
        self._free_pages.sort(reverse=True)

    def set_table(self, row: int, pages: list[int], n_shared: int) -> None:
        """Install a row's page table: ``pages[:n_shared]`` are prefix-
        cache pages (read-only), the rest owned."""
        self.tables[row] = TRASH
        self.tables[row, :len(pages)] = pages
        self.n_shared[row] = n_shared

    def grow_table(self, row: int, page: int) -> None:
        """Append one owned page to a row's table (decode growth)."""
        idx = int(np.argmax(self.tables[row] == TRASH))
        if self.tables[row, idx] != TRASH:
            raise RuntimeError(f"row {row} table is full")
        self.tables[row, idx] = page

    def next_write_unbacked(self, row: int) -> bool:
        """True when the row's next decode write position has no page."""
        pi = int(self.positions[row]) // self.page_size
        return (pi < self.max_pages_per_row
                and self.tables[row, pi] == TRASH)

    # ------------------------------------------------------------ state
    def scatter_prefill(self, row: int, states1, n_pages_used: int) -> None:
        """Install a dense batch-1 prefill state (``cache_len`` wide) into
        the first ``n_pages_used`` pages of the row's table."""
        self.states = self._scatter(self.states, states1,
                                    jnp.asarray(self.tables[row]),
                                    np.int32(n_pages_used))

    def activate(self, row: int, first_token: int, prompt_len: int) -> None:
        """Prefill complete: the row joins the shared decode batch at
        position ``prompt_len`` feeding ``first_token``."""
        if prompt_len >= self.cache_len:
            raise RuntimeError(
                f"prompt_len {prompt_len} >= cache_len {self.cache_len}")
        self.reserved[row] = False
        self.live[row] = True
        self.positions[row] = prompt_len
        self.tokens[row] = first_token

    def advance(self, row: int, token: int) -> None:
        self.positions[row] += 1
        self.tokens[row] = token
        if self.positions[row] > self.cache_len:
            raise RuntimeError(
                f"row {row} position {self.positions[row]} overflowed "
                f"cache_len {self.cache_len}")

    def decode_inputs(self) -> dict:
        """Batched decode inputs; dead rows feed token 0 at position 0
        through their all-trash tables (reads junk, writes trash)."""
        return {"tokens": jnp.asarray(self.tokens[:, None]),
                "positions": jnp.asarray(self.positions[:, None]
                                         .astype(np.int32)),
                "pages": jnp.asarray(self.tables)}
