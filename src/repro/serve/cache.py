"""Slot-based KV-cache manager for continuous batching.

One shared decode-state pytree (``lm.make_decode_state`` with batch =
``max_slots``) lives on device for the whole engine lifetime; a *slot* is
one batch row of every leaf. Admission scatters a freshly prefilled
batch-1 state into the slot's row; retirement just returns the slot index
to the free list -- the stale row is dead weight until the next admission
overwrites it (decode steps keep writing junk at the dead row's position 0,
which is harmless for the same reason: nothing reads a row between free and
the full-row overwrite at the next admission).

Leaf layout note: scanned group states are stacked ``[G, B, ...]`` while
head/tail block states are ``[B, ...]``, so the scatter runs per top-level
key with the right batch axis (1 vs 0) rather than one uniform tree_map.
Full-attention K/V leaves are lane-dense ``[B, T, kv * hd]`` (``[G, B, T,
kv * hd]`` stacked): with hd = 64 a ``[.., kv, hd]`` leaf fills half a
128-lane tile and the compiler relays it out, so the prefill produces
the flat rows this scatter installs, the decode step writes its rows
into the stack in place and its attention reads them in that layout
(``lm.make_decode_state``).

Mesh mode: constructed with a ``Mesh``, the shared states live as
``runtime.sharding.cache_shardings`` NamedShardings (slot axis over the
data axes, one trailing feature dim over "model") and the scatter is
re-jitted per instance with those explicit out_shardings. The scatter
ALWAYS donates the shared states -- admission rewrites one row in place
instead of double-buffering the whole cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.config import ArchConfig


def _scatter_body(states, upd, slot):
    """Write batch-1 prefill states ``upd`` into row ``slot`` of the shared
    states (dynamic slot index: one compile serves every slot)."""
    def at_axis(axis):
        return lambda s, u: jax.lax.dynamic_update_slice_in_dim(
            s, u.astype(s.dtype), slot, axis=axis)

    return {
        "head": jax.tree.map(at_axis(0), states["head"], upd["head"]),
        "groups": jax.tree.map(at_axis(1), states["groups"],
                               upd["groups"]),
        "tail": jax.tree.map(at_axis(0), states["tail"], upd["tail"]),
    }


#: single-device scatter, shared across engine instances (one compile);
#: arg 0 (the shared states) is donated -- the update happens in place
_scatter_slot = jax.jit(_scatter_body, donate_argnums=(0,))


class SlotCache:
    """Fixed-capacity slot allocator over one shared decode-state tree.

    Tracks, per slot: whether it is live, the next cache write position
    (== tokens held so far), and the current input token (the one the next
    decode step will embed). Host-side numpy mirrors keep the per-step
    bookkeeping off the device.
    """

    def __init__(self, cfg: ArchConfig, max_slots: int, cache_len: int,
                 dtype=None, mesh=None):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1: {max_slots}")
        self.cfg = cfg
        self.max_slots = max_slots
        self.cache_len = cache_len
        kw = {} if dtype is None else {"dtype": dtype}
        self.states = lm.make_decode_state(cfg, max_slots, cache_len, **kw)
        self.mesh = mesh
        if mesh is not None:
            from repro.runtime import sharding as rsh
            self.shardings = rsh.cache_shardings(mesh, self.states)
            self.states = jax.device_put(self.states, self.shardings)
            self._scatter = jax.jit(_scatter_body,
                                    out_shardings=self.shardings,
                                    donate_argnums=(0,))
        else:
            self.shardings = None
            self._scatter = _scatter_slot
        self._free: list[int] = list(range(max_slots - 1, -1, -1))
        self.live = np.zeros(max_slots, bool)
        self.positions = np.zeros(max_slots, np.int32)
        self.tokens = np.zeros(max_slots, np.int32)
        self.allocations = 0           # total allocate() calls (reuse stat)

    # ------------------------------------------------------------ slots
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        return self.max_slots - len(self._free)

    def live_slots(self) -> list[int]:
        return [i for i in range(self.max_slots) if self.live[i]]

    def allocate(self) -> int:
        """Pop the lowest free slot. Caller must follow with write_prefill."""
        if not self._free:
            raise RuntimeError("no free slot")
        slot = self._free.pop()
        self.live[slot] = True
        self.allocations += 1
        return slot

    def release(self, slot: int) -> None:
        if not self.live[slot]:
            raise RuntimeError(f"slot {slot} is not live")
        self.live[slot] = False
        self.positions[slot] = 0
        self.tokens[slot] = 0
        self._free.append(slot)
        self._free.sort(reverse=True)   # deterministic: lowest slot next

    # ------------------------------------------------------------ state
    def write_prefill(self, slot: int, states1, first_token: int,
                      prompt_len: int) -> None:
        """Install a prefilled request: batch-1 ``states1`` into the slot
        row, position at ``prompt_len`` (where ``first_token`` -- sampled
        from the prefill logits -- will be written by the next decode)."""
        if prompt_len >= self.cache_len:
            raise RuntimeError(
                f"prompt_len {prompt_len} >= cache_len {self.cache_len}")
        self.states = self._scatter(self.states, states1,
                                    np.int32(slot))
        self.positions[slot] = prompt_len
        self.tokens[slot] = first_token

    def advance(self, slot: int, token: int) -> None:
        """After a decode step: slot consumed its input token (written at
        ``positions[slot]``) and will feed ``token`` next."""
        self.positions[slot] += 1
        self.tokens[slot] = token
        if self.positions[slot] > self.cache_len:
            raise RuntimeError(
                f"slot {slot} position {self.positions[slot]} overflowed "
                f"cache_len {self.cache_len}")

    def decode_inputs(self) -> dict:
        """Batched inputs for one shared decode step. Dead rows feed token
        0 at position 0 -- their outputs are discarded and their cache rows
        are rewritten wholesale on the next admission."""
        tok = jnp.asarray(self.tokens[:, None])
        pos = jnp.asarray(self.positions[:, None].astype(np.int32))
        return {"tokens": tok, "positions": pos}
