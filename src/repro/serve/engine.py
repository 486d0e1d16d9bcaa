"""ServeEngine: continuous-batching LM serving with power accounting.

The engine owns one shared decode batch of ``max_slots`` KV-cache slots and
pumps it with :meth:`ServeEngine.step`:

  1. **admit** -- while a slot is free and the queue is non-empty, prefill
     the next request (batch-1, prompt right-padded to a shape bucket so
     mixed lengths reuse a handful of compiles), scatter its states into
     the free slot, and sample its first token from the prefill logits;
  2. **decode** -- one shared decode step over all ``max_slots`` rows, each
     live slot at its own position (dead rows compute garbage that nothing
     reads); per-request sampling parameters are ``[B]`` arrays, so greedy
     and stochastic requests co-batch without recompiling;
  3. **retire** -- EOS / token budget / cache horizon, in slot order; the
     freed slot is available to the very next step's admission phase.

Per-row decode outputs depend only on that row's cache and position (every
batched op in the decode path is row-independent), so a request's tokens
are bit-identical whether it runs alone or co-batched -- the invariant
``tests/test_serve_engine.py`` pins down.

Mesh mode: pass a ``Mesh`` (``launch.mesh.make_host_mesh`` /
``make_production_mesh``) and the engine goes SPMD: params are sharded
with the TP-only serving rules (``runtime.sharding.LOGICAL_RULES_SERVE``
-- no FSDP gather on the decode path), the slot cache lives as
``cache_shardings`` NamedShardings (slot axis over the data axes, one
trailing feature dim over "model"), and prefill / decode are jitted with
explicit in_shardings / out_shardings; the decode cache is donated, so
steady-state decode updates the sharded cache in place. Host-side
control flow (scheduler, slots, sampling inputs) is unchanged, which is
what makes the sharded engine's token stream comparable 1:1 with the
single-device engine -- ``tests/multidevice`` asserts tokens AND power
counters are bit-identical.

Power accounting (optional): each admitted request carries a
:class:`repro.serve.power.PowerAccountant` slot that accumulates BIC + ZVG
streaming counters over the request's OWN operand streams -- its real
prompt rows at prefill, its embedded decode inputs each step, streamed
against representative layer-0 weights -- and retirement attaches a
:class:`RequestPowerReport` answering "what would the paper's technique
have saved on this request".
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import monitor as pm_monitor
from repro.models import lm
from repro.models import matmul as mm
from repro.models.config import ArchConfig
from repro.models.transformer import parse_spec

from . import sampling
from .cache import SlotCache
from .power import PowerAccountant
from .request import Request, RequestStatus
from .scheduler import FIFOScheduler
from .spans import fetch, span

#: mixers whose decode reads the cache strictly by position mask, making
#: right-padded prefill exact (see lm.make_slot_prefill_step); recurrent
#: mixers carry state through pad tokens and "local" rings can evict real
#: tokens, so those archs prefill at exact prompt length instead
_PAD_SAFE_MIXERS = frozenset({"attn", "mla"})


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (not architecture)."""
    max_slots: int = 4            # decode batch width = max concurrency
    cache_len: int = 128          # KV positions per slot
    eos_id: int | None = None     # retire when a request samples this token
    seed: int = 0                 # sampling PRNG seed
    prompt_buckets: tuple[int, ...] = ()   # explicit prefill shape buckets
    power_monitor: bool = False   # per-request BIC+ZVG power reports
    monitor: pm_monitor.MonitorConfig = pm_monitor.DEFAULT_MONITOR
    power_sample_every: int = 1   # stream every k-th decode step
    # decode-step matmul/attention implementation: "ref" (stock XLA) or
    # "pallas" (the fused ZVG kernels in kernels.zvg_matmul.fused).
    # Tokens, per-request energies, and trace_report() are bit-identical
    # across backends -- the contract tests/test_serve_kernel_backend.py
    # pins. Only the decode jit is affected; prefill always traces "ref"
    kernel_backend: str = "ref"
    # block-paged KV cache mode (repro.serve.paging); None = slot cache.
    # When set, max_slots is ignored in favor of paging.max_rows and
    # cache_len becomes the per-request position HORIZON, not a
    # per-request HBM reservation
    paging: "object | None" = None
    # windowed telemetry + online per-site design re-selection
    # (repro.serve.telemetry.TelemetryConfig); requires power_monitor.
    # None = off. Read results via engine.telemetry_report(). With
    # TelemetryConfig(actuate=True) committed flips are applied to the
    # accountant between steps (closed-loop actuation)
    telemetry: "object | None" = None

    def __post_init__(self):
        if self.telemetry is not None and not self.power_monitor:
            raise ValueError(
                "ServeConfig.telemetry requires ServeConfig."
                "power_monitor=True: the windowed registry consumes the "
                "power accountant's retirement records, so telemetry "
                "without the monitor would observe nothing. Set "
                "power_monitor=True alongside telemetry=TelemetryConfig"
                "(...), or drop the telemetry config.")


class ServeEngine:
    """Continuous-batching serving over one model + one slot cache."""

    def __new__(cls, params=None, cfg=None, scfg=None, mesh=None):
        if cls is ServeEngine and scfg is not None and scfg.paging is not None:
            from .paging.engine import PagedServeEngine
            return super().__new__(PagedServeEngine)
        return super().__new__(cls)

    def __init__(self, params, cfg: ArchConfig, scfg: ServeConfig,
                 mesh=None):
        if cfg.inputs != "tokens":
            raise ValueError(
                f"ServeEngine serves token LMs; {cfg.name} has "
                f"inputs={cfg.inputs!r}")
        if scfg.kernel_backend not in mm.BACKENDS:
            raise ValueError(
                f"unknown kernel_backend {scfg.kernel_backend!r}; "
                f"expected one of {mm.BACKENDS}")
        if scfg.kernel_backend == "pallas" and jax.default_backend() == "tpu":
            raise NotImplementedError(
                "kernel_backend='pallas' cannot run on a TPU yet: Mosaic "
                "refuses the fused decode kernels gated_row_matmul, "
                "fused_matmul_counters and fused_paged_attention "
                "(repro.kernels.zvg_matmul.fused) as they are shaped "
                "today; re-shaping them is ROADMAP item S4. Use "
                "kernel_backend='ref': the power accountant still runs "
                "the Mosaic-compiled counter kernel on a TPU.")
        self.cfg = cfg
        self.scfg = scfg
        self.mesh = mesh
        if mesh is not None:
            from repro.runtime import sharding as rsh
            self.param_shardings = rsh.param_shardings(mesh, params,
                                                       serve=True)
            params = jax.device_put(params, self.param_shardings)
        else:
            self.param_shardings = None
        self.params = params
        self._build_state()        # cache + scheduler (paged overrides)
        prefill_fn = lm.make_slot_prefill_step(cfg, scfg.cache_len)
        decode_fn = lm.make_decode_step(cfg)
        embed_fn = lm.make_embed_step(cfg)
        from repro.runtime import sharding as rsh
        compute_kb = rsh.decode_compute_backend(mesh, scfg.kernel_backend)
        if mesh is None:
            # decode donates the slot cache (arg 1): steady-state decode
            # rewrites the KV rows in place instead of double-buffering.
            # Only the decode step traces under the configured kernel
            # backend: prefill/embed stay XLA on every backend (the
            # partial-bound backend arg does not shift donate indices)
            self._prefill = jax.jit(prefill_fn)
            self._decode = jax.jit(
                functools.partial(mm.with_backend, compute_kb, decode_fn),
                donate_argnums=(1,))
            self._embed = jax.jit(embed_fn)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(mesh, P())
            rep_like = lambda tree: jax.tree.map(lambda _: rep, tree)
            cache_sh = self.cache.shardings
            # prefill is batch-1 (nothing to shard but the weights): its
            # fresh states come back replicated and the scatter reshards
            # them into the slot row's layout
            self._prefill = jax.jit(
                prefill_fn,
                in_shardings=(self.param_shardings, rep, rep),
                out_shardings=(rep, rep_like(cache_sh)))
            inputs_sh = rsh.batch_shardings(
                mesh, self.cache.decode_inputs())
            # mesh decode always traces the "ref" model compute
            # (compute_kb == "ref" here; see rsh.decode_compute_backend).
            # The accountant still honors kernel_backend -- its counters
            # run on gathered local operands outside this jit, so mesh +
            # "pallas" keeps the fused counter pass and the bit-identity
            # contract
            self._decode = jax.jit(
                decode_fn,
                in_shardings=(self.param_shardings, cache_sh, inputs_sh),
                out_shardings=(rep, cache_sh),
                donate_argnums=(1,))
            # replicated out_shardings: the accountant's operand slices
            # are gathered before any counter math, so power numbers are
            # bit-identical to the single-device engine
            self._embed = jax.jit(embed_fn,
                                  in_shardings=(self.param_shardings, rep),
                                  out_shardings=rep)
        self._running: dict[int, Request] = {}
        self._temp = np.zeros(self._batch, np.float32)
        self._topk = np.zeros(self._batch, np.int32)
        self._key = jax.random.key(scfg.seed)
        mixers = {parse_spec(s)[0]
                  for s in (*cfg.pattern, *cfg.head, *cfg.tail)}
        self._pad_safe = mixers <= _PAD_SAFE_MIXERS
        # host_syncs: blocking device-to-host fetches (spans.fetch), the
        # accountant's included -- it counts into this same dict
        self.stats = {"steps": 0, "decode_steps": 0, "tokens": 0,
                      "occupancy_sum": 0, "peak_live": 0, "host_syncs": 0}
        self.accountant = (PowerAccountant(
                               scfg.monitor, scfg.power_sample_every,
                               kernel_backend=scfg.kernel_backend,
                               stats=self.stats)
                           if scfg.power_monitor else None)
        self.telemetry = None
        if scfg.telemetry is not None:
            if self.accountant is None:
                # unreachable via ServeConfig (its __post_init__ rejects
                # this pairing); kept for hand-built config objects
                raise ValueError(
                    "ServeConfig.telemetry requires power_monitor=True: "
                    "the windowed registry consumes the accountant's "
                    "retirement records")
            from .telemetry import ServeTelemetry
            self.telemetry = ServeTelemetry(scfg.telemetry, scfg.monitor)
            self.accountant.retire_hooks.append(self.telemetry.on_retire)
            if getattr(scfg.telemetry, "actuate", False):
                self.accountant.enable_actuation()
        weights = (lm.pick_monitor_weights(params)
                   if scfg.power_monitor else [])
        if mesh is not None:
            # gather the monitored weights off the mesh once: counter
            # streaming then runs on the default device with operands
            # bit-identical to the unsharded engine's
            weights = [(site, jnp.asarray(jax.device_get(w)))
                       for site, w in weights]
        self._power_weights = weights

    def _build_state(self):
        """Cache + scheduler + decode batch width (subclass hook)."""
        self._batch = self.scfg.max_slots
        self.cache = SlotCache(self.cfg, self.scfg.max_slots,
                               self.scfg.cache_len,
                               dtype=jnp.dtype(self.cfg.compute_dtype),
                               mesh=self.mesh)
        self.scheduler = FIFOScheduler(self.scfg.cache_len)

    # -------------------------------------------------------------- submit
    def submit(self, req: Request | list[int], **kw) -> Request:
        """Queue a request (or a bare prompt, with Request kwargs)."""
        if isinstance(req, Request):
            if kw:
                raise TypeError(
                    f"keyword arguments {sorted(kw)} are ignored when "
                    f"submitting a Request instance; set them on the "
                    f"Request itself")
        else:
            req = Request(prompt=list(req), **kw)
        req = self.scheduler.submit(req)
        req.submit_step = self.stats["steps"]
        return req

    # ---------------------------------------------------------------- step
    def step(self) -> list[Request]:
        """One engine iteration: admit, one shared decode, retire.
        Returns the requests retired during this step."""
        with span("step"):
            self._apply_design_swaps()
            retired: list[Request] = []
            self._admission_phase(retired)
            live = self._decode_ready(retired)
            if live:
                self._decode_phase(live, retired)
            self.stats["steps"] += 1
        return retired

    def _decode_phase(self, live: list[int], retired: list[Request]) -> None:
        inputs = self.cache.decode_inputs()
        if self.accountant is not None:
            with span("accountant"):
                self._record_decode_power(live, inputs)
        with span("decode"):
            logits, self.cache.states = self._decode(
                self.params, self.cache.states, inputs)
        with span("sample"):
            self._key, sub = jax.random.split(self._key)
            toks = np.asarray(fetch(sampling.sample_tokens(
                sub, logits, jnp.asarray(self._temp),
                jnp.asarray(self._topk)), self.stats))
        for slot in live:
            req = self._running[slot]
            tok = int(toks[slot])
            self.cache.advance(slot, tok)
            req.generated.append(tok)
            self.stats["tokens"] += 1
            self._maybe_retire(req, retired)
        self.stats["decode_steps"] += 1
        self.stats["occupancy_sum"] += len(live)
        self.stats["peak_live"] = max(self.stats["peak_live"], len(live))

    def _record_decode_power(self, live: list[int], inputs: dict) -> None:
        """Stream this step's embedded decode inputs through the
        monitored sites, when the accountant's cadence samples it."""
        if not self.accountant.tick(live):
            return
        with span("accountant.embed"):
            x = self._embed(self.params, inputs)[:, 0]
        for site, w in self._power_weights:
            self.accountant.record_decode(live, x, w, site)
        self.accountant.mark_sampled(live)

    def _apply_design_swaps(self) -> None:
        """Commit any design flips the online selector staged since the
        last step (TelemetryConfig(actuate=True)). Runs at the step
        boundary, strictly host-side -- the swap only redirects which
        design future counter recordings are priced under, so no jitted
        decode ever observes it."""
        if (self.telemetry is not None
                and getattr(self.telemetry.tcfg, "actuate", False)):
            self.telemetry.actuate_pending(self.accountant)

    def _admission_phase(self, retired: list[Request]) -> None:
        while self.cache.n_free and self.scheduler.n_pending:
            req = self.scheduler.pop_admissible(1)[0]
            with span("admit", uid=req.uid):
                self._admit(req)
            self._maybe_retire(req, retired)   # max_new == 1 / prompt EOS

    def _decode_ready(self, retired: list[Request]) -> list[int]:
        """Rows entering this step's shared decode (the paged engine
        first secures a page under every row's next write position here,
        which may preempt)."""
        return self.cache.live_slots()

    def run(self, max_steps: int = 0) -> list[Request]:
        """Pump :meth:`step` until queue and slots drain (or max_steps)."""
        finished: list[Request] = []
        while self.scheduler.n_pending or self.cache.n_live:
            finished.extend(self.step())
            if max_steps and self.stats["steps"] >= max_steps:
                break
        return finished

    # ------------------------------------------------------------ internals
    def _bucket(self, length: int) -> int:
        """Static prefill length for a prompt: explicit buckets if given,
        else next power of two. Architectures that are not pad-safe
        (recurrent state through pad tokens, local-attention ring
        eviction) ALWAYS prefill at exact length -- explicit buckets must
        not override correctness."""
        if not self._pad_safe:
            return length
        if self.scfg.prompt_buckets:
            for b in sorted(self.scfg.prompt_buckets):
                if b >= length:
                    return min(b, self.scfg.cache_len - 1)
        bucket = 1
        while bucket < length:
            bucket *= 2
        return min(bucket, self.scfg.cache_len - 1)

    def _admit(self, req: Request) -> None:
        slot = self.cache.allocate()
        req.slot = slot
        req.status = RequestStatus.RUNNING
        req.start_step = self.stats["steps"]
        length = req.prompt_len
        bucket = max(self._bucket(length), length)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :length] = req.prompt
        logits, states1 = self._prefill(
            self.params, {"tokens": jnp.asarray(toks)}, np.int32(length))
        first = self._sample_first(req, logits)
        self.cache.write_prefill(slot, states1, first, length)
        req.generated.append(first)
        self.stats["tokens"] += 1
        self._running[slot] = req
        if self.accountant is not None:
            with span("accountant"):
                self.accountant.begin(slot, req.uid, length)
                self._record_prefill_power(slot, toks, 0, length)

    def _sample_first(self, req: Request, logits) -> int:
        """Install the request's sampling params on its slot and draw its
        first token from batch-1 prefill logits."""
        slot = req.slot
        self._temp[slot] = req.sampling.temperature
        self._topk[slot] = req.sampling.top_k
        self._key, sub = jax.random.split(self._key)
        return int(fetch(sampling.sample_tokens(
            sub, logits, jnp.full((1,), req.sampling.temperature,
                                  jnp.float32),
            jnp.full((1,), req.sampling.top_k, jnp.int32)), self.stats)[0])

    def _record_prefill_power(self, slot: int, toks: np.ndarray,
                              lo: int, length: int) -> None:
        """Stream the prompt rows ``[lo, length)`` of a bucketed token
        array through the monitored sites (one record_prefill per site).

        Embeds the SAME bucketed token array prefill just consumed (one
        compile per bucket, not per distinct prompt length); the slice
        back to the real rows is exact -- embedding is per-token, so
        padding never leaks into ``[lo, length)``. ``lo > 0`` is the
        prefix-reuse case: the request pays only for the suffix it
        actually computed (the first-payer contract)."""
        with span("accountant.embed"):
            x = self._embed(self.params,
                            {"tokens": jnp.asarray(toks)})[:, lo:length]
        for site, w in self._power_weights:
            self.accountant.record_prefill(slot, x, w, site)

    def _maybe_retire(self, req: Request, retired: list[Request]) -> None:
        reason = self.scheduler.retire_reason(
            req, int(self.cache.positions[req.slot]), self.scfg.eos_id)
        if not reason:
            return
        self._retire(req, reason, retired)

    def _retire(self, req: Request, reason: str,
                retired: list[Request]) -> None:
        with span("retire", uid=req.uid):
            slot = req.slot
            if self.accountant is not None:
                with span("accountant"):
                    req.power = self.accountant.finish(
                        slot, len(req.generated))
            self._release_slot(slot)
            self._temp[slot] = 0.0
            self._topk[slot] = 0
            self._running.pop(slot)
            req.status = RequestStatus.FINISHED
            req.finish_reason = reason
            req.finish_step = self.stats["steps"]
            retired.append(req)

    def _release_slot(self, slot: int) -> None:
        self.cache.release(slot)

    def cancel(self, uid: int) -> bool:
        """Drop a request that has not been admitted yet (the slot cache
        never evicts running work; the paged engine extends cancel to
        running and preempted requests)."""
        return self.scheduler.cancel(uid)

    # -------------------------------------------------------------- views
    def trace_report(self):
        """Serve-wide paper-style TraceReport over all monitored traffic
        (requires power_monitor=True). In mesh mode this already
        aggregates across the mesh: counters are booked from gathered
        operand slices scaled to the full operand extent, so the
        serve-wide numbers equal the single-device engine's exactly."""
        if self.accountant is None:
            raise RuntimeError("power_monitor is off")
        from repro.trace.report import build_report
        report = build_report(self.accountant.capture,
                              model=f"serve/{self.cfg.name}")
        # closed-loop runs additionally carry the "actuated" pseudo-
        # design: each site's traffic priced under the design active at
        # each recording (sums the per-request actuated energies exactly)
        self.accountant.inject_actuated(report)
        return report

    def telemetry_report(self) -> dict:
        """Finalize and return the telemetry roll-up (windows + flip
        timeline + fixed/online/oracle savings tracks); requires
        ``ServeConfig.telemetry``. Finalization closes still-open
        windows as partial and fills the oracle-static track, so call
        this after the run drains."""
        if self.telemetry is None:
            raise RuntimeError(
                "telemetry is off (set ServeConfig.telemetry to a "
                "TelemetryConfig)")
        return self.telemetry.report()

    def occupancy(self) -> float:
        """Mean live slots per decode step (batch efficiency)."""
        d = max(self.stats["decode_steps"], 1)
        return self.stats["occupancy_sum"] / d
