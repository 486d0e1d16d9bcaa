"""Per-request streaming-power accounting for the serving engine.

The question PR 1's tracer could not answer: *what does the paper's
BIC + ZVG save per served request*, over the operand streams that request
actually produced -- its own prompt at prefill, its own sampled tokens at
every decode step, with the switching statistics of serving traffic rather
than training batches.

Mechanism: the engine hands the accountant one (activations, weight)
operand pair per monitored site per decode step -- activations ``[B, K]``
with one row per KV slot. A single jitted+vmapped ``stream_counters`` call
models all rows at once; rows of live slots are credited to the request
occupying that slot, scaled back to the full operand extent exactly like
:mod:`repro.trace.capture` scales sampled operands. Counters accumulate as
flat host-side floats per (slot, site); retirement freezes them into a
:class:`RequestPowerReport` whose ratios are computed energies-first (the
paper's aggregation rule). At retirement the request's (extrapolated)
per-site counters are ALSO booked into a :class:`repro.trace.TraceCapture`
keyed by site name, so the engine can emit a serve-wide paper-style report
with the identical machinery that traces training models -- and, because
both views are frozen from the same per-request sums, request-level
energies add up to the serve-wide aggregate exactly, at ANY sampling
cadence (the serve-wide report therefore covers *retired* requests).

Sampling cadence: with ``sample_every = k`` only every k-th decode step is
streamed through the SA model; retirement extrapolates decode-site
energies by ``steps / sampled_steps`` (the same stationarity argument as
capture's ``max_calls_per_site``). Ratios are unaffected; energies are
estimates marked by ``sampled_steps < decode_steps`` in the report.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import monitor
from repro.trace.capture import CaptureConfig, TraceCapture

from .spans import fetch, span

#: name of the pseudo-design that prices each counter recording under
#: the design that was ACTIVE when it was recorded (closed-loop online
#: actuation, repro.serve.telemetry); rides report machinery like
#: design.select's "selected"
ACTUATED = "actuated"

#: components an actuated pricing carries: the monitor's energy
#: components plus the h/v pipeline-toggle counts trace reports quote
ACTUATED_COMPONENTS = monitor.COMPONENTS + ("h", "v")


def _epoch_energy(design: str, counters: dict) -> dict[str, float]:
    """Price one swap epoch's counter sub-sums under its design."""
    comps = monitor.counters_to_energy(dict(counters)).get(design, {})
    out = {c: float(comps.get(c, 0.0)) for c in monitor.COMPONENTS}
    out["h"] = float(counters.get(f"h/{design}", 0.0))
    out["v"] = float(counters.get(f"v/{design}", 0.0))
    return out


def actuated_site_energy(record: "SiteRecord",
                         primary: str) -> dict[str, float]:
    """Price one frozen site record AS RECORDED: each swap epoch's
    counter sub-sums under the design that was active when they were
    recorded (the in-flight attribution rule -- a request spanning a
    swap is priced under the old design for its pre-swap recordings and
    the new one after). One shared function for the live accountant and
    every offline consumer, with one float-addition order, so replays
    reproduce actuated energies bit for bit. Records without epochs
    (schema-v1 dumps) price entirely under ``primary``."""
    epochs = record.epochs or ((primary, record.counters),)
    total: dict[str, float] | None = None
    for design, counters in epochs:
        e = _epoch_energy(design, counters)
        if total is None:
            total = e
        else:
            for c in total:
                total[c] += e[c]
    return total if total is not None else dict.fromkeys(
        ACTUATED_COMPONENTS, 0.0)


def actuated_stream_energy(records, primary: str) -> float:
    """Total actuated energy (fJ) of a retirement-record stream: per
    (site, active design) counter sub-sums are merged across the stream
    FIRST, then priced -- the same sum-counters-then-price grouping the
    selector's fixed/online window tracks use, so on a swap-free stream
    the actuated total equals the fixed-primary total bit for bit (each
    record's single primary epoch carries the identical floats as its
    flat counters)."""
    by_site: dict[str, dict[str, dict[str, float]]] = {}
    for rec in records:
        for sr in rec.sites:
            site = by_site.setdefault(sr.site, {})
            for design, counters in sr.epochs or ((primary, sr.counters),):
                sub = site.setdefault(design, {})
                for k, v in counters.items():
                    if k == "zero_fraction":
                        continue
                    sub[k] = sub.get(k, 0.0) + float(v)
    total = 0.0
    for site, designs in by_site.items():
        for design, counters in designs.items():
            total += float(monitor.counters_to_energy(counters)
                           .get(design, {}).get("total", 0.0))
    return total


def gather_local(a, stats: dict):
    """Bring a (possibly mesh-sharded) operand onto the default device
    (the fetch counts in ``stats["host_syncs"]``).

    The accountant's contract is that its numbers are sums of
    ``monitor.stream_counters`` outputs -- the SAME outputs whether the
    engine runs on one device or a mesh. Counter math over locally
    re-assembled operands guarantees that: the gather is exact (no
    arithmetic), and the jitted counter kernels then see bit-identical
    inputs on the same (single-device) partitioning either way. No-op
    for anything already on one device.
    """
    if isinstance(a, jax.Array) and len(a.sharding.device_set) > 1:
        return jnp.asarray(fetch(a, stats))
    return a


@partial(jax.jit, static_argnames=("mcfg",))
def _rows_counters(A: jax.Array, W: jax.Array,
                   mcfg: monitor.MonitorConfig) -> dict:
    """Per-row flat counters: ``A [B, K]`` rows each streamed against
    ``W [K, N]``. Returns a dict of ``[B]`` arrays.

    Legacy whole-graph path, kept as the fallback for multi-geometry
    design menus; single-geometry configs (the default) go through the
    counter-producer/assembler split below so the reference and fused
    Pallas backends share one compiled pricing step bit-for-bit.
    """
    def one(a):
        a2, w2 = monitor.subsample_operands(a[None, :], W, mcfg)
        return monitor.stream_counters(a2, w2, mcfg)

    return jax.vmap(one)(A)


def fused_decode_supported(mcfg: monitor.MonitorConfig) -> bool:
    """Whether the counter-producer/assembler decode split (and hence
    the fused Pallas decode pass) can price this config.

    The split walks ONE stream geometry per pass; a design list spanning
    multiple geometries/precisions needs one pass each, which only the
    legacy :func:`_rows_counters` fallback does, and the decode counter
    producers bitcast native bf16 streams, so non-bf16 groups also fall
    back. (The default paper-pair menu is single-geometry bf16, so
    serving configs hit the split path.)
    """
    from repro.design.evaluate import menu_args
    groups = menu_args(mcfg.design_list)
    if len(groups) != 1:
        return False
    ((_, precision),) = groups.keys()
    return precision == "bf16"


def _decode_menu(mcfg: monitor.MonitorConfig):
    """Static decode-menu plumbing of a single-geometry config:
    ``(geometry, menu kwargs, west CounterSpec, north CounterSpec)``."""
    from repro.design.evaluate import menu_args
    from repro.kernels.power_counters.spec import CounterSpec
    ((geom, _precision), kw), = menu_args(mcfg.design_list).items()
    return (geom, kw,
            CounterSpec(bic_variants=kw["west_bic"], zvg=kw["west_zvg"]),
            CounterSpec(bic_variants=kw["north_bic"],
                        zvg=kw["north_zvg"]))


def _subsample_decode(A, W, mcfg: monitor.MonitorConfig):
    """Batched twin of the per-row ``subsample_operands``: the strided
    take along each axis commutes with the row batch, so every row sees
    exactly the reference path's sample."""
    A2 = monitor._subsample(A, mcfg.max_depth, 1)
    W2 = monitor._subsample(
        monitor._subsample(W, mcfg.max_depth, 0), mcfg.max_cols, 1)
    return A2, W2


def _pad_lanes(bits, lanes: int):
    if lanes > bits.shape[1]:
        bits = jnp.concatenate(
            [bits, jnp.zeros((bits.shape[0], lanes - bits.shape[1]),
                             jnp.uint16)], axis=1)
    return bits


@partial(jax.jit, static_argnames=("mcfg",))
def _ref_decode_counters(A: jax.Array, W: jax.Array,
                         mcfg: monitor.MonitorConfig):
    """Reference counter producer: the decode streams' per-lane integer
    counters via :func:`repro.kernels.power_counters.edge_counters`
    (the config's counter backend), one west stream per request row
    plus the shared north/weight stream. Returns ``(west_counts
    int32[B, n_rows_w, R], west_rowzeros int32[B, K], north_counts
    int32[n_rows_n, Np], north_rowzeros int32[K])`` -- the same
    contract as the fused Pallas producer, feeding the same assembler.
    """
    from repro.core.bits import to_bits
    from repro.kernels import power_counters as pc

    geom, _, wspec, nspec = _decode_menu(mcfg)
    A2, W2 = _subsample_decode(A, W, mcfg)
    R, C = geom.rows, geom.cols
    lanes_n = -(-W2.shape[1] // C) * C

    wb = _pad_lanes(to_bits(W2), lanes_n)
    nrows = pc.edge_counters(wb, nspec, backend=mcfg.backend)
    nc = jnp.stack([nrows[name] for name in nspec.rows], axis=0)

    def one(row_bits):
        x_w = jnp.concatenate(
            [row_bits[:, None],
             jnp.zeros((row_bits.shape[0], R - 1), jnp.uint16)], axis=1)
        rows = pc.edge_counters(x_w, wspec, backend=mcfg.backend)
        return (jnp.stack([rows[name] for name in wspec.rows], axis=0),
                rows["rowzeros"])

    wc, wz = jax.vmap(one)(to_bits(A2))
    return wc, wz, nc, nrows["rowzeros"]


@partial(jax.jit, static_argnames=("mcfg",))
def _fused_decode_counters(A: jax.Array, W: jax.Array,
                           mcfg: monitor.MonitorConfig):
    """Fused counter producer: ONE Pallas pass emits the (ZVG-gated)
    decode products AND the same per-lane integer counters as
    :func:`_ref_decode_counters` (bit-identical by the power_counters
    differential contract). Returns ``(wc, wz, nc, nz, product)``."""
    from repro.kernels.zvg_matmul.fused import fused_matmul_counters

    geom, _, wspec, nspec = _decode_menu(mcfg)
    A2, W2 = _subsample_decode(A, W, mcfg)
    product, wc, wz, nc, nz = fused_matmul_counters(
        A2, W2, wspec, nspec, geom.rows, geom.cols)
    return wc, wz, nc, nz, product


@partial(jax.jit, static_argnames=("mcfg", "ns"))
def _assemble_decode(wc, wz, nc, nz, mcfg: monitor.MonitorConfig,
                     ns: int):
    """Price the per-lane integer counters into per-row flat counter
    dicts (the :func:`monitor.stream_counters` contract).

    This is ONE jitted function shared by both counter producers: both
    feed identically-shaped integer arrays into the identical compiled
    executable, so the reference and fused decode paths emit
    bit-identical energies by construction (float assembly happens
    exactly once, here). ``ns`` is the subsampled weight-column count
    (the unpadded N of the stream facts).
    """
    from repro.design.evaluate import design_energy
    from repro.core import systolic

    geom, kw, wspec, nspec = _decode_menu(mcfg)
    n_rows = {name: nc[i] for i, name in enumerate(nspec.rows)}
    n_menu = systolic.menu_lane_sums(n_rows, "n", kw["north_bic"],
                                     kw["north_zvg"])
    Kd = wz.shape[1]
    designs = mcfg.design_list

    def assemble(wc_b, wz_b):
        w_rows = {name: wc_b[i] for i, name in enumerate(wspec.rows)}
        menu = systolic.menu_lane_sums(w_rows, "w", kw["west_bic"],
                                       kw["west_zvg"])
        menu.update(n_menu)
        menu.update(systolic.stream_facts(geom, 1, Kd, ns, wz_b, nz))
        ev = {d.name: design_energy(menu, d) for d in designs}
        return monitor.flatten_evaluated(ev, mcfg.design_names)

    return jax.vmap(assemble)(wc, wz)


@dataclasses.dataclass(frozen=True)
class SiteRecord:
    """One monitored site's frozen contribution to one retired request:
    exactly the ``(site, kind, shape, counters)`` tuple the accountant
    books into the serve-wide capture at retirement -- same floats, same
    order -- so replaying SiteRecords through ``record_counters``
    reproduces the capture bit-for-bit."""
    site: str
    kind: str
    shape: tuple[int, ...]
    counters: dict           # flat counters incl. "zero_fraction"
    #: swap-epoch split of ``counters``: ``((design, sub_counters), ...)``
    #: where each sub-dict holds the recordings made while that design
    #: was the site's active choice. Sub-sums are accumulated in the same
    #: float-addition order as ``counters``, so on a swap-free life the
    #: single epoch's floats equal ``counters`` bit for bit. Empty on
    #: records dumped before actuation existed (schema v1).
    epochs: tuple = ()

    def to_json_dict(self) -> dict:
        return {"site": self.site, "kind": self.kind,
                "shape": list(self.shape), "counters": dict(self.counters),
                "epochs": [[d, dict(c)] for d, c in self.epochs]}

    @classmethod
    def from_json_dict(cls, d: dict) -> "SiteRecord":
        return cls(site=d["site"], kind=d["kind"],
                   shape=tuple(d["shape"]), counters=dict(d["counters"]),
                   epochs=tuple((e[0], dict(e[1]))
                                for e in d.get("epochs", [])))


@dataclasses.dataclass(frozen=True)
class RetirementRecord:
    """Everything one retirement contributes to serve-wide accounting, as
    plain data: the unit the windowed-telemetry registry partitions
    (:mod:`repro.serve.telemetry`). Emitted to every hook in
    ``PowerAccountant.retire_hooks`` at the same moment the counters are
    booked into the capture, so window sums and ``trace_report()`` are
    two views of the one retirement stream."""
    uid: int
    prompt_tokens: int
    new_tokens: int
    decode_steps: int
    sampled_steps: int
    sites: tuple[SiteRecord, ...]

    def to_json_dict(self) -> dict:
        return {"uid": self.uid, "prompt_tokens": self.prompt_tokens,
                "new_tokens": self.new_tokens,
                "decode_steps": self.decode_steps,
                "sampled_steps": self.sampled_steps,
                "sites": [s.to_json_dict() for s in self.sites]}

    @classmethod
    def from_json_dict(cls, d: dict) -> "RetirementRecord":
        return cls(uid=d["uid"], prompt_tokens=d["prompt_tokens"],
                   new_tokens=d["new_tokens"],
                   decode_steps=d["decode_steps"],
                   sampled_steps=d["sampled_steps"],
                   sites=tuple(SiteRecord.from_json_dict(s)
                               for s in d["sites"]))


@dataclasses.dataclass
class RequestPowerReport:
    """Frozen power outcome of one retired request (energies in fJ,
    extrapolated to the full operand extent and all decode steps)."""
    uid: int
    prompt_tokens: int
    new_tokens: int
    decode_steps: int          # decode steps this request was live for
    sampled_steps: int         # of which were streamed through the model
    energy: dict               # {design name: {component: fJ}}
    zero_fraction: float       # mean over sampled (site, step) records
    sites: tuple[str, ...]     # monitored site names
    reference: str = "baseline"   # savings denominator design
    primary: str = "proposed"     # headline design for the twin ratios

    def saving(self, design: str, component: str = "total") -> float:
        b = self.energy[self.reference][component]
        return 1.0 - self.energy[design][component] / max(b, 1e-30)

    @property
    def saving_total(self) -> float:
        return self.saving(self.primary)

    @property
    def saving_streaming(self) -> float:
        return self.saving(self.primary, "streaming")

    @property
    def streaming_share(self) -> float:
        return (self.energy[self.reference]["streaming"]
                / max(self.energy[self.reference]["total"], 1e-30))

    def summary(self) -> dict:
        return {
            "prompt_tokens": self.prompt_tokens,
            "new_tokens": self.new_tokens,
            "sampled_steps": self.sampled_steps,
            "saving_total": self.saving_total,
            "saving_streaming": self.saving_streaming,
            "streaming_share": self.streaming_share,
            "zero_fraction": self.zero_fraction,
            "energy_base_fj": self.energy[self.reference]["total"],
            "energy_prop_fj": self.energy[self.primary]["total"],
            "design_savings": {d: self.saving(d) for d in self.energy
                               if d != self.reference},
        }


class _SiteRec:
    """Summed flat counters for one (slot, site), plus the site's operand
    shape ``(B, M, K, N)`` so retirement can book honest MAC counts."""

    def __init__(self, shape: tuple[int, int, int, int]):
        self.shape = shape
        self.counters: dict[str, float] = {}
        # active-design sub-sums (swap epochs): design -> counters added
        # while that design was the site's choice, accumulated with the
        # same float-addition order as ``counters`` so a single-design
        # life's sub-sum IS ``counters`` bit for bit
        self.priced: dict[str, dict[str, float]] = {}
        self.zf_sum = 0.0
        self.zf_n = 0

    def add(self, counters: dict, zf: float, design: str):
        sub = self.priced.setdefault(design, {})
        for k, v in counters.items():
            self.counters[k] = self.counters.get(k, 0.0) + v
            sub[k] = sub.get(k, 0.0) + v
        self.zf_sum += zf
        self.zf_n += 1

    @property
    def zf_mean(self) -> float:
        return self.zf_sum / max(self.zf_n, 1)


class _SlotAcc:
    """Mutable per-slot accumulator while its request is live."""

    def __init__(self, uid: int, prompt_tokens: int):
        self.uid = uid
        self.prompt_tokens = prompt_tokens
        self.decode_steps = 0
        self.sampled_steps = 0
        self.due = False           # current step is sampled for this slot
        # site -> _SiteRec; decode sites extrapolate at finish
        self.prefill: dict[str, _SiteRec] = {}
        self.decode: dict[str, _SiteRec] = {}


class PowerAccountant:
    """Per-slot incremental accounting, one live request per slot."""

    def __init__(self, mcfg: monitor.MonitorConfig = monitor.DEFAULT_MONITOR,
                 sample_every: int = 1, kernel_backend: str = "ref",
                 stats: dict | None = None):
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1: {sample_every}")
        if kernel_backend not in ("ref", "pallas"):
            raise ValueError(
                f"unknown kernel_backend {kernel_backend!r}; "
                f"expected 'ref' or 'pallas'")
        self.mcfg = mcfg
        self.sample_every = sample_every
        # counts the accountant's device-to-host fetches under
        # "host_syncs"; the engine passes its own stats dict
        self.stats = {"host_syncs": 0} if stats is None else stats
        # decode accounting uses the counter-producer/assembler split for
        # single-geometry menus (the fused matmul+counter Pallas pass
        # when kernel_backend="pallas", the edge_counters reference
        # otherwise -- bit-identical, both feed the SAME compiled
        # assembler); multi-geometry menus fall back to the legacy
        # per-row stream_counters path on either backend. Prefill
        # always stays on the reference path -- its row budget is
        # request-shaped, not batch-shaped.
        self.kernel_backend = kernel_backend
        self._split = fused_decode_supported(mcfg)
        self._fused = kernel_backend == "pallas" and self._split
        self._global_step = 0
        self._slots: dict[int, _SlotAcc] = {}
        # serve-wide registry (paper-style report over ALL traffic)
        self.capture = TraceCapture(CaptureConfig(monitor=mcfg))
        # retirement-stream observers: each callable receives the
        # RetirementRecord of every finished request, AFTER its counters
        # were booked into the capture (the telemetry registry's feed)
        self.retire_hooks: list = []
        # ------------------------------------------ closed-loop actuation
        # per-site active design (full "prefill/x"/"decode/x" names);
        # sites absent from the map price under the fixed primary
        self.actuation_enabled = False
        self.swap_epoch = 0
        self._site_design: dict[str, str] = {}
        self.swap_log: list[tuple[int, dict[str, str]]] = []
        # request-major actuated totals (retirement order -- the sum of
        # per-request actuated energies, bit for bit) and site-major
        # actuated totals (per-site retirement order, for trace-report
        # injection); both only fed while actuation is enabled
        self._act_totals: dict[str, float] = dict.fromkeys(
            ACTUATED_COMPONENTS, 0.0)
        self._act_sites: dict[str, dict[str, float]] = {}

    # ----------------------------------------------------------- actuation
    def enable_actuation(self) -> None:
        """Turn on epoch-priced accounting: every retirement gains an
        ``"actuated"`` energy entry pricing each recording under the
        design active when it was made, and :meth:`apply_swaps` becomes
        legal. Enable before any traffic so the actuated track covers
        every retired request."""
        if ACTUATED in self.mcfg.design_names:
            raise ValueError(
                f"design name {ACTUATED!r} is reserved for the actuated "
                f"pricing track; rename the configured design")
        self.actuation_enabled = True

    def design_for(self, site: str) -> str:
        """The design currently pricing ``site`` (full
        ``prefill/...``/``decode/...`` name)."""
        return self._site_design.get(site, self.mcfg.primary_design)

    def apply_swaps(self, mapping: dict[str, str]) -> int:
        """Atomically swap the active design of the given sites (full
        site name -> design). Host-side bookkeeping only -- call it
        between engine steps, never inside a jitted decode; recordings
        already accumulated keep their old design (the in-flight
        attribution rule), subsequent ones price under the new choice.
        Returns the swap epoch (unchanged if the mapping is a no-op)."""
        if not self.actuation_enabled:
            raise RuntimeError(
                "apply_swaps requires enable_actuation() first")
        known = set(self.mcfg.design_names)
        bad = sorted(set(mapping.values()) - known)
        if bad:
            raise KeyError(f"unknown designs in swap: {bad}; "
                           f"configured: {sorted(known)}")
        changed = {s: d for s, d in mapping.items()
                   if self.design_for(s) != d}
        if not changed:
            return self.swap_epoch
        self.swap_epoch += 1
        self._site_design.update(changed)
        self.swap_log.append((self.swap_epoch, changed))
        return self.swap_epoch

    def actuated_totals(self) -> dict[str, float]:
        """Serve-wide actuated energy components: the request-major
        accumulation, equal bit for bit to summing every retired
        request's ``energy["actuated"]`` in retirement order."""
        return dict(self._act_totals)

    def inject_actuated(self, report) -> None:
        """Add the ``"actuated"`` pseudo-design to a serve-wide
        :class:`repro.trace.TraceReport` in place, from the site-major
        actuated sums -- the same floats the per-request reports carry,
        re-grouped by site in per-site retirement order."""
        if not self.actuation_enabled:
            return
        for s in report.sites:
            tot = self._act_sites.get(
                s.name, dict.fromkeys(ACTUATED_COMPONENTS, 0.0))
            s.designs[ACTUATED] = {"total": tot["total"],
                                   "streaming": tot["streaming"],
                                   "h": tot["h"], "v": tot["v"]}
        if ACTUATED not in report.designs:
            report.designs = tuple(report.designs) + (ACTUATED,)

    # ----------------------------------------------------------- lifecycle
    def begin(self, slot: int, uid: int, prompt_tokens: int) -> None:
        self._slots[slot] = _SlotAcc(uid, prompt_tokens)

    def suspend(self, slot: int) -> _SlotAcc:
        """Detach a preempted request's accumulator WITHOUT booking it:
        nothing reaches the serve-wide capture until the request finally
        retires, so preemption cannot double-count or leak energy. Hand
        the accumulator back via :meth:`resume` at re-admission."""
        return self._slots.pop(slot)

    def resume(self, slot: int, acc: _SlotAcc) -> None:
        """Re-attach a suspended accumulator to the request's new slot.
        Subsequent record_prefill calls (the re-prefill of prompt +
        generated-so-far) ADD to the suspended sums -- recomputed KV is
        honestly paid-for energy, exactly what preemption costs."""
        if slot in self._slots:
            raise RuntimeError(f"slot {slot} already accounted")
        self._slots[slot] = acc

    def finish(self, slot: int, new_tokens: int) -> RequestPowerReport:
        """Freeze the slot's sums into a report AND book the same frozen,
        extrapolated per-site counters into the serve-wide capture (one
        record_counters call per site per request, so capture totals equal
        the sum of retired requests' reports by construction)."""
        return self.finish_detached(self._slots.pop(slot), new_tokens)

    def finish_detached(self, acc: _SlotAcc,
                        new_tokens: int) -> RequestPowerReport:
        """Freeze a (possibly suspended) accumulator directly -- the
        retirement path for a request cancelled while preempted, which
        holds real prefill energy but occupies no slot."""
        scale = acc.decode_steps / max(acc.sampled_steps, 1)
        total: dict[str, float] = {}
        zf_sum = zf_n = 0.0
        site_records: list[SiteRecord] = []
        for site, rec in acc.prefill.items():
            for k, v in rec.counters.items():
                total[k] = total.get(k, 0.0) + v
            zf_sum += rec.zf_sum
            zf_n += rec.zf_n
            site_records.append(SiteRecord(
                site, "dot_general", rec.shape,
                {**rec.counters, "zero_fraction": rec.zf_mean},
                epochs=tuple((d, dict(sub))
                             for d, sub in rec.priced.items())))
        for site, rec in acc.decode.items():
            scaled = {k: v * scale for k, v in rec.counters.items()}
            for k, v in scaled.items():
                total[k] = total.get(k, 0.0) + v
            zf_sum += rec.zf_sum
            zf_n += rec.zf_n
            # MACs extrapolate with the energies: all decode steps count
            shape = (acc.decode_steps,) + rec.shape[1:]
            site_records.append(SiteRecord(
                site, "dot_general", shape,
                {**scaled, "zero_fraction": rec.zf_mean},
                # epoch sub-sums extrapolate exactly like the totals:
                # the same per-key float is scaled by the same factor
                epochs=tuple((d, {k: v * scale for k, v in sub.items()})
                             for d, sub in rec.priced.items())))
        # ONE frozen per-site record set, booked into the capture AND
        # handed to every retirement hook: the serve-wide report and any
        # windowed view are sums over the identical floats
        for sr in site_records:
            self.capture.record_counters(sr.site, sr.kind, sr.shape,
                                         sr.counters)
        retirement = RetirementRecord(
            uid=acc.uid, prompt_tokens=acc.prompt_tokens,
            new_tokens=new_tokens, decode_steps=acc.decode_steps,
            sampled_steps=acc.sampled_steps, sites=tuple(site_records))
        for hook in self.retire_hooks:
            hook(retirement)
        energy = monitor.counters_to_energy(total)
        # zero-fill every configured design so a request that retired with
        # no sampled records still yields a well-formed (all-zero) report
        for name in self.mcfg.design_names:
            comps = energy.setdefault(name, {})
            for c in monitor.COMPONENTS:
                comps.setdefault(c, 0.0)
        if self.actuation_enabled:
            # price the request AS RECORDED (each epoch under its active
            # design), feeding both serve-wide actuated accumulations:
            # request-major (this request's total, added once) and
            # site-major (per site, for trace-report injection)
            req_e = dict.fromkeys(ACTUATED_COMPONENTS, 0.0)
            for sr in site_records:
                e = actuated_site_energy(sr, self.mcfg.primary_design)
                site_tot = self._act_sites.setdefault(
                    sr.site, dict.fromkeys(ACTUATED_COMPONENTS, 0.0))
                for c in ACTUATED_COMPONENTS:
                    req_e[c] += e[c]
                    site_tot[c] += e[c]
            for c in ACTUATED_COMPONENTS:
                self._act_totals[c] += req_e[c]
            energy[ACTUATED] = {c: req_e[c] for c in monitor.COMPONENTS}
        return RequestPowerReport(
            uid=acc.uid, prompt_tokens=acc.prompt_tokens,
            new_tokens=new_tokens, decode_steps=acc.decode_steps,
            sampled_steps=acc.sampled_steps,
            energy=energy,
            zero_fraction=zf_sum / max(zf_n, 1),
            sites=tuple(sorted(set(acc.prefill) | set(acc.decode))),
            reference=self.mcfg.reference_design,
            primary=self.mcfg.primary_design)

    # ----------------------------------------------------------- recording
    def record_prefill(self, slot: int, acts: jax.Array, weight: jax.Array,
                       site: str) -> None:
        """One prefill matmul for the slot's request: ``acts [..., K]`` (the
        request's real prompt rows only -- no padding), ``weight [K, N]``."""
        with span("accountant.counters"):
            acts = gather_local(acts, self.stats)
            weight = gather_local(weight, self.stats)
            A = acts.reshape(-1, acts.shape[-1])
            m = A.shape[0]
            # pre-sample rows to a power-of-two budget so the jitted
            # stream model compiles O(log max_rows) shapes total, not one
            # per distinct prompt length (the accounting analogue of the
            # engine's prefill buckets); even-stride sampling +
            # back-scaling keeps ratios exact and totals unbiased
            ms = 1 << (min(m, self.mcfg.max_rows).bit_length() - 1)
            a2, w2 = monitor.subsample_operands(
                monitor._subsample(A, ms, 0), weight, self.mcfg)
            out = monitor.stream_counters(a2, w2, self.mcfg)
        fetched = fetch(out, self.stats)
        with span("accountant.book"):
            counters = {k: float(v) for k, v in fetched.items()}
            zf = counters.pop("zero_fraction")
            factor = monitor.sampled_fraction_scale(
                m, A.shape[1], weight.shape[1], self.mcfg, sampled_m=ms)
            scaled = {k: v * factor for k, v in counters.items()}
            acc = self._slots[slot]
            name = f"prefill/{site}"
            rec = acc.prefill.get(name)
            if rec is None:
                rec = acc.prefill[name] = _SiteRec(
                    (1, A.shape[0], A.shape[1], weight.shape[1]))
            else:
                # a re-prefill after preemption streams more rows through
                # the same site: grow the booked MAC extent with the energy
                rec.shape = (1, rec.shape[1] + A.shape[0],
                             rec.shape[2], rec.shape[3])
            rec.add(scaled, zf, self.design_for(name))

    def tick(self, slots: list[int]) -> bool:
        """Advance live slots by one decode step; True when this step
        should be sampled (engine then calls :meth:`record_decode`).

        The cadence is keyed to the GLOBAL decode-step counter -- not
        per-request -- so staggered admissions cannot phase-shift every
        step into being due and the accounting work really runs ~1/k of
        the time. A request's first decode step is always sampled, so
        short-lived requests admitted between sample points still get a
        decode energy estimate.
        """
        self._global_step += 1
        due_global = (self._global_step - 1) % self.sample_every == 0
        sample = False
        for s in slots:
            acc = self._slots[s]
            acc.decode_steps += 1
            acc.due = due_global or acc.decode_steps == 1
            sample = sample or acc.due
        return sample

    def record_decode(self, slots: list[int], acts: jax.Array,
                      weight: jax.Array, site: str) -> None:
        """One decode-step matmul across the whole batch: ``acts [B, K]``
        (row per KV slot), ``weight [K, N]``. Only rows in ``slots`` are
        credited; the step must have been announced with :meth:`tick`."""
        with span("accountant.counters"):
            A = gather_local(acts, self.stats)
            W = gather_local(weight, self.stats)
            if self._split:
                if self._fused:
                    wc, wz, nc, nz, _ = _fused_decode_counters(A, W,
                                                               self.mcfg)
                else:
                    wc, wz, nc, nz = _ref_decode_counters(A, W, self.mcfg)
                out = _assemble_decode(wc, wz, nc, nz, self.mcfg,
                                       min(W.shape[1], self.mcfg.max_cols))
            else:
                out = _rows_counters(A, W, self.mcfg)
        per_row = fetch(out, self.stats)
        with span("accountant.book"):
            for s in slots:
                acc = self._slots[s]
                if not acc.due:
                    continue
                row = {k: float(v[s]) for k, v in per_row.items()}
                zf = row.pop("zero_fraction")
                factor = monitor.sampled_fraction_scale(
                    1, acts.shape[1], weight.shape[1], self.mcfg)
                scaled = {k: v * factor for k, v in row.items()}
                name = f"decode/{site}"
                rec = acc.decode.setdefault(
                    name, _SiteRec((1, 1, acts.shape[1], weight.shape[1])))
                rec.add(scaled, zf, self.design_for(name))

    def mark_sampled(self, slots: list[int]) -> None:
        """Book that this step's records covered these slots (called once
        per sampled step, after the per-site record_decode calls)."""
        for s in slots:
            acc = self._slots[s]
            if acc.due:
                acc.sampled_steps += 1

