"""Pallas TPU kernel: fused power-counter pass over one operand stream.

One tiled walk over a ``uint16[T, L]`` stream emits EVERY counter of the
design menu (see :class:`.spec.CounterSpec`): raw and mantissa-field
transitions, zero counts, zero-held (ZVG) register transitions and
is-zero line toggles, per-variant BIC data + invert-line toggles over
both the raw and the zero-held stream, and per-bit ones histograms.
That replaces O(menu) separate passes -- each with its own sequential
``lax.scan`` -- by a single bandwidth-bound kernel.

The kernel has two in-block algorithms, selected by the static ``algo``
argument (both bit-exact, differentially tested against each other and
``ref.py``):

* ``"parallel"`` -- the TPU form, and the one Mosaic compiles: both
  sequential recurrences become log-step prefix scans (log-depth, fully
  lane-vectorized, words widened to int32), and the scan count is
  MENU-SIZE-INDEPENDENT (three per block).
* ``"scan"`` -- the CPU/interpret form (Mosaic refuses its in-kernel
  ``lax.scan``, so it is never the compiled default): ONE ``lax.scan``
  over the block's cycles computes every counter of every menu entry
  per step.
  A sequential scan is what XLA:CPU compiles best (single fused loop,
  row-sized working set); doing ALL menu entries in that one loop is
  exactly the fused-pass win over the reference's per-menu-entry scans.

The parallel form's recurrences:

* BIC: inverting a segment flips all of its bits, so the invert decision
  is a composition of per-step boolean functions of the previous state --
  a prefix scan over (f(0), f(1)) pairs (the identity proven in
  ``repro.kernels.bic_encode``). Two refinements on top of that kernel:
  (a) the composition ``h(s) = f(s) ? g(1) : g(0)`` is BITWISE, so every
  unique segment's pair rides one bit lane of a packed int32 -- ALL
  segment recurrences share a single scan; (b) the encoded-bus toggles
  follow without materializing the encoded stream: within a segment of
  width w the step distance is ``d`` when the invert line holds and
  ``w - d`` when it flips.
* ZVG: the held register value is "last non-zero word so far", i.e. the
  value packed under a running MAX of ``index << 16 | word`` (unset
  cycles pack to -1) -- a prefix scan of ``maximum``.

Cross-block state (held value, previous is-zero bit, the previous
block's last word, one PACKED invert word per encoded stream) is carried
in a single int32 scratch whose rows are indexed statically -- including
the one-step-delayed stream copy, so the kernel reads each input element
exactly once. The T axis is the sequential minor grid dimension, so
revisited accumulator blocks are adjacent.

The kernel counts the PADDED stream unmasked (padded rows repeat the
last real row and padded lanes are all-zero words, so no counter sees a
spurious *transition*); the wrapper subtracts the deterministic padding
contribution to the value counters (zeros / rowzeros / ones histograms)
on the host, which keeps per-element work off the hot loop.

Grid/VMEM: blocks of (TB, LB); working set is TB x LB x 2B input plus
the (n_rows, LB) int32 accumulator -- ~200 KiB at the (256, 128)
default, far under VMEM. All ops (XOR, popcount, compares, shifts, adds)
map to the VPU; there is no MXU work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bits import MANT_MASK, segment_width
from repro.kernels.platform import resolve_interpret

from .spec import WORD_BITS, CounterSpec

NOT_SIGN = 0x7FFF         # zero test ignores the sign bit (-0.0 is zero)
MANT = int(MANT_MASK)     # python int: jnp constants cannot be captured
                          # by a pallas kernel body


def _compose_packed(f, g):
    """Compose step functions ``h(s) = g(f(s))`` represented as packed
    (f(0), f(1)) int32 words, one bit lane per segment. The select
    ``f0 ? g1 : g0`` is bitwise, so one composition serves every
    segment simultaneously."""
    f0, f1 = f
    g0, g1 = g
    return ((f0 & g1) | (~f0 & g0), (f1 & g1) | (~f1 & g0))


def _shift_down(x, k: int, fill: int):
    """``x`` moved ``k`` rows down its first axis, the top ``k`` rows
    filled with ``fill``."""
    pad = jnp.full((k,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([pad, x[:-k]], axis=0)


def _prefix_scan(combine, elems: tuple, identity: tuple) -> tuple:
    """Inclusive prefix scan along axis 0 by log-step doubling.

    ``combine(earlier, later)`` is associative over tuples of equally
    shaped arrays and ``identity`` is its neutral element, one scalar per
    array. Built from statically shifted concatenates because Mosaic
    lowers those, while ``lax.associative_scan``'s strided slices lower
    to zero-size vectors."""
    n = elems[0].shape[0]
    k = 1
    while k < n:
        prev = tuple(_shift_down(e, k, i) for e, i in zip(elems, identity))
        elems = combine(prev, elems)
        k *= 2
    return elems


def _seg_distances(xo, masks):
    """Per-mask popcounts of an XOR-delta block, memoized across the
    fixed menu masks (0xFFFF and the mantissa field are also counter
    rows, so segments sharing them cost nothing extra)."""
    cache = {}

    def d(m):
        if m not in cache:
            cache[m] = jax.lax.population_count(xo & m).astype(jnp.int32)
        return cache[m]

    for m in masks:
        d(m)
    return d


def _bic_variant_rows(d_of, raw_sum, spec, state_ref, state_row: int):
    """Data/inv toggle rows (per-lane sums) for every BIC variant of one
    stream.

    ``d_of`` maps a segment mask to the block's per-step XOR distances
    and ``raw_sum`` is the stream's summed full-bus toggles. A segment's
    invert recurrence depends only on the raw stream and its own mask,
    so variants SHARE segment recurrences (``spec.unique_segments``) --
    and all unique segments share ONE packed scan (bit lane ``si`` of
    the packed words carries segment ``si``'s boolean pair). The
    encoded-bus distance never needs the encoded stream: within a
    segment it is ``d`` when the invert line holds and ``w - d`` when it
    flips, so a variant's data toggles are ``raw_sum + sum_seg
    sum_t flip * (w - 2 d)`` (pass-through bits toggle as raw) -- only
    the per-segment SUMS are materialized, variant assembly is [LB]-wide
    adds.

    The packed carried invert word lives in ``state_ref[state_row]``;
    it is updated to the block's final invert lines.
    """
    segs_u = spec.unique_segments
    if not segs_u:
        return []
    a_pack = None
    b_pack = None
    for si, m in enumerate(segs_u):
        w = segment_width(m)
        d = d_of(m)
        a = (d * 2 > w).astype(jnp.int32) << si   # decision if prev inv 0
        b = (d * 2 < w).astype(jnp.int32) << si   # decision if prev inv 1
        a_pack = a if a_pack is None else a_pack | a
        b_pack = b if b_pack is None else b_pack | b
    # identity step function: f(0) = 0, f(1) = 1 in every bit lane
    pre0, pre1 = _prefix_scan(_compose_packed, (a_pack, b_pack), (0, -1))
    carried = state_ref[state_row:state_row + 1, :]          # [1, LB]
    inv = (carried & pre1) | (~carried & pre0)               # [TB, LB]
    prev_inv = jnp.concatenate([carried, inv[:-1]], axis=0)
    flip_pack = inv ^ prev_inv
    state_ref[state_row:state_row + 1, :] = inv[-1:]

    dsum = {}
    fsum = {}
    for si, m in enumerate(segs_u):
        w = segment_width(m)
        flip = (flip_pack >> si) & 1
        dsum[m] = (flip * (w - 2 * d_of(m))).sum(axis=0)     # [LB]
        fsum[m] = flip.sum(axis=0)
    rows = []
    for segs in spec.bic_variants:
        data = raw_sum
        invtog = fsum[segs[0]]
        for m in segs:
            data = data + dsum[m]
        for m in segs[1:]:
            invtog = invtog + fsum[m]
        rows.append(data)
        rows.append(invtog)
    return rows


def _parallel_block(x, spec, state_ref):
    """Prefix-scan (TPU) in-block algorithm: returns (rows, per-row
    zero counts) and advances the carried scratch states.

    Words are widened to int32 on load and zero masks stay int32: Mosaic
    refuses 16-bit vector compares and bool-vector concatenates."""
    x = x.astype(jnp.int32)
    xp = jnp.concatenate([state_ref[2:3, :], x[:-1]], axis=0)

    zc = ((x & NOT_SIGN) == 0).astype(jnp.int32)

    xo = x ^ xp                                      # shared XOR deltas
    d_of = _seg_distances(xo, (0xFFFF, MANT) + spec.unique_segments)
    raw_sum = d_of(0xFFFF).sum(axis=0)
    rows = [
        raw_sum,                                    # raw
        d_of(MANT).sum(axis=0),                     # mant_raw
        zc.sum(axis=0),                             # zeros (pre-correction)
    ]

    if spec.zvg:
        held_c = state_ref[0:1, :]                           # [1, LB]
        # held value = word at the latest non-zero cycle so far: a MAX
        # scan over (cycle << 16 | word), with zero cycles packed to -1
        it = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        packed = jnp.where(zc == 0, (it << 16) | x, -1)
        (mx,) = _prefix_scan(lambda a, b: (jnp.maximum(a[0], b[0]),),
                             (packed,), (-1,))
        held = jnp.where(mx >= 0, mx & 0xFFFF, held_c)
        held_prev = jnp.concatenate([held_c, held[:-1]], axis=0)
        ho = held ^ held_prev
        h_of = _seg_distances(ho, (0xFFFF, MANT) + spec.unique_segments)
        hraw_sum = h_of(0xFFFF).sum(axis=0)
        z_prev = jnp.concatenate([state_ref[1:2, :], zc[:-1]], axis=0)
        rows.append(hraw_sum)                                       # zvg
        rows.append(h_of(MANT).sum(axis=0))
        rows.append((zc ^ z_prev).sum(axis=0))

    rows += _bic_variant_rows(d_of, raw_sum, spec, state_ref, 3)
    if spec.zvg:
        rows += _bic_variant_rows(h_of, hraw_sum, spec, state_ref, 4)
        state_ref[0:1, :] = held[-1:]
        state_ref[1:2, :] = zc[-1:]
    state_ref[2:3, :] = x[-1:]

    if spec.hist:
        for bit in range(WORD_BITS):
            rows.append(((x >> bit) & 1).sum(axis=0))

    return rows, zc.sum(axis=1)


def _bic_step(xo_d, raw_d, inv, spec):
    """One cycle of every segment's invert recurrence, bit-packed.

    Per segment: distance > w/2 toggles the line, < w/2 keeps it, == w/2
    clears it (ties transmit data, resetting the relative state; ties
    cannot occur on odd-width segments, whose clear term is elided).
    Returns the new packed lines and the per-variant (data, inv) toggle
    rows of this cycle."""
    tog = None
    clr = None
    for si, m in enumerate(spec.unique_segments):
        w = segment_width(m)
        d = xo_d(m)
        t = (d * 2 > w).astype(jnp.int32) << si
        tog = t if tog is None else tog | t
        if w % 2 == 0:
            c = (d * 2 == w).astype(jnp.int32) << si
            clr = c if clr is None else clr | c
    inv_new = inv ^ tog
    if clr is not None:
        inv_new = inv_new & ~clr
    flip_pack = inv_new ^ inv
    flip = {}
    delta = {}
    for si, m in enumerate(spec.unique_segments):
        w = segment_width(m)
        flip[m] = (flip_pack >> si) & 1
        delta[m] = flip[m] * (w - 2 * xo_d(m))
    rows = []
    for segs in spec.bic_variants:
        data = raw_d
        invtog = flip[segs[0]]
        for m in segs:
            data = data + delta[m]
        for m in segs[1:]:
            invtog = invtog + flip[m]
        rows.append(data)
        rows.append(invtog)
    return inv_new, rows


def _scan_block(x, spec: CounterSpec, state_ref):
    """Single-``lax.scan`` (CPU/interpret) in-block algorithm: one fused
    loop over the block's cycles computes every counter of every menu
    entry per step -- the same per-step math the paper's hardware does,
    with all menu entries sharing one traversal. Returns (rows, per-row
    zero counts) and advances the carried scratch states."""
    L = x.shape[1]
    zeros_rows = tuple(jnp.zeros((L,), jnp.int32)
                       for _ in range(spec.n_rows))
    has_bic = bool(spec.unique_segments)
    row = lambda i: state_ref[i:i + 1, :][0]
    carry0 = (
        row(2).astype(jnp.uint16),                   # previous word
        row(0).astype(jnp.uint16),                   # held register
        row(1) != 0,                                 # previous is-zero
        row(3) if has_bic else None,                 # packed inv (raw)
        row(4) if has_bic and spec.zvg else None,    # packed inv (held)
        zeros_rows,
    )

    def step(carry, x_t):
        prev_x, held, prev_z, inv_r, inv_h, acc = carry
        z = (x_t & jnp.uint16(NOT_SIGN)) == 0
        xo = x_t ^ prev_x
        d_of = _seg_distances(xo, (0xFFFF, MANT))
        raw_d = d_of(0xFFFF)
        rows = [raw_d, d_of(MANT), z.astype(jnp.int32)]
        held_n = held
        if spec.zvg:
            held_n = jnp.where(z, held, x_t)
            ho = held_n ^ held
            h_of = _seg_distances(ho, (0xFFFF, MANT))
            rows += [h_of(0xFFFF), h_of(MANT),
                     (z ^ prev_z).astype(jnp.int32)]
        if has_bic:
            inv_r, bic_rows = _bic_step(d_of, raw_d, inv_r, spec)
            rows += bic_rows
            if spec.zvg:
                inv_h, hic_rows = _bic_step(h_of, h_of(0xFFFF), inv_h,
                                            spec)
                rows += hic_rows
        if spec.hist:
            for bit in range(WORD_BITS):
                rows.append(((x_t >> jnp.uint16(bit))
                             & jnp.uint16(1)).astype(jnp.int32))
        acc = tuple(a + r for a, r in zip(acc, rows))
        return ((x_t, held_n, z, inv_r, inv_h, acc),
                z.astype(jnp.int32).sum())

    (last_x, held, last_z, inv_r, inv_h, acc), rowz = jax.lax.scan(
        step, carry0, x)
    state_ref[2:3, :] = last_x[None].astype(jnp.int32)
    if spec.zvg:
        state_ref[0:1, :] = held[None].astype(jnp.int32)
        state_ref[1:2, :] = last_z[None].astype(jnp.int32)
    if has_bic:
        state_ref[3:4, :] = inv_r[None]
        if spec.zvg:
            state_ref[4:5, :] = inv_h[None]
    return list(acc), rowz


def _counters_kernel(x_ref, counts_ref, rowz_ref, state_ref, *,
                     spec: CounterSpec, algo: str):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...]          # [TB, LB] uint16
    block = _scan_block if algo == "scan" else _parallel_block
    rows, rowz = block(x, spec, state_ref)
    rowz_ref[...] = rowz[None, :]
    counts_ref[...] += jnp.stack(rows, axis=0)


def fused_counters_pallas(x: jax.Array, spec: CounterSpec,
                          block_t: int | None = None,
                          block_l: int | None = None,
                          interpret: bool | None = None,
                          algo: str | None = None):
    """Run the fused counter pass over ``uint16[T, L]`` via Pallas.

    Returns ``(counts: int32[spec.n_rows, L], rowzeros: int32[T])``; the
    stream is encoded against an all-zeros initial bus state (every
    counter includes the ``init -> x[0]`` edge, matching the core
    primitives). ``interpret=None`` compiles with Mosaic on a TPU and
    runs the interpreter elsewhere.

    ``algo`` picks the in-block algorithm (see module docstring):
    ``"parallel"`` (prefix scans; default when compiled for TPU) or
    ``"scan"`` (one fused sequential loop; default in interpret mode,
    where the executing backend is a CPU). Bit-exact either way.

    Block sizes default per mode: (256, 128) compiled -- VMEM-sized,
    VREG-aligned -- vs up-to-(1024, 512) in interpret mode, where the
    interpreter's per-grid-step overhead dominates and there is no VMEM
    to blow (results are bit-identical either way; only the grid
    changes).
    """
    interpret = resolve_interpret(interpret)
    if algo is None:
        algo = "scan" if interpret else "parallel"
    if algo not in ("scan", "parallel"):
        raise ValueError(f"unknown algo {algo!r}")
    x = x.astype(jnp.uint16)
    T, L = x.shape
    if block_t is None:
        block_t = min(max(T, 8), 1024) if interpret else 256
    if block_l is None:
        block_l = min(max(L, 8), 512) if interpret else 128

    # pad to block multiples: T with repeats of the last row and L with
    # zero lanes. Neither padding produces TRANSITIONS (the delayed copy
    # is derived in-kernel, and repeated/zero words do not toggle any
    # counted line), so the kernel counts unmasked; the deterministic
    # padding contribution to the value counters is subtracted below.
    pt = (-T) % block_t
    pl_ = (-L) % block_l
    if pt:
        x = jnp.concatenate([x, jnp.repeat(x[-1:], pt, axis=0)], axis=0)
    if pl_:
        x = jnp.pad(x, ((0, 0), (0, pl_)))
    Tp, Lp = x.shape
    grid = (Lp // block_l, Tp // block_t)

    counts, rowz = pl.pallas_call(
        functools.partial(_counters_kernel, spec=spec, algo=algo),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, block_l), lambda l, t: (t, l)),
        ],
        out_specs=[
            # per-lane counter table: revisited across the sequential
            # minor t axis, accumulated in place
            pl.BlockSpec((spec.n_rows, block_l), lambda l, t: (0, l)),
            # per-cycle zero counts: one private block per grid step
            # (partial sums over lane blocks; the host reduces). The
            # unit middle axis keeps the block's last two dims equal to
            # the array's / lane-aligned, as Mosaic requires
            pl.BlockSpec((None, 1, block_t), lambda l, t: (l, 0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((spec.n_rows, Lp), jnp.int32),
            jax.ShapeDtypeStruct((grid[0], 1, Tp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((3 + spec.n_bic_states, block_l), jnp.int32)],
        interpret=interpret,
    )(x)

    counts = counts[:, :L]
    rowzeros = rowz.sum(axis=(0, 1))[:T]
    if pl_:
        # padded lanes are all-zero words: one zero per padded lane per
        # kept cycle (the padded lanes' own counter columns are sliced
        # off above)
        rowzeros = rowzeros - pl_
    if pt:
        # padded rows repeat the last real row: un-count its zero words
        # and histogram bits, repeated pt times (padded-row cycles of
        # rowzeros are sliced off above)
        last = x[T - 1, :L]
        last_z = ((last & jnp.uint16(NOT_SIGN)) == 0).astype(jnp.int32)
        names = spec.rows
        corr = [jnp.zeros_like(last_z)] * len(names)
        corr[names.index("zeros")] = pt * last_z
        if spec.hist:
            for bit in range(WORD_BITS):
                ones = ((last >> jnp.uint16(bit)) & 1).astype(jnp.int32)
                corr[names.index(f"ones/{bit:02d}")] = pt * ones
        counts = counts - jnp.stack(corr, axis=0)
    return counts, rowzeros
