"""Fused decode-path Pallas kernels for the serving engine.

Three kernels, one per decode hot spot, all bit-identical to the XLA
path they replace (differentially tested in
``tests/test_zvg_matmul_kernels.py`` and end-to-end in
``tests/test_serve_kernel_backend.py``):

* :func:`gated_row_matmul` -- the decode-shaped ``[M, K] @ [K, N]``
  matmul with PER-ROW zero-value gating: a row whose operand words are
  all (+0.0) skips the MXU pass entirely (``@pl.when``) and keeps the
  zero-initialized output, which IS the true product for finite
  weights. This is the paper's ZVG realized at the granularity decode
  exposes (one token row per request), and it resolves the
  docs/kernels.md tile-gating caveat: at M-row granularity the gate is
  exact, not tile-coarse. Rows are gated on their VALUE BITS (a -0.0 or
  subnormal row still computes), so live rows are bit-identical to
  ``x @ w``.
* :func:`fused_matmul_counters` -- the monitored-decode pass: ONE
  kernel walks the subsampled per-request operand rows and emits the
  product AND every per-lane coding-menu counter that
  :class:`repro.serve.power.PowerAccountant` prices (west stream per
  row, north/weight stream once per batch). The counter math is the
  shared :func:`repro.kernels.power_counters.kernel._scan_block` loop,
  so the integers are bit-identical to the reference monitor path by
  the PR-4 differential contract.
* :func:`fused_paged_attention` -- the paged decode attention step with
  the page-table gather fused into the same Pallas pass as the
  attention math (the ``attend`` callable, closed over scale/softcap,
  runs on the gathered [B, pages*page_size] view inside the kernel).

All three take ``interpret=None`` by default: the interpreter off TPU
(bitwise vs XLA there -- the serve contract), Mosaic on a TPU. Mosaic
refuses all three as they are shaped today, so the serve engine rejects
``kernel_backend="pallas"`` on a TPU instead of running them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret
from repro.kernels.power_counters.kernel import _scan_block
from repro.kernels.power_counters.spec import CounterSpec

#: unsigned view of a float operand's words, for exact liveness tests
_UINT_OF_SIZE = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _row_is_live(a: jax.Array) -> jax.Array:
    """True iff gating this operand block would need the real matmul.

    For float operands the test is on the raw value bits: exactly-+0.0
    words are the only ones whose product magnitudes are guaranteed
    zero, so -0.0 and subnormal rows stay live (their true products
    carry sign / tiny magnitudes the gate must not erase). Integer
    operands use the plain value test.
    """
    if jnp.issubdtype(a.dtype, jnp.floating):
        bits = jax.lax.bitcast_convert_type(
            a, _UINT_OF_SIZE[a.dtype.itemsize])
        return jnp.any(bits != 0)
    return jnp.any(a != 0)


def _gated_zero_row(w: jax.Array, out_dtype) -> jax.Array:
    """The exact product row of an all-+0.0 operand row, ``[1, N]``.

    Every term ``+0.0 * w[k, j]`` is a zero whose sign is ``w``'s, and
    an IEEE sum of signed zeros is -0.0 iff EVERY addend is -0.0 (any
    association order), so column j gates to -0.0 exactly when all of
    ``w[:, j]`` is sign-negative. Keeps the gated fill byte-identical
    to XLA's dot for finite weights.
    """
    if not jnp.issubdtype(out_dtype, jnp.floating):
        return jnp.zeros((1, w.shape[1]), out_dtype)
    neg = (jnp.signbit(w) if jnp.issubdtype(w.dtype, jnp.floating)
           else w < 0)
    return jnp.where(jnp.all(neg, axis=0, keepdims=True),
                     jnp.asarray(-0.0, out_dtype),
                     jnp.asarray(0.0, out_dtype))


# --------------------------------------------------------------- row matmul
def _row_matmul_kernel(x_ref, w_ref, o_ref):
    a = x_ref[...]                                   # [1, K]
    o_ref[...] = _gated_zero_row(w_ref[...], o_ref.dtype)

    @pl.when(_row_is_live(a))
    def _mac():
        o_ref[...] = jnp.matmul(a, w_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_row_matmul(x: jax.Array, w: jax.Array,
                     interpret: bool | None = None) -> jax.Array:
    """ZVG-gated ``x @ w`` for decode-shaped operands, bitwise vs XLA.

    Args:
      x: ``[M, K]`` activations; each row is one request's token.
      w: ``[K, N]`` weights.
    Returns:
      ``[M, N]`` in ``jnp.result_type(x, w)`` -- bit-identical to
      ``x @ w`` for finite weights (all-+0.0 rows are gated; the fill
      is the exact signed-zero row XLA's dot produces, see
      :func:`_gated_zero_row`).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if M == 0 or K == 0:
        return jnp.zeros((M, N), out_dtype)
    return pl.pallas_call(
        _row_matmul_kernel,
        grid=(M,),
        in_specs=[
            pl.BlockSpec((1, K), lambda m: (m, 0)),
            pl.BlockSpec((K, N), lambda m: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, N), lambda m: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=resolve_interpret(interpret),
    )(x, w)


# ------------------------------------------------- fused matmul + counters
def _fused_decode_kernel(a_ref, w_ref, o_ref, wc_ref, wz_ref, nc_ref,
                         nz_ref, west_state, north_state, *,
                         west_spec: CounterSpec, north_spec: CounterSpec,
                         lanes_w: int, lanes_n: int):
    b = pl.program_id(0)
    a = a_ref[...]                                   # [1, K] original dtype
    w = w_ref[...]                                   # [K, N]
    K = a.shape[1]

    # west stream of THIS request row: the row's bf16 bits ride lane 0 of
    # the R-lane array edge, the other lanes are the padding rows of the
    # [1, K] -> [R, K] tile (all-zero words, counted -- the reference
    # counts them too, and zero_fraction normalizes by the padded extent)
    bits = jax.lax.bitcast_convert_type(
        a.astype(jnp.bfloat16), jnp.uint16)          # [1, K]
    x_w = jnp.concatenate(
        [bits[0][:, None], jnp.zeros((K, lanes_w - 1), jnp.uint16)],
        axis=1)                                      # [K, R]
    west_state[...] = jnp.zeros_like(west_state)     # independent stream / row
    rows_w, rowz_w = _scan_block(x_w, west_spec, west_state)
    wc_ref[...] = jnp.stack(rows_w, axis=0)[None]
    wz_ref[...] = rowz_w[None]

    # north/weight stream: identical for every row, computed once on the
    # first grid step; its constant-index output blocks persist across
    # the remaining steps (same revisiting contract the power_counters
    # accumulator relies on)
    @pl.when(b == 0)
    def _north():
        north_state[...] = jnp.zeros_like(north_state)
        wb = jax.lax.bitcast_convert_type(
            w.astype(jnp.bfloat16), jnp.uint16)      # [K, N]
        if lanes_n > wb.shape[1]:
            wb = jnp.concatenate(
                [wb, jnp.zeros((K, lanes_n - wb.shape[1]), jnp.uint16)],
                axis=1)                              # [K, Np] padded lanes
        rows_n, rowz_n = _scan_block(wb, north_spec, north_state)
        nc_ref[...] = jnp.stack(rows_n, axis=0)
        nz_ref[...] = rowz_n[None]

    # the product, ZVG-gated exactly like gated_row_matmul
    o_ref[...] = _gated_zero_row(w, o_ref.dtype)

    @pl.when(_row_is_live(a))
    def _mac():
        o_ref[...] = jnp.matmul(a, w).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "west_spec", "north_spec", "lanes_w", "cols", "interpret"))
def fused_matmul_counters(a: jax.Array, w: jax.Array,
                          west_spec: CounterSpec,
                          north_spec: CounterSpec,
                          lanes_w: int, cols: int,
                          interpret: bool | None = None):
    """One fused pass: gated products + the whole coding-menu counter set.

    Args:
      a: ``[B, K]`` per-request operand rows (original compute dtype;
        the counter bits are the bf16 view, like every monitor path).
      w: ``[K, N]`` monitored weights.
      west_spec / north_spec: counter menus per edge
        (:class:`repro.kernels.power_counters.spec.CounterSpec`).
      lanes_w: west-edge lane count = the SA geometry's rows (each
        request row streams through an R-row tile).
      cols: the SA geometry's columns (the north stream pads N up to a
        multiple of this, exactly like ``systolic.sa_design_report``).
    Returns:
      ``(product [B, N], west_counts int32[B, n_rows_w, lanes_w],
      west_rowzeros int32[B, K], north_counts int32[n_rows_n, Np],
      north_rowzeros int32[K])``.
    """
    B, K = a.shape
    K2, N = w.shape
    assert K == K2, (a.shape, w.shape)
    lanes_n = -(-N // cols) * cols
    out_dtype = jnp.result_type(a.dtype, w.dtype)
    product, wc, wz, nc, nz = pl.pallas_call(
        functools.partial(
            _fused_decode_kernel, west_spec=west_spec,
            north_spec=north_spec, lanes_w=lanes_w, lanes_n=lanes_n),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, K), lambda b: (b, 0)),
            pl.BlockSpec((K, N), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, N), lambda b: (b, 0)),
            pl.BlockSpec((1, west_spec.n_rows, lanes_w),
                         lambda b: (b, 0, 0)),
            pl.BlockSpec((1, K), lambda b: (b, 0)),
            pl.BlockSpec((north_spec.n_rows, lanes_n), lambda b: (0, 0)),
            pl.BlockSpec((1, K), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, N), out_dtype),
            jax.ShapeDtypeStruct((B, west_spec.n_rows, lanes_w),
                                 jnp.int32),
            jax.ShapeDtypeStruct((B, K), jnp.int32),
            jax.ShapeDtypeStruct((north_spec.n_rows, lanes_n), jnp.int32),
            jax.ShapeDtypeStruct((1, K), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((3 + west_spec.n_bic_states, lanes_w), jnp.int32),
            pltpu.VMEM((3 + north_spec.n_bic_states, lanes_n), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(a, w)
    return product, wc, wz, nc, nz[0]


# ------------------------------------------------- fused paged attention
def _paged_gather(pool: jax.Array, pages: jax.Array) -> jax.Array:
    """Page-table gather: ``[P, ps, ...]`` pool + ``[B, MP]`` tables ->
    ``[B, MP*ps, ...]`` contiguous per-request views (the same indexing
    as ``models.transformer._gather_pages``)."""
    b, mp = pages.shape
    view = jnp.take(pool, pages, axis=0)
    return view.reshape((b, mp * pool.shape[1]) + pool.shape[2:])


def _paged_attention_kernel(q_ref, kp_ref, vp_ref, pages_ref, len_ref,
                            o_ref, *, attend):
    pages = pages_ref[...]
    kc = _paged_gather(kp_ref[...], pages)
    vc = _paged_gather(vp_ref[...], pages)
    o_ref[...] = attend(q_ref[...], kc, vc, len_ref[...]
                        ).astype(o_ref.dtype)


def fused_paged_attention(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, pages: jax.Array,
                          lengths: jax.Array, attend,
                          interpret: bool | None = None) -> jax.Array:
    """Paged decode attention with the page gather fused into the kernel.

    Args:
      q: ``[B, 1, h, hd]`` decode queries.
      k_pool / v_pool: ``[P, ps, kv, hd]`` global page pools.
      pages: ``[B, MP]`` int32 per-request page tables.
      lengths: ``[B]`` int32 attention lengths (positions + 1).
      attend: ``(q, k_cache, v_cache, lengths) -> [B, 1, h, hd]``
        attention body (closed over scale/softcap), evaluated on the
        gathered per-request views INSIDE the Pallas pass.
    Returns the attention output, bit-identical (interpret mode) to
    gathering first and calling ``attend`` outside.
    """
    return pl.pallas_call(
        functools.partial(_paged_attention_kernel, attend=attend),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k_pool, v_pool, pages, lengths)
