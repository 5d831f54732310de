"""Pallas TPU kernel: PAA-to-iSAX lower-bound distance (paper §3.3.1).

This is ParIS+'s flagship SIMD contribution adapted to the TPU VPU. The paper
evaluates the 3-way branch (query PAA ABOVE / BELOW / IN the iSAX region) on
all 8 AVX lanes and mask-combines the results; here the same branch-free
algebra runs on 8x128-lane vector registers over VMEM-resident tiles.

The breakpoint dictionary lookup (symbol -> region bounds) is a one-hot x
table product on the MXU (:func:`_bounds`): Mosaic lowers no 1-D gather.
It runs at ``Precision.HIGHEST``: each output column has exactly one
nonzero term, so the f32 product is exact, whereas the default bf16 pass
would round a breakpoint and could raise a bound above the true distance —
a silent pruning error.

Baseline layout: SAX tiles of shape (block_n, w) uint8; w=16 symbols sit on
the lane axis. The optimized layout (``transposed=True``) stores SAX as
(w, N): the N axis lands on the 128-wide lanes so every lane does useful work
(the (block_n, 16) layout wastes 7/8 of each vector register to lane padding).
Both layouts share the same algebra and oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import isax

_TABLE_ROWS = 8  # row 0: lower bounds, row 1: upper bounds, rest zero


def bounds_table(bp_padded: jax.Array) -> jax.Array:
    """(card+1,) padded breakpoints -> (8, card_pad) f32 lookup table.

    Row 0 holds each symbol's lower breakpoint, row 1 its upper one; the
    symbol axis is zero-padded to a lane multiple (no symbol selects it).
    Infinite end breakpoints are clipped to ``±isax.BIG``: the lookup
    multiplies every entry by 0 or 1, and ``inf * 0`` would be NaN.
    """
    card = bp_padded.shape[0] - 1
    card_pad = -(-card // 128) * 128
    tab = jnp.zeros((_TABLE_ROWS, card_pad), jnp.float32)
    bp = jnp.clip(bp_padded.astype(jnp.float32), -isax.BIG, isax.BIG)
    return tab.at[0, :card].set(bp[:-1]).at[1, :card].set(bp[1:])


def _bounds(sym_row: jax.Array, tab: jax.Array) -> tuple:
    """(1, bn) int32 symbols -> ((1, bn) lower, (1, bn) upper) bounds."""
    card_pad, bn = tab.shape[1], sym_row.shape[1]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (card_pad, bn), 0)
              == sym_row).astype(jnp.float32)
    lohi = jax.lax.dot(tab, onehot, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)  # (8, bn)
    return lohi[0:1], lohi[1:2]


def _fill_bounds(sym, tab, lo_ref, hi_ref):
    """(w, bn) int32 symbols -> per-segment bounds in (w, bn) VMEM refs."""
    for j in range(sym.shape[0]):
        lo, hi = _bounds(sym[j:j + 1], tab)
        lo_ref[j:j + 1, :] = lo
        hi_ref[j:j + 1, :] = hi


def _lb_kernel_rows(q_ref, tab_ref, sax_ref, o_ref, lo_ref, hi_ref, *,
                    scale: float):
    """Tile layout (block_n, w): symbols on lanes. One output per sublane row."""
    _fill_bounds(sax_ref[...].astype(jnp.int32).T, tab_ref[...], lo_ref,
                 hi_ref)
    q = q_ref[...]  # (w, 1)
    # Paper's three masked branches, combined without control flow.
    d = jnp.maximum(jnp.maximum(q - hi_ref[...], lo_ref[...] - q), 0.0)
    o_ref[...] = scale * jnp.sum((d * d).T, axis=-1, keepdims=True)


def _lb_kernel_cols(q_ref, tab_ref, sax_ref, o_ref, lo_ref, hi_ref, *,
                    scale: float):
    """Tile layout (w, block_n): candidates on lanes (optimized layout)."""
    _fill_bounds(sax_ref[...].astype(jnp.int32), tab_ref[...], lo_ref,
                 hi_ref)
    q = q_ref[...]  # (w, 1)
    d = jnp.maximum(jnp.maximum(q - hi_ref[...], lo_ref[...] - q), 0.0)
    o_ref[...] = scale * jnp.sum(d * d, axis=0, keepdims=True)


def _lb_batch_tile(q_ref, tab_ref, sax_ref, lo_ref, hi_ref, *, scale: float):
    """(block_q, w) queries x (w, block_n) SAX tile -> (block_q, block_n).

    The grid runs query blocks innermost, so the breakpoint lookups run
    once per SAX tile (on the first query block, into VMEM scratch) and are
    shared by every query of the batch: the SAX array streams through VMEM
    once per *batch*, not once per query.
    """
    w = q_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        _fill_bounds(sax_ref[...].astype(jnp.int32), tab_ref[...], lo_ref,
                     hi_ref)

    q = q_ref[...]  # (bq, w)
    acc = jnp.zeros((q.shape[0], lo_ref.shape[1]), jnp.float32)
    for j in range(w):  # w is 8-32: unrolled VPU ops, no (bq, w, bn) blowup
        qj = q[:, j:j + 1]  # (bq, 1)
        d = jnp.maximum(
            jnp.maximum(qj - hi_ref[j:j + 1, :], lo_ref[j:j + 1, :] - qj), 0.0)
        acc = acc + d * d
    return scale * acc


def _lb_kernel_batch(q_ref, tab_ref, sax_ref, o_ref, lo_ref, hi_ref, *,
                     scale: float):
    o_ref[...] = _lb_batch_tile(q_ref, tab_ref, sax_ref, lo_ref, hi_ref,
                                scale=scale)


def _lb_kernel_batch_masked(len_ref, q_ref, tab_ref, sax_ref, o_ref, lo_ref,
                            hi_ref, *, scale: float):
    """Batched tile over a *packed multi-component* SAX array.

    Same algebra as ``_lb_kernel_batch``, plus a per-block validity count:
    the packed layout (``core.search.pack_components``) pads every
    component's leaf-sorted run to a block_n multiple so an append can
    extend the buffer without moving earlier components' rows, and
    ``len_ref`` (scalar-prefetched into SMEM) carries how many lanes of
    each block are real rows. Pad lanes come back +inf, so no
    downstream selection (top_k, round masks, fallback scan) can ever pick
    one — the kernel, not the caller, owns the component boundaries.
    """
    acc = _lb_batch_tile(q_ref, tab_ref, sax_ref, lo_ref, hi_ref,
                         scale=scale)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    o_ref[...] = jnp.where(
        lane < len_ref[pl.program_id(0)], acc, jnp.float32(jnp.inf))


def _batch_call(kernel, nq, w, n, card_pad, block_q, block_n, n_prefetch,
                interpret):
    """pallas_call for the (N-block, Q-block) batch grid, Q innermost.

    Index maps take (j, i) grid indices plus the scalar-prefetch refs.
    """
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(n // block_n, nq // block_q),
            in_specs=[
                pl.BlockSpec((block_q, w), lambda j, i, *_: (i, 0)),
                pl.BlockSpec((_TABLE_ROWS, card_pad),
                             lambda j, i, *_: (0, 0)),
                pl.BlockSpec((w, block_n), lambda j, i, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((block_q, block_n),
                                   lambda j, i, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((w, block_n), jnp.float32)] * 2,
        ),
        out_shape=jax.ShapeDtypeStruct((nq, n), jnp.float32),
        # The scratch lookups are filled on the first query block of each
        # N block, so the query axis must run in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("series_length", "block_q", "block_n", "interpret"),
)
def lower_bound_sq_multi_pallas(
    query_paa: jax.Array,
    sax_t: jax.Array,
    bp_padded: jax.Array,
    series_length: int,
    block_len: jax.Array,
    *,
    block_q: int = 8,
    block_n: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """(Q, w) PAA batch x (w, N_pad) packed sax -> (Q, N_pad) lower bounds.

    The fused multi-component sweep: ``sax_t`` concatenates every live
    component (base + runs + deltas) with each component independently
    padded to a ``block_n`` multiple, and ``block_len`` (N_pad/block_n,)
    gives the valid-row count per block. One grid pass covers the whole
    store — no per-component kernel launches — and pad lanes are masked to
    +inf inside the kernel. Q must divide ``block_q`` exactly (ops.py pads).
    """
    nq, w = query_paa.shape
    w2, n = sax_t.shape
    if w != w2:
        raise ValueError(f"query w={w} != sax w={w2}")
    if nq % block_q or n % block_n:
        raise ValueError(
            f"(Q={nq}, N={n}) not multiples of ({block_q}, {block_n})"
        )
    if block_len.shape != (n // block_n,):
        raise ValueError(
            f"block_len {block_len.shape} != ({n // block_n},)")
    scale = float(series_length) / float(w)
    tab = bounds_table(bp_padded)
    kernel = functools.partial(_lb_kernel_batch_masked, scale=scale)
    call = _batch_call(kernel, nq, w, n, tab.shape[1], block_q, block_n, 1,
                       interpret)
    return call(block_len.astype(jnp.int32), query_paa.astype(jnp.float32),
                tab, sax_t)


@functools.partial(
    jax.jit,
    static_argnames=("series_length", "block_q", "block_n", "interpret"),
)
def lower_bound_sq_batch_pallas(
    query_paa: jax.Array,
    sax_t: jax.Array,
    bp_padded: jax.Array,
    series_length: int,
    *,
    block_q: int = 8,
    block_n: int = 1024,
    interpret: bool = True,
) -> jax.Array:
    """(Q, w) PAA batch x (w, N) sax -> (Q, N) squared lower bounds.

    Grid is (N/block_n, Q/block_q); both must divide exactly (ops.py pads;
    padded rows/cols produce garbage the caller slices off). Query blocks sit
    on the sublane axis so all 8 sublanes do useful work, candidates on the
    128-wide lanes (the optimized transposed layout).
    """
    nq, w = query_paa.shape
    w2, n = sax_t.shape
    if w != w2:
        raise ValueError(f"query w={w} != sax w={w2}")
    if nq % block_q or n % block_n:
        raise ValueError(
            f"(Q={nq}, N={n}) not multiples of ({block_q}, {block_n})"
        )
    scale = float(series_length) / float(w)
    tab = bounds_table(bp_padded)
    kernel = functools.partial(_lb_kernel_batch, scale=scale)
    call = _batch_call(kernel, nq, w, n, tab.shape[1], block_q, block_n, 0,
                       interpret)
    return call(query_paa.astype(jnp.float32), tab, sax_t)


@functools.partial(
    jax.jit,
    static_argnames=("series_length", "block_n", "interpret", "transposed"),
)
def lower_bound_sq_pallas(
    query_paa: jax.Array,
    sax: jax.Array,
    bp_padded: jax.Array,
    series_length: int,
    *,
    block_n: int = 1024,
    interpret: bool = True,
    transposed: bool = False,
) -> jax.Array:
    """(w,) PAA x sax -> (N,) squared lower bounds.

    ``sax`` is (N, w) uint8 for the row layout, (w, N) for ``transposed``.
    N must be a multiple of ``block_n`` (ops.py pads; padded entries produce
    garbage the caller slices off).
    """
    if transposed:
        w, n = sax.shape
    else:
        n, w = sax.shape
    if n % block_n:
        raise ValueError(f"N={n} not a multiple of block_n={block_n}")
    scale = float(series_length) / float(w)
    tab = bounds_table(bp_padded)
    grid = (n // block_n,)
    q2d = query_paa.astype(jnp.float32)[:, None]  # (w, 1)

    if transposed:
        kernel = functools.partial(_lb_kernel_cols, scale=scale)
        sax_spec = pl.BlockSpec((w, block_n), lambda i: (0, i))
        out_specs = pl.BlockSpec((1, block_n), lambda i: (0, i))
        out_shape = jax.ShapeDtypeStruct((1, n), jnp.float32)
    else:
        kernel = functools.partial(_lb_kernel_rows, scale=scale)
        sax_spec = pl.BlockSpec((block_n, w), lambda i: (i, 0))
        out_specs = pl.BlockSpec((block_n, 1), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((n, 1), jnp.float32)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, 1), lambda i: (0, 0)),
            pl.BlockSpec(tab.shape, lambda i: (0, 0)),
            sax_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((w, block_n), jnp.float32)] * 2,
        interpret=interpret,
    )(q2d, tab, sax)
    return out.reshape(n)
