"""Mesh-distributed ParIS+ search and build (shard_map over the pod mesh).

Paper -> pod mapping (DESIGN.md §2):

  * 24 cores -> up to 512 devices; the SAX array, the (index-ordered) raw
    data, and the position map are sharded along N over every mesh axis — each
    device plays the role of one LBC+RDC worker pair over its partition.
  * the shared BSF (one atomically-updated float) -> a per-round
    ``all-reduce(min)`` over the mesh: each round every device distances one
    tile of its own sorted candidate list, then the BSF is globally agreed
    before the next round. Round size trades collective latency against
    pruning freshness — the TPU analogue of the paper's atomic-update
    frequency (hillclimbed in EXPERIMENTS.md §Perf).
  * nb-ParIS+ (local BSFs, Fig. 8) -> ``shared_bsf=False``: devices scan
    independently and agree only once at the end. Reproduces the Fig. 20
    pruning-effort gap at mesh scale.
  * early termination: the *global* minimum unprocessed lower bound is
    compared with the BSF, so the while_loop trip count is identical on every
    device (collectives inside the loop stay aligned).

Raw-data placement: the distributed index stores raw series in *index order*
(``raw_sorted = raw[pos]``), co-locating every candidate's raw data with its
summarization shard — the distributed analogue of the paper's sorted
candidate list turning random disk reads into sequential ones; no cross-device
gather is needed in the hot loop.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import isax
from repro.core.index import ParISIndex
from repro.core.search import (
    NO_POS, SearchResult, dedup_mask, select_len as search_select_len,
)
from repro.kernels import ops

INF = jnp.float32(jnp.inf)
IMAX = jnp.int32(2**31 - 1)


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DistIndex:
    """Index arrays laid out for the mesh: all sharded along N (axis 0)."""

    sax: jax.Array  # (N, w) uint8, index order
    raw_sorted: jax.Array  # (N, n) f32, index order (co-located with sax)
    pos: jax.Array  # (N,) int32, index order -> file offset
    series_length: int = dataclasses.field(metadata=dict(static=True))
    segments: int = dataclasses.field(metadata=dict(static=True))
    cardinality: int = dataclasses.field(metadata=dict(static=True))


def dist_index_from(index: ParISIndex, shardings: DistIndex) -> DistIndex:
    """Place the index on the mesh, with raw data in index order.

    ``shardings`` comes from :func:`index_shardings`. N is padded to the
    number of shards along axis 0, and each device's rows of ``raw_sorted``
    are gathered and moved one shard at a time, so no device ever holds the
    whole index-ordered copy beside ``index.raw``.
    """
    row_sharding = shardings.raw_sorted
    axes = row_sharding.spec[0]  # one axis name, or a tuple of them
    axes = (axes,) if isinstance(axes, str) else axes
    num_shards = int(np.prod([row_sharding.mesh.shape[ax] for ax in axes]))
    n = index.num_series
    padded = -(-n // num_shards) * num_shards
    pad = padded - n
    sax = jnp.pad(index.sax, ((0, pad), (0, 0)))
    # Pad positions carry the NO_POS sentinel so kernels can recognize
    # filler rows (the k-NN kernel masks them out of its result lists; for
    # 1-NN the +BIG raw filler already keeps them from winning).
    pos = jnp.pad(index.pos, (0, pad), constant_values=int(NO_POS))

    def rows_of(p):
        # Padded rows: +BIG raw values so their distance can never win.
        raw = jnp.take(index.raw, jnp.maximum(p, 0), axis=0)
        return jnp.where(p[:, None] >= 0, raw, jnp.float32(1e9))

    shape = (padded, index.series_length)
    placed = row_sharding.addressable_devices_indices_map(shape)
    raw_sorted = jax.make_array_from_single_device_arrays(
        shape, row_sharding,
        [jax.device_put(rows_of(pos[rows]), dev)
         for dev, (rows, _) in placed.items()])
    return DistIndex(
        sax=jax.device_put(sax, shardings.sax),
        raw_sorted=raw_sorted,
        pos=jax.device_put(pos, shardings.pos),
        series_length=index.series_length,
        segments=index.segments,
        cardinality=index.cardinality,
    )


def index_shardings(mesh: Mesh, axes: Sequence[str]) -> DistIndex:
    """NamedShardings (as a DistIndex-shaped pytree) for placement/dry-run."""
    spec = P(tuple(axes))
    row = NamedSharding(mesh, P(tuple(axes), None))
    vec = NamedSharding(mesh, spec)
    return DistIndex(sax=row, raw_sorted=row, pos=vec,
                     series_length=0, segments=0, cardinality=0)


def _local_exact_search(
    sax_l: jax.Array,
    raw_l: jax.Array,
    pos_l: jax.Array,
    query: jax.Array,
    *,
    series_length: int,
    segments: int,
    cardinality: int,
    round_size: int,
    leaf_cap: int,
    shared_bsf: bool,
    axis_names: tuple,
    impl: str,
    select: str = "sort",
) -> SearchResult:
    """Per-device body (runs under shard_map); collectives over axis_names."""
    n_local = sax_l.shape[0]
    q = isax.znorm(query)
    qp = isax.paa(q, segments)
    bpp = isax.padded_breakpoints(cardinality)

    def gmin(x):
        for ax in axis_names:
            x = jax.lax.pmin(x, ax)
        return x

    def gsum(x):
        for ax in axis_names:
            x = jax.lax.psum(x, ax)
        return x

    # Approximate search: every device scans its first leaf_cap entries in
    # leaf order; the global pmin is at least as good as one leaf's scan.
    cap = min(leaf_cap, n_local)
    d0 = ops.euclid_sq(q, raw_l[:cap], impl=impl)
    j0 = jnp.argmin(d0)
    bsf0, bsfpos0 = d0[j0], pos_l[j0]
    gb = gmin(bsf0)
    bsfpos0 = jnp.where(bsf0 <= gb, bsfpos0, IMAX)
    bsf0 = gb
    bsfpos0 = gmin(bsfpos0)

    # LBC phase on the local shard. ParIS+ sorts its candidate list (enables
    # wholesale early termination); nb- scans in SAX order (Alg. 7/8).
    # select="topk" (beyond-paper, §Perf): the paper sorts the *candidate
    # list* — a full argsort of every local lower bound is the dominant LBC
    # cost at pod scale. Partial selection keeps only the smallest K bounds
    # (K = max(n/16, round)); exactness is preserved by a fallback pass
    # over the remainder that only runs if the K-th bound still beats the
    # BSF when the candidate list is exhausted (rare: reads are ~1-4%).
    lb = ops.lower_bound_sq(qp, sax_l, bpp, series_length, impl=impl)
    if shared_bsf and select == "topk":
        k_sel = min(max(n_local // 16, round_size), n_local)
        neg, order = jax.lax.top_k(-lb, k_sel)
        order = order.astype(jnp.int32)
        lb_sorted = -neg
        sel_len = k_sel
    elif shared_bsf:
        order = jnp.argsort(lb).astype(jnp.int32)
        lb_sorted = jnp.take(lb, order, axis=0)
        sel_len = n_local
    else:
        order = jnp.arange(n_local, dtype=jnp.int32)
        lb_sorted = lb
        sel_len = n_local
    n_rounds = -(-sel_len // round_size)
    padded = n_rounds * round_size
    if padded > sel_len:
        order = jnp.concatenate(
            [order, jnp.zeros(padded - sel_len, jnp.int32)])
        lb_sorted = jnp.concatenate(
            [lb_sorted, jnp.full(padded - sel_len, INF)])

    # Candidate data is gathered into round order OUTSIDE the while_loop:
    # a data-dependent gather inside a while_loop body miscompiles under
    # shard_map on older jax (rows silently come back wrong on the forced
    # host-device backend), and a contiguous dynamic_slice of pre-gathered
    # rows is the TPU-friendly access pattern anyway (the paper's sequential
    # reads of the sorted candidate list).
    raw_ordered = jnp.take(raw_l, order, axis=0)  # (padded, n)
    pos_ordered = jnp.take(pos_l, order, axis=0)  # (padded,)

    def cond(st):
        r, bsf, *_ = st
        nxt = jax.lax.dynamic_index_in_dim(
            lb_sorted, r * round_size, keepdims=False)
        # Global early stop: run while ANY device still has live candidates,
        # so the while_loop trip count (and the collectives inside) stay
        # aligned across devices. In shared mode bsf is globally equal, so
        # gmin(nxt) < bsf is exactly "any device live"; in nb- mode each
        # device has its own bsf and we reduce the liveness bit instead.
        if shared_bsf:
            live = gmin(nxt) < bsf
        else:
            # Unsorted list: a high next-lb proves nothing about the rest, so
            # nb- has no early exit — it scans every round (like Alg. 8).
            live = True
        return (r < n_rounds) & live

    def body(st):
        r, bsf, bsfpos, reads, updates = st
        lbs = jax.lax.dynamic_slice_in_dim(lb_sorted, r * round_size,
                                           round_size)
        mask = lbs < bsf
        raws = jax.lax.dynamic_slice_in_dim(
            raw_ordered, r * round_size, round_size)
        d = jnp.where(mask, ops.euclid_sq(q, raws, impl=impl), INF)
        j = jnp.argmin(d)
        cand_pos = jax.lax.dynamic_slice_in_dim(
            pos_ordered, r * round_size, round_size)
        better = d[j] < bsf
        bsf_new = jnp.where(better, d[j], bsf)
        pos_new = jnp.where(better, cand_pos[j], bsfpos)
        if shared_bsf:
            gb_new = gmin(bsf_new)
            pos_new = jnp.where(bsf_new <= gb_new, pos_new, IMAX)
            pos_new = gmin(pos_new)
            bsf_new = gb_new
        return (r + 1, bsf_new, pos_new, reads + jnp.sum(mask),
                updates + better.astype(jnp.int32))

    st0 = (jnp.int32(0), bsf0, bsfpos0.astype(jnp.int32),
           jnp.int32(cap), jnp.int32(0))
    r, bsf, bsfpos, reads, updates = jax.lax.while_loop(cond, body, st0)

    if shared_bsf and select == "topk" and sel_len < n_local:
        # Fallback for exactness: if the truncated candidate list was
        # exhausted while its worst bound still beat the BSF, unselected
        # series might qualify — scan the full shard in SAX order with
        # BSF pruning. Globally gated so collectives stay aligned.
        kth = lb_sorted[sel_len - 1]
        need = gmin(jnp.where(kth < bsf, 0, 1)) < 1
        all_rounds = -(-n_local // round_size)
        pad_all = all_rounds * round_size
        pad_f = pad_all - n_local
        lb_all = jnp.concatenate(
            [lb, jnp.full(pad_f, INF)]) if pad_f else lb
        # Wraparound row padding replaces the old `arange % n_local` gather
        # (same rows, but sliceable — see the in-loop-gather note above).
        raw_file = jnp.concatenate(
            [raw_l, raw_l[:pad_f]], axis=0) if pad_f else raw_l
        pos_file = jnp.concatenate(
            [pos_l, pos_l[:pad_f]]) if pad_f else pos_l

        def fcond(st):
            r2, bsf2, *_ = st
            live = gmin(jnp.where(r2 < all_rounds, 0, 1)) < 1
            return live & need

        def fbody(st):
            r2, bsf2, pos2, reads2, upd2 = st
            lbs = jax.lax.dynamic_slice_in_dim(lb_all, r2 * round_size,
                                               round_size)
            mask = lbs < bsf2
            raws = jax.lax.dynamic_slice_in_dim(
                raw_file, r2 * round_size, round_size)
            d = jnp.where(mask, ops.euclid_sq(q, raws, impl=impl), INF)
            j = jnp.argmin(d)
            cand = jax.lax.dynamic_slice_in_dim(
                pos_file, r2 * round_size, round_size)
            better = d[j] < bsf2
            bsf_new = jnp.where(better, d[j], bsf2)
            pos_new = jnp.where(better, cand[j], pos2)
            gb2 = gmin(bsf_new)
            pos_new = jnp.where(bsf_new <= gb2, pos_new, IMAX)
            return (r2 + 1, gb2, gmin(pos_new), reads2 + jnp.sum(mask),
                    upd2 + better.astype(jnp.int32))

        st1 = (jnp.int32(0), bsf, bsfpos, reads, updates)
        _, bsf, bsfpos, reads, updates = jax.lax.while_loop(
            fcond, fbody, st1)

    # Final agreement (no-op when shared_bsf already converged).
    gb = gmin(bsf)
    bsfpos = jnp.where(bsf <= gb, bsfpos, IMAX)
    return SearchResult(gb, gmin(bsfpos), gsum(reads), gsum(updates), r)


def make_distributed_search(
    mesh: Mesh,
    axes: Sequence[str],
    *,
    series_length: int = 256,
    segments: int = isax.DEFAULT_SEGMENTS,
    cardinality: int = isax.DEFAULT_CARDINALITY,
    round_size: int = 4096,
    leaf_cap: int = 256,
    shared_bsf: bool = True,
    impl: str = "auto",
    batch_queries: int = 0,
    select: str = "sort",
):
    """Build the jitted, mesh-sharded exact-search step.

    Returns ``search_step(dist_index, query) -> SearchResult`` with
    ``dist_index`` sharded along N over ``axes`` and the query replicated.
    ``batch_queries > 0``: the step takes (Q, n) and answers Q queries per
    launch (vmapped workers; per-query collectives batch into one — the
    throughput-serving variant, see EXPERIMENTS.md §Perf). This is also the
    step the dry-run lowers for the ``paris`` arch.
    """
    axes = tuple(axes)
    kernel = functools.partial(
        _local_exact_search,
        series_length=series_length,
        segments=segments,
        cardinality=cardinality,
        round_size=round_size,
        leaf_cap=leaf_cap,
        shared_bsf=shared_bsf,
        axis_names=axes,
        impl=impl,
        select=select,
    )
    if batch_queries:
        inner = kernel

        def kernel(sax_l, raw_l, pos_l, queries):  # noqa: F811
            return jax.vmap(
                lambda q: inner(sax_l, raw_l, pos_l, q))(queries)

    row = P(axes, None)
    vec = P(axes)
    rep = P()

    def step(dist_index: DistIndex, query: jax.Array) -> SearchResult:
        return _shard_map(
            kernel,
            mesh,
            in_specs=(row, row, vec, rep),
            out_specs=SearchResult(rep, rep, rep, rep, rep),
        )(dist_index.sax, dist_index.raw_sorted, dist_index.pos, query)

    return step


def _local_batch_search(
    sax_l: jax.Array,
    raw_l: jax.Array,
    pos_l: jax.Array,
    queries: jax.Array,
    *,
    series_length: int,
    segments: int,
    cardinality: int,
    round_size: int,
    leaf_cap: int,
    axis_names: tuple,
    impl: str,
) -> SearchResult:
    """Per-device body of the batched search (runs under shard_map).

    The batched analogue of :func:`_local_exact_search` with shared BSFs:
    one fused (Q, n_local) LBC pass per shard, per-query local candidate
    orders, and ONE joint while_loop whose per-round collectives min-reduce
    the whole (Q,) BSF vector (and its positions) across shards at once —
    Q queries cost one collective per round instead of Q.
    """
    n_local = sax_l.shape[0]
    n_q = queries.shape[0]
    rs = round_size
    qs = isax.znorm(queries)
    qps = isax.paa(qs, segments)
    bpp = isax.padded_breakpoints(cardinality)

    def gmin(x):
        for ax in axis_names:
            x = jax.lax.pmin(x, ax)
        return x

    def gsum(x):
        for ax in axis_names:
            x = jax.lax.psum(x, ax)
        return x

    # Approximate phase: every device scans its first cap rows for every
    # query; the global elementwise pmin seeds the (Q,) BSF vector.
    cap = min(leaf_cap, n_local)
    d0 = jax.vmap(lambda q: ops.euclid_sq(q, raw_l[:cap], impl=impl))(qs)
    j0 = jnp.argmin(d0, axis=1)
    bsf0 = jnp.take_along_axis(d0, j0[:, None], axis=1)[:, 0]
    pos0 = jnp.take(pos_l, j0, axis=0)
    gb = gmin(bsf0)
    pos0 = jnp.where(bsf0 <= gb, pos0, IMAX)
    bsf0 = gb
    pos0 = gmin(pos0)

    # LBC: one fused (Q, n_local) pass, then per-query top_k partial
    # selection (ties break toward lower index like a stable sort). The
    # selection bounds the pre-gathered candidate block below; exactness is
    # preserved by the fallback scan after the main loop. On top of the
    # shared heuristic, cap the pre-gather at ~256 MiB of f32 per device —
    # raw_sel is (Q, sel_len, n) and would otherwise grow unboundedly with
    # Q and shard size; a tighter cap only means earlier fallback scans,
    # never lost exactness.
    lb = ops.lower_bound_sq_batch(qps, sax_l, bpp, series_length, impl=impl)
    budget_rows = (64 * 1024 * 1024) // max(1, n_q * series_length)
    sel_len = search_select_len(n_local, rs)
    sel_len = min(sel_len, max(rs, budget_rows))
    neg, order = jax.lax.top_k(-lb, sel_len)
    order = order.astype(jnp.int32)
    lb_sorted = -neg
    kth_bound = lb_sorted[:, -1]  # worst selected bound per query
    n_rounds = -(-sel_len // rs)
    padded = n_rounds * rs
    if padded > sel_len:
        order = jnp.concatenate(
            [order, jnp.zeros((n_q, padded - sel_len), jnp.int32)], axis=1
        )
        lb_sorted = jnp.concatenate(
            [lb_sorted, jnp.full((n_q, padded - sel_len), INF)], axis=1
        )
    # Pre-gather candidates OUTSIDE the while_loop (see the note in
    # _local_exact_search: in-loop data-dependent gathers miscompile under
    # shard_map on older jax, and contiguous slices are TPU-friendly).
    # top_k indices are in bounds: "clip" skips the fill-mode select, which
    # costs a second (Q, padded, n) buffer.
    raw_sel = jnp.take(raw_l, order, axis=0, mode="clip")  # (Q, padded, n)
    pos_sel = jnp.take(pos_l, order, axis=0, mode="clip")  # (Q, padded)

    def cond(st):
        r, bsf, *_ = st
        head = jax.lax.dynamic_slice_in_dim(lb_sorted, r * rs, 1, axis=1)[:, 0]
        # bsf is globally agreed every round, so "any query on any shard
        # still live" is replicated — trip counts (and the collectives
        # inside the body) stay aligned across devices.
        return (r < n_rounds) & jnp.any(gmin(head) < bsf)

    def body(st):
        r, bsf, bsfpos, reads, updates = st
        lbs = jax.lax.dynamic_slice_in_dim(lb_sorted, r * rs, rs, axis=1)
        mask = lbs < bsf[:, None]
        raws = jax.lax.dynamic_slice_in_dim(raw_sel, r * rs, rs, axis=1)
        d = jax.vmap(lambda q, rw: ops.euclid_sq(q, rw, impl=impl))(qs, raws)
        d = jnp.where(mask, d, INF)
        j = jnp.argmin(d, axis=1)
        dj = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
        cand_pos = jax.lax.dynamic_slice_in_dim(pos_sel, r * rs, rs, axis=1)
        candj = jnp.take_along_axis(cand_pos, j[:, None], axis=1)[:, 0]
        better = dj < bsf
        bsf_new = jnp.where(better, dj, bsf)
        pos_new = jnp.where(better, candj, bsfpos)
        # Cross-shard agreement of the whole (dist, pos) vector at once.
        gb_new = gmin(bsf_new)
        pos_new = jnp.where(bsf_new <= gb_new, pos_new, IMAX)
        pos_new = gmin(pos_new)
        return (
            r + 1,
            gb_new,
            pos_new,
            reads + jnp.sum(mask, axis=1, dtype=jnp.int32),
            updates + better.astype(jnp.int32),
        )

    st0 = (
        jnp.int32(0),
        bsf0,
        pos0.astype(jnp.int32),
        jnp.full((n_q,), cap, jnp.int32),
        jnp.zeros((n_q,), jnp.int32),
    )
    r, bsf, bsfpos, reads, updates = jax.lax.while_loop(cond, body, st0)

    if sel_len < n_local:
        # Exactness fallback over the full shard in SAX order (contiguous
        # slices, wraparound row padding). A query whose worst selected
        # bound still beats its BSF may have unselected qualifying
        # candidates on this shard; the global need bit keeps trip counts
        # aligned across devices.
        all_rounds = -(-n_local // rs)
        pad_all = all_rounds * rs
        pad_f = pad_all - n_local
        lb_all = (
            jnp.concatenate([lb, jnp.full((n_q, pad_f), INF)], axis=1)
            if pad_f else lb
        )
        raw_file = (
            jnp.concatenate([raw_l, raw_l[:pad_f]], axis=0)
            if pad_f else raw_l
        )
        pos_file = (
            jnp.concatenate([pos_l, pos_l[:pad_f]]) if pad_f else pos_l
        )

        def fcond(st):
            r2, bsf2, *_ = st
            local_need = jnp.any(kth_bound < bsf2)
            need_g = gmin(jnp.where(local_need, 0, 1)) < 1
            return (r2 < all_rounds) & need_g

        def fbody(st):
            r2, bsf2, bsfpos2, reads2, upd2 = st
            lbs = jax.lax.dynamic_slice_in_dim(lb_all, r2 * rs, rs, axis=1)
            # >= kth_bound skips candidates already in the selected list.
            mask = (
                (lbs < bsf2[:, None])
                & (lbs >= kth_bound[:, None])
                & (kth_bound < bsf2)[:, None]
            )
            raws = jax.lax.dynamic_slice_in_dim(raw_file, r2 * rs, rs)
            d = jax.vmap(
                lambda q: ops.euclid_sq(q, raws, impl=impl)
            )(qs)
            d = jnp.where(mask, d, INF)
            j = jnp.argmin(d, axis=1)
            dj = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
            cand = jax.lax.dynamic_slice_in_dim(pos_file, r2 * rs, rs)
            candj = jnp.take(cand, j, axis=0)
            better = dj < bsf2
            bsf_new = jnp.where(better, dj, bsf2)
            pos_new = jnp.where(better, candj, bsfpos2)
            gb_new = gmin(bsf_new)
            pos_new = jnp.where(bsf_new <= gb_new, pos_new, IMAX)
            pos_new = gmin(pos_new)
            return (
                r2 + 1,
                gb_new,
                pos_new,
                reads2 + jnp.sum(mask, axis=1, dtype=jnp.int32),
                upd2 + better.astype(jnp.int32),
            )

        st1 = (jnp.int32(0), bsf, bsfpos, reads, updates)
        r2, bsf, bsfpos, reads, updates = jax.lax.while_loop(
            fcond, fbody, st1
        )
        r = r + r2

    return SearchResult(bsf, bsfpos, gsum(reads), gsum(updates), r)


def _local_batch_knn(
    sax_l: jax.Array,
    raw_l: jax.Array,
    pos_l: jax.Array,
    queries: jax.Array,
    *,
    k: int,
    series_length: int,
    segments: int,
    cardinality: int,
    round_size: int,
    leaf_cap: int,
    axis_names: tuple,
    impl: str,
) -> SearchResult:
    """Per-device body of the batched exact k-NN (runs under shard_map).

    Mirrors the single-host k-safe ``select="topk"`` protocol of
    :func:`repro.core.search._engine_core` — shared ``select_len``,
    the K-th-bound fallback gate, and :func:`repro.core.search.dedup_mask`
    against re-distanced candidates — on top of a per-shard result list.
    Each shard carries a local (Q, k) top list holding ONLY its own
    positions (shards partition the data, so the lists are disjoint); the
    cross-shard merge each round is an ``all_gather`` + ``top_k`` over the
    (S*k,) concatenation, which is duplicate-free by construction. Only the
    globally-agreed k-th distance (the pruning threshold) rides in the
    carry; the final list is one more merge at exit.
    """
    n_local = sax_l.shape[0]
    n_q = queries.shape[0]
    rs = round_size
    qs = isax.znorm(queries)
    qps = isax.paa(qs, segments)
    bpp = isax.padded_breakpoints(cardinality)

    def gmin(x):
        for ax in axis_names:
            x = jax.lax.pmin(x, ax)
        return x

    def gsum(x):
        for ax in axis_names:
            x = jax.lax.psum(x, ax)
        return x

    def gtopk(d, p):
        """Merge ownership-disjoint per-shard (Q, k) lists; replicated."""
        for ax in axis_names:
            d_all = jax.lax.all_gather(d, ax)  # (S, Q, k)
            p_all = jax.lax.all_gather(p, ax)
            dq = jnp.moveaxis(d_all, 0, 1).reshape(n_q, -1)
            pq = jnp.moveaxis(p_all, 0, 1).reshape(n_q, -1)
            neg, sel = jax.lax.top_k(-dq, k)
            d = -neg
            p = jnp.take_along_axis(pq, sel, axis=1)
        return d, p

    def gkth(d):
        """Globally-agreed k-th best distance — the pruning threshold. The
        hot loop needs only this (Q,) vector, so it gathers distances
        alone; positions are merged once at exit via gtopk."""
        for ax in axis_names:
            d_all = jax.lax.all_gather(d, ax)  # (S, Q, k)
            dq = jnp.moveaxis(d_all, 0, 1).reshape(n_q, -1)
            d = -jax.lax.top_k(-dq, k)[0]
        return d[:, -1]

    # Approx phase: seed row 0 of the local list with the shard's best over
    # its first cap rows (rows 1..k-1 stay at INF/NO_POS — the same row-0
    # seeding shape as the single-host engine's init="approx").
    cap = min(leaf_cap, n_local)
    d0 = jax.vmap(lambda q: ops.euclid_sq(q, raw_l[:cap], impl=impl))(qs)
    d0 = jnp.where(pos_l[None, :cap] < 0, INF, d0)  # skip filler rows
    j0 = jnp.argmin(d0, axis=1)
    seed_d = jnp.take_along_axis(d0, j0[:, None], axis=1)[:, 0]
    seed_p = jnp.take(pos_l, j0, axis=0).astype(jnp.int32)
    seed_p = jnp.where(jnp.isfinite(seed_d), seed_p, NO_POS)
    loc_d = jnp.concatenate(
        [seed_d[:, None], jnp.full((n_q, k - 1), INF)], axis=1)
    loc_p = jnp.concatenate(
        [seed_p[:, None], jnp.full((n_q, k - 1), NO_POS)], axis=1)

    # LBC + partial selection (same select_len heuristic and VMEM budget cap
    # as the 1-NN kernel; a tighter cap only means earlier fallback scans).
    lb = ops.lower_bound_sq_batch(qps, sax_l, bpp, series_length, impl=impl)
    budget_rows = (64 * 1024 * 1024) // max(1, n_q * series_length)
    sel_len = search_select_len(n_local, rs)
    sel_len = min(sel_len, max(rs, budget_rows))
    neg, order = jax.lax.top_k(-lb, sel_len)
    order = order.astype(jnp.int32)
    lb_sorted = -neg
    kth_bound = lb_sorted[:, -1]  # worst selected bound per query
    n_rounds = -(-sel_len // rs)
    padded = n_rounds * rs
    if padded > sel_len:
        order = jnp.concatenate(
            [order, jnp.zeros((n_q, padded - sel_len), jnp.int32)], axis=1)
        lb_sorted = jnp.concatenate(
            [lb_sorted, jnp.full((n_q, padded - sel_len), INF)], axis=1)
    # pre-gather, in-bounds indices (see the 1-NN note)
    raw_sel = jnp.take(raw_l, order, axis=0, mode="clip")
    pos_sel = jnp.take(pos_l, order, axis=0, mode="clip")

    def merge(loc_d, loc_p, cand_pos, d):
        d = jnp.where(dedup_mask(cand_pos, loc_d, loc_p), INF, d)
        md = jnp.concatenate([loc_d, d], axis=1)
        mp = jnp.concatenate([loc_p, cand_pos], axis=1)
        neg_d, sel = jax.lax.top_k(-md, k)
        return -neg_d, jnp.take_along_axis(mp, sel, axis=1)

    kth0 = gkth(loc_d)

    def cond(st):
        r, _, _, kth, *_ = st
        head = jax.lax.dynamic_slice_in_dim(lb_sorted, r * rs, 1, axis=1)[:, 0]
        # kth is globally agreed each round, so "any query on any shard
        # still live" is replicated and trip counts stay aligned.
        return (r < n_rounds) & jnp.any(gmin(head) < kth)

    def body(st):
        r, loc_d, loc_p, kth, reads, updates = st
        lbs = jax.lax.dynamic_slice_in_dim(lb_sorted, r * rs, rs, axis=1)
        mask = lbs < kth[:, None]
        raws = jax.lax.dynamic_slice_in_dim(raw_sel, r * rs, rs, axis=1)
        d = jax.vmap(lambda q, rw: ops.euclid_sq(q, rw, impl=impl))(qs, raws)
        cand_pos = jax.lax.dynamic_slice_in_dim(pos_sel, r * rs, rs, axis=1)
        d = jnp.where(mask & (cand_pos >= 0), d, INF)  # drop filler rows
        improved = jnp.min(d, axis=1) < kth
        loc_d, loc_p = merge(loc_d, loc_p, cand_pos, d)
        kth = gkth(loc_d)
        return (
            r + 1,
            loc_d,
            loc_p,
            kth,
            reads + jnp.sum(mask, axis=1, dtype=jnp.int32),
            updates + improved.astype(jnp.int32),
        )

    st0 = (jnp.int32(0), loc_d, loc_p, kth0,
           jnp.full((n_q,), cap, jnp.int32), jnp.zeros((n_q,), jnp.int32))
    r, loc_d, loc_p, kth, reads, updates = jax.lax.while_loop(cond, body, st0)

    if sel_len < n_local:
        # Exactness fallback over the full shard in file order: same gate
        # and skip-mask protocol as the single-host engine; dedup_mask
        # keeps re-distanced ties at the K-th bound out of the list.
        all_rounds = -(-n_local // rs)
        pad_all = all_rounds * rs
        pad_f = pad_all - n_local
        lb_all = (
            jnp.concatenate([lb, jnp.full((n_q, pad_f), INF)], axis=1)
            if pad_f else lb
        )
        raw_file = (
            jnp.concatenate([raw_l, raw_l[:pad_f]], axis=0)
            if pad_f else raw_l
        )
        pos_file = (
            jnp.concatenate([pos_l, pos_l[:pad_f]]) if pad_f else pos_l
        )

        def fcond(st):
            r2, _, _, kth2, *_ = st
            local_need = jnp.any(kth_bound < kth2)
            need_g = gmin(jnp.where(local_need, 0, 1)) < 1
            return (r2 < all_rounds) & need_g

        def fbody(st):
            r2, loc_d, loc_p, kth2, reads2, upd2 = st
            lbs = jax.lax.dynamic_slice_in_dim(lb_all, r2 * rs, rs, axis=1)
            mask = (
                (lbs < kth2[:, None])
                & (lbs >= kth_bound[:, None])
                & (kth_bound < kth2)[:, None]
            )
            raws = jax.lax.dynamic_slice_in_dim(raw_file, r2 * rs, rs)
            d = jax.vmap(lambda q: ops.euclid_sq(q, raws, impl=impl))(qs)
            cand = jax.lax.dynamic_slice_in_dim(pos_file, r2 * rs, rs)
            cand_pos = jnp.broadcast_to(cand[None, :], (n_q, rs))
            d = jnp.where(mask & (cand_pos >= 0), d, INF)
            improved = jnp.min(d, axis=1) < kth2
            loc_d, loc_p = merge(loc_d, loc_p, cand_pos, d)
            kth2 = gkth(loc_d)
            return (
                r2 + 1,
                loc_d,
                loc_p,
                kth2,
                reads2 + jnp.sum(mask, axis=1, dtype=jnp.int32),
                upd2 + improved.astype(jnp.int32),
            )

        st1 = (jnp.int32(0), loc_d, loc_p, kth, reads, updates)
        r2, loc_d, loc_p, kth, reads, updates = jax.lax.while_loop(
            fcond, fbody, st1)
        r = r + r2

    g_d, g_p = gtopk(loc_d, loc_p)
    return SearchResult(g_d, g_p, gsum(reads), gsum(updates), r)


def make_distributed_batch_search(
    mesh: Mesh,
    axes: Sequence[str],
    *,
    series_length: int = 256,
    segments: int = isax.DEFAULT_SEGMENTS,
    cardinality: int = isax.DEFAULT_CARDINALITY,
    round_size: int = 4096,
    leaf_cap: int = 256,
    impl: str = "auto",
    k: int = 1,
):
    """Build the jitted mesh-sharded *batched* search step.

    Returns ``search_step(dist_index, queries) -> SearchResult`` where
    ``queries`` is (Q, n) replicated and every result field is a (Q,) vector
    (``rounds`` stays scalar). Unlike ``make_distributed_search(...,
    batch_queries=Q)`` — which vmaps Q independent single-query loops — this
    runs ONE loop whose collectives reduce the whole BSF vector per round,
    so collective count is independent of Q.

    ``k > 1`` answers exact k-NN instead: ``dist_sq``/``position`` become
    (Q, k) arrays (ascending, sentinel (INF, -1) when the index holds fewer
    than k real series) via the k-safe partial-selection protocol of
    :func:`_local_batch_knn`. ``k`` must not exceed the per-shard padded
    row count for sentinel-free results.
    """
    axes = tuple(axes)
    if k > 1:
        kernel = functools.partial(
            _local_batch_knn,
            k=k,
            series_length=series_length,
            segments=segments,
            cardinality=cardinality,
            round_size=round_size,
            leaf_cap=leaf_cap,
            axis_names=axes,
            impl=impl,
        )
    else:
        kernel = functools.partial(
            _local_batch_search,
            series_length=series_length,
            segments=segments,
            cardinality=cardinality,
            round_size=round_size,
            leaf_cap=leaf_cap,
            axis_names=axes,
            impl=impl,
        )
    row = P(axes, None)
    vec = P(axes)
    rep = P()

    def step(dist_index: DistIndex, queries: jax.Array) -> SearchResult:
        return _shard_map(
            kernel,
            mesh,
            in_specs=(row, row, vec, rep),
            out_specs=SearchResult(rep, rep, rep, rep, rep),
        )(dist_index.sax, dist_index.raw_sorted, dist_index.pos, queries)

    return step


def make_distributed_build(
    mesh: Mesh,
    axes: Sequence[str],
    *,
    segments: int = isax.DEFAULT_SEGMENTS,
    cardinality: int = isax.DEFAULT_CARDINALITY,
    impl: str = "auto",
):
    """Mesh-sharded bulk-loading step: raw chunk -> (sax, root keys).

    The conversion (Stage 2) is embarrassingly parallel over devices; the
    global leaf-order sort stays on the host pipeline (build_pipeline.py)
    which consumes these per-shard outputs. Lowered for the dry-run as the
    ``paris`` arch's build step.
    """
    axes = tuple(axes)
    bp = isax.gaussian_breakpoints(cardinality)

    def local_convert(chunk):
        x = isax.znorm(chunk)
        sax, _ = ops.paa_isax(x, bp, segments, impl=impl, normalize=False)
        return sax, isax.root_key(sax, cardinality)

    row = P(axes, None)
    vec = P(axes)

    def step(chunk: jax.Array):
        return _shard_map(
            local_convert,
            mesh,
            in_specs=(row,),
            out_specs=(row, vec),
        )(chunk)

    return step
