"""Exact and approximate similarity search over a ParIS index (paper §3.3).

Single-device reference implementations; ``core.distributed`` wraps them in
``shard_map`` for the mesh. All algorithms operate on *squared* distances
(sqrt is monotone) and return file-order positions.

Algorithm map (paper -> here):

  approximate search        -> :func:`approx_search` — O(1) root-bucket lookup
                               + true distances over one leaf-sized window of
                               index-sorted neighbors.
  LBC workers (Alg. 10)     -> one vectorized lower-bound pass over the SAX
                               array (the Pallas VPU kernel).
  candidate list, sorted    -> argsort of lower bounds; processed in rounds.
  RDC workers + shared BSF  -> :func:`exact_search` — a ``while_loop`` over
    (Alg. 11)                  candidate rounds; within a round a whole tile of
                               raw series is gathered and distanced (MXU), the
                               BSF updates *between* rounds (the collective-
                               friendly granularity of an atomic update).
  early abandon             -> the loop exits when the smallest unprocessed
                               lower bound >= BSF (list is sorted, so the rest
                               is pruned wholesale).
  nb-ParIS+ (Alg. 7/8)      -> :func:`nb_exact_search` — workers scan disjoint
                               *unsorted* SAX blocks with purely local BSFs.
  ADS+ serial scan          -> :func:`exact_search` with ``sort=False`` (file-
                               order candidate processing, no early exit).
  UCR-Suite optimized scan  -> :func:`brute_force` — full-data distance scan,
                               no index.

Batched query answering (beyond-paper; MESSI-style multi-query execution):

  LBC over a query batch    -> :func:`ops.lower_bound_sq_batch` — one fused
                               (Q, N) kernel pass; the SAX array streams
                               through VMEM once per *batch*, not per query.
  candidate selection       -> per-query selection of the smallest K
                               bounds (``select="topk"``, K = N/16) instead
                               of a full argsort: :func:`select_candidates`.
                               On the TPU a ``top_k`` this wide is a sort of
                               the whole row (log^2 N passes), so from 2^18
                               rows it runs in two stages: strided groups of
                               2,048 keep their 256 smallest, the union
                               (N/8) is sorted again, and a check falls back
                               to the full ``top_k`` where the union may
                               miss the top K. An exactness fallback scan
                               runs only if the K-th bound still beats a
                               query's BSF at list exhaustion.
                               The path is k-safe for k-NN: re-distanced
                               candidates are masked against the current
                               (Q, k) result list by position
                               (:func:`dedup_mask`), so the fallback can
                               never duplicate an entry.
  RDC over a query batch    -> :func:`exact_search_batch` / ``exact_knn_batch``
                               — ONE shared ``while_loop`` with a per-query
                               BSF vector, per-query masked rounds, and a
                               joint early exit when every query's smallest
                               unprocessed lower bound exceeds its own BSF.
                               Once at most Q/8 rows (at least 8) are live,
                               the loop and the fallback scan go on over
                               those rows alone (:func:`_narrowing_loop`).
  single-query API         -> :func:`exact_search` / :func:`exact_knn` are
                               thin Q=1 wrappers over the batch engine;
                               :func:`exact_search_single` keeps the original
                               one-query-at-a-time implementation as the
                               benchmark baseline.

Engine architecture — ONE core, many storage views. The whole RDC
protocol (LBC pass -> per-query candidate order -> masked rounds + BSF
merge -> joint early exit -> exactness fallback) is implemented exactly
once, in :func:`_engine_core`; everything layout-specific enters through
an :class:`EngineView` hook bundle::

    exact_*_batch / make_batch_engine        exact_*_batch_packed
        |                                        |
    _engine_for (index as jit argument)      _packed_engine_for /
        |                                    packed_engine_args
        v                                        v
    _index_view: identity positions          _packed_view: gpos global
    (index.pos), approx-seeded BSF,          translation, masked multi-
    per-index LBC kernel                     component LBC kernel, +inf
            |                                pad lanes, cold BSF
            |                                    |
            +----------------+-------------------+
                             v
               _engine_core(view, queries, ...)

The single-index engines take the index arrays as jit ARGUMENTS (a
closed-over array would be lowered as a constant, which at chip scale no
host can compile); :func:`packed_engine_args` likewise takes the packed
buffers as ARGUMENTS, so an incrementally grown view with stable
capacity (``core.ingest.IncrementalPacker``) reuses one compiled engine
across snapshot swaps. Adding an engine feature (new selection modes,
BSF seeding strategies) is a change to ``_engine_core`` or a new hook —
never two parallel edits.

Service tiers (beyond-paper; the ng-approximate line of "Fast Data
Series Indexing for In-Memory Data"): the SAME engine core answers three
per-request quality tiers, selected by a :class:`Tier` value —

  ``exact``      today's behavior: the loop runs until every query's
                 smallest unprocessed lower bound meets its BSF.
  ``epsilon``    stop a query's rounds once BSF <= (1+eps) * its
                 min-remaining-lower-bound: the answer is provably
                 within (1+eps) of the exact distance (squared-space
                 factor (1+eps)^2; see :func:`tier_arrays`). Candidates
                 whose scaled bound already exceeds the BSF are pruned
                 inside rounds too, which is where the raw-read savings
                 come from.
  ``budget``     best answer after a fixed number of candidate rounds,
                 with the ACHIEVED error bound reported alongside the
                 answer (the engine tracks the smallest lower bound it
                 never distance-checked; ``bsf / that bound`` is an
                 honest upper bound on the answer's error factor).

Tier parameters enter the jitted engines as per-query-row ARRAYS
(``eps_factor_sq``, ``budget_rounds``), not as jit statics: one
compiled tiered engine serves every epsilon value and every budget in a
mixed batch — the jit cache splits only exact vs tiered (see
``_engine_for``), so mixed-SLA serving batches never recompile.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isax, tuning
from repro.core.index import ParISIndex
from repro.kernels import ops

INF = jnp.float32(jnp.inf)


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    """Tuning knobs for the exact-search paths (see field comments)."""
    round_size: int = 4096  # candidates distance-checked per BSF round
    leaf_cap: int = 256  # approximate-search window ("leaf" size)
    sort: bool = True  # sort candidate list by lower bound (ParIS+)
    impl: str = "auto"  # kernel dispatch (ops.py)
    workers: int = 16  # nb- variant only: #independent scan blocks
    select: str = "topk"  # candidate ordering: "topk" partial / "sort" full


_BUDGET_UNLIMITED = np.int32(np.iinfo(np.int32).max)  # "no round budget"


@dataclasses.dataclass(frozen=True)
class Tier:
    """A per-request service tier: how exact must this answer be?

    Three kinds (see the module docstring for the algorithmic contract):

      ``Tier.exact()``        the default; today's exact answer.
      ``Tier.epsilon(eps)``   answer provably within ``(1+eps)`` of the
                              exact distance (``eps >= 0``; ``eps == 0``
                              is exact, just without the bit-exactness
                              promise of the exact path).
      ``Tier.budget(rounds)`` best answer after at most ``rounds``
                              candidate rounds (``rounds >= 1``), with
                              the achieved error bound reported.

    Parameters are validated HERE, at construction — the API edge — so a
    negative epsilon or a zero budget is a ``ValueError`` with a clear
    message instead of a silently exact (or silently empty) answer deep
    inside a jitted loop.
    """

    kind: str = "exact"  # "exact" | "epsilon" | "budget"
    eps: float = 0.0  # epsilon tier: relative error bound, >= 0
    budget_rounds: int = 0  # budget tier: max candidate rounds, >= 1

    def __post_init__(self):
        if self.kind not in ("exact", "epsilon", "budget"):
            raise ValueError(
                f"unknown tier kind {self.kind!r}: expected 'exact', "
                "'epsilon' or 'budget'")
        if self.kind == "epsilon":
            eps = float(self.eps)
            if not eps >= 0.0:  # rejects NaN too
                raise ValueError(
                    f"epsilon tier needs eps >= 0, got {self.eps!r} "
                    "(eps is the relative error bound: the answer is "
                    "guaranteed within (1+eps) of the exact distance)")
        if self.kind == "budget":
            if int(self.budget_rounds) < 1:
                raise ValueError(
                    f"budget tier needs budget_rounds >= 1, got "
                    f"{self.budget_rounds!r} (the engine must run at "
                    "least one candidate round to produce an answer)")

    @staticmethod
    def exact() -> "Tier":
        """The exact tier (today's default behavior)."""
        return Tier("exact")

    @staticmethod
    def epsilon(eps: float) -> "Tier":
        """An epsilon tier: answers within ``(1+eps)`` of exact."""
        return Tier("epsilon", eps=float(eps))

    @staticmethod
    def budget(rounds: int) -> "Tier":
        """A budget tier: best answer after ``rounds`` candidate rounds."""
        return Tier("budget", budget_rounds=int(rounds))


def as_tier(tier) -> Tier:
    """Normalize a user-facing tier argument to a :class:`Tier`.

    Accepts ``None`` (exact), the string ``"exact"``, or a :class:`Tier`.
    Epsilon/budget tiers carry parameters, so their string forms are not
    accepted — construct them via :meth:`Tier.epsilon` /
    :meth:`Tier.budget`.
    """
    if tier is None:
        return Tier.exact()
    if isinstance(tier, Tier):
        return tier
    if tier == "exact":
        return Tier.exact()
    raise ValueError(
        f"tier must be None, 'exact' or a Tier instance, got {tier!r}")


def tier_arrays(tiers) -> tuple:
    """Per-row engine parameters for a sequence of :class:`Tier` values.

    Returns ``((Q,) float32 eps_factor_sq, (Q,) int32 budget_rounds)``.
    The engine works in SQUARED distances, so the (1+eps) true-distance
    guarantee becomes the factor ``(1+eps)**2`` here; exact and budget
    rows carry factor 1.0. Budget rows carry their round budget; exact
    and epsilon rows are unlimited (INT32_MAX — no real candidate list
    has that many rounds).
    """
    fac = np.ones((len(tiers),), np.float32)
    bud = np.full((len(tiers),), _BUDGET_UNLIMITED, np.int32)
    for i, t in enumerate(tiers):
        if t.kind == "epsilon":
            fac[i] = (1.0 + t.eps) ** 2
        elif t.kind == "budget":
            bud[i] = t.budget_rounds
    return jnp.asarray(fac), jnp.asarray(bud)


def achieved_epsilon(achieved_factor_sq) -> np.ndarray:
    """Squared-space achieved factor -> achieved epsilon, host side.

    The tiered engine reports, per query, ``bsf_sq / denom_sq`` where
    ``denom_sq`` is the smallest lower bound it never distance-checked
    (1.0 when nothing qualifying was skipped): the answer's true distance
    is within ``sqrt(factor)`` of exact. This converts to the additive
    epsilon form users reason in: ``achieved_eps = sqrt(factor) - 1``,
    clamped at 0 (an exact answer achieves epsilon 0). ``inf`` means a
    budget so tight the engine can certify nothing.
    """
    f = np.asarray(achieved_factor_sq, np.float64)
    return np.maximum(np.sqrt(np.maximum(f, 1.0)) - 1.0, 0.0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SearchResult:
    """One exact 1-NN answer plus the paper's per-query instrumentation."""
    dist_sq: jax.Array  # squared distance of the 1-NN
    position: jax.Array  # file-order offset of the 1-NN
    raw_reads: jax.Array  # series whose raw data was fetched (Fig. 20b)
    bsf_updates: jax.Array  # BSF improvements after init (Fig. 20a)
    rounds: jax.Array  # candidate rounds executed


def _query_paa(index: ParISIndex, query: jax.Array) -> tuple:
    q = isax.znorm(query)
    return q, isax.paa(q, index.segments)


def bucket_window_start(bucket_offsets: jax.Array, keys: jax.Array,
                        leaf_cap: int, num_series: int) -> jax.Array:
    """Start row of each query's ``leaf_cap`` seed window, in leaf order.

    The window is centered on the query's root bucket (an empty or small
    bucket degrades gracefully to its leaf-order neighbors) and clamped
    to the array. This is THE definition of where approximate search
    looks: :func:`approx_search`/:func:`approx_search_batch` (in-memory)
    and the cold tier's seed (``core.coldtier``, which reads the same
    window as one contiguous disk range) must use it unchanged —
    bit-exactness of the cold path's approx-seeded engines depends on
    the window math having exactly one home.
    """
    starts = bucket_offsets[keys]
    ends = bucket_offsets[keys + 1]
    pad = jnp.maximum(leaf_cap - (ends - starts), 0) // 2
    return jnp.clip(starts - pad, 0, num_series - leaf_cap)


def approx_search(
    index: ParISIndex, query: jax.Array, leaf_cap: int = 256,
    impl: str = "auto",
) -> tuple:
    """Initial BSF: true distances over the query's root-bucket neighborhood.

    The paper walks root->leaf and scans that leaf. Our flat index sorts
    series in leaf order, so the analogue is a fixed ``leaf_cap`` window of
    index-sorted entries starting at the query's bucket (an empty bucket
    degrades gracefully to the nearest neighbors in leaf order). Returns
    (bsf_sq, file position).
    """
    # Tiny indices: a window larger than the index would push the clip's
    # upper bound negative (below its lower bound) — clamp the cap first.
    leaf_cap = min(int(leaf_cap), index.num_series)
    q, qp = _query_paa(index, query)
    qsax = isax.sax_from_paa(qp, index.cardinality)
    key = isax.root_key(qsax, index.cardinality)
    s = bucket_window_start(
        index.bucket_offsets, key, leaf_cap, index.num_series)
    window = jax.lax.dynamic_slice_in_dim(index.pos, s, leaf_cap)
    raws = jnp.take(index.raw, window, axis=0)
    d = ops.euclid_sq(q, raws, impl=impl)
    j = jnp.argmin(d)
    return d[j], window[j]


def approx_search_batch(
    index: ParISIndex, queries: jax.Array, leaf_cap: int = 256,
    impl: str = "auto",
) -> tuple:
    """Batched :func:`approx_search`: (Q, n) queries -> ((Q,) bsf, (Q,) pos).

    Same bucket-window scan per query, vectorized; seeds the per-query BSF
    vector of the batched RDC loop.
    """
    leaf_cap = min(int(leaf_cap), index.num_series)
    qs = isax.znorm(queries)
    qps = isax.paa(qs, index.segments)
    qsax = isax.sax_from_paa(qps, index.cardinality)
    keys = isax.root_key(qsax, index.cardinality)
    s = bucket_window_start(
        index.bucket_offsets, keys, leaf_cap, index.num_series)

    def one(q, si):
        window = jax.lax.dynamic_slice_in_dim(index.pos, si, leaf_cap)
        raws = jnp.take(index.raw, window, axis=0)
        d = ops.euclid_sq(q, raws, impl=impl)
        j = jnp.argmin(d)
        return d[j], window[j]

    return jax.vmap(one)(qs, s)


def _pad_to(x: jax.Array, size: int, fill) -> jax.Array:
    pad = size - x.shape[0]
    if pad <= 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])


def _pad_cols(x: jax.Array, size: int, fill) -> jax.Array:
    pad = size - x.shape[1]
    if pad <= 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((x.shape[0], pad), fill, x.dtype)], axis=1
    )


def select_len(n: int, round_size: int) -> int:
    """Per-query candidate-list length K of the partial selection.

    Shared by the single-host batch engine and the distributed batch kernel:
    the exactness-fallback protocol on both sides assumes the K-th selected
    bound comes from exactly this K, so there is ONE definition.
    """
    return min(n, max(n // 16, 4 * round_size))


# Two-stage selection: strided groups of SELECT_GROUP rows keep their
# SELECT_KEEP smallest bounds each, and the union is sorted again. Engaged
# only from SELECT_TWO_STAGE_MIN rows, where K = N/16 (select_len).
SELECT_GROUP = 2048
SELECT_KEEP = SELECT_GROUP // 8
SELECT_TWO_STAGE_MIN = 1 << 18


def _full_select(lb: jax.Array, sel_len: int) -> tuple:
    neg, order = jax.lax.top_k(-lb, sel_len)
    return -neg, order.astype(jnp.int32)


def _by_blocks(fn, lb: jax.Array):
    """``fn`` over blocks of at most 8 rows of ``lb``, outputs joined
    along the rows: a sort's buffers then hold one block, not the batch."""
    n_q, n = lb.shape
    block = math.gcd(n_q, 8)
    out = jax.lax.map(fn, lb.reshape(n_q // block, block, n))
    return jax.tree.map(lambda x: x.reshape(n_q, *x.shape[2:]), out)


def _group_heads(lb: jax.Array) -> tuple:
    """(b, N) bounds -> (b, G, SELECT_KEEP) smallest bounds of each strided
    group and their row ids, ascending by (bound, row)."""
    b, n = lb.shape
    groups = -(-n // SELECT_GROUP)
    shape = (b, groups, SELECT_GROUP)
    vals = jnp.swapaxes(
        _pad_cols(lb, groups * SELECT_GROUP, INF).reshape(
            b, SELECT_GROUP, groups), 1, 2)
    idx = (jax.lax.broadcasted_iota(jnp.int32, shape, 2) * groups
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    # (bound, row) pairs are distinct, so the sort need not be stable.
    vals, idx = jax.lax.sort((vals, idx), dimension=2, num_keys=2,
                             is_stable=False)
    return vals[:, :, :SELECT_KEEP], idx[:, :, :SELECT_KEEP]


def select_candidates(lb: jax.Array, sel_len: int) -> tuple:
    """(Q, N) bounds -> the ``sel_len`` smallest of each row, ascending.

    Returns ``(lb_sel, order)``, bit-identical to ``lax.top_k(-lb,
    sel_len)`` negated back: the same values and row indices, ties toward
    the lower index. On the TPU a ``top_k`` this wide is a sort of the
    whole row, whose passes grow as log^2 N. Where ``sel_len`` is N/16 and
    N >= ``SELECT_TWO_STAGE_MIN`` it runs in two stages instead:

    1. Row i = a * G + b is slot a of strided group b (G = N_pad /
       ``SELECT_GROUP``, +inf padding). Each group is sorted by (bound,
       index) and keeps its ``SELECT_KEEP`` smallest, 8 queries at a time.
    2. The union, N_pad / 8 per row, is sorted by (bound, index) and cut
       to ``sel_len``.

    The union holds the exact top ``sel_len`` when every group's largest
    kept bound is strictly above the ``sel_len``-th selected one: all rows
    a group dropped lie above it. If any query of the batch misses that
    check, a ``lax.cond`` selects with the full ``top_k`` instead, under
    the ``paris.select.full`` scope (opened in the taken branch only).
    """
    n_q, n = lb.shape
    if sel_len != n // 16 or n < SELECT_TWO_STAGE_MIN:
        return _full_select(lb, sel_len)
    vals, idx = _by_blocks(_group_heads, lb)
    lb_sel, order = jax.lax.sort(
        (vals.reshape(n_q, -1), idx.reshape(n_q, -1)), dimension=1,
        num_keys=2, is_stable=False)
    lb_sel, order = lb_sel[:, :sel_len], order[:, :sel_len]
    exact = jnp.all(vals[:, :, -1] > lb_sel[:, -1:])

    @jax.named_scope("paris.select.full")
    def full(lb):
        return _by_blocks(functools.partial(_full_select, sel_len=sel_len),
                          lb)

    return jax.lax.cond(exact, lambda lb: (lb_sel, order), full, lb)


# Narrow RDC rounds: a batch of Q rows runs its rounds over the live rows
# only once at most max(NARROW_MIN_ROWS, Q // 8) of them are live; batches
# of NARROW_MIN_ROWS rows and fewer always run at full width.
NARROW_MIN_ROWS = 8


def _take_rows(x: jax.Array, slot: jax.Array) -> jax.Array:
    """Rows ``slot`` of ``x``; an out-of-range slot reads a filler row of
    +inf (floats) or 0 (ints, False), which no loop predicate admits."""
    fill = jnp.inf if jnp.issubdtype(x.dtype, jnp.inexact) else 0
    return jnp.take(x, slot, axis=0, mode="fill", fill_value=fill)


def _narrowing_loop(cond, body, live, rows: dict, st: tuple) -> tuple:
    """``lax.while_loop`` over a batch's rows, narrowed to its live rows.

    ``rows`` holds the loop's per-row inputs and ``st`` is ``(r, *per-row
    state)``, all with the batch's Q rows on axis 0. ``cond(rows, st)`` and
    ``body(rows, st)`` are the loop's own, at any number of rows, and
    ``live(rows, st)`` -> (Q,) marks the rows whose state a round can still
    change: a row that is not live never becomes live again, and a round
    leaves its state as it is. So a row's rounds give the same state
    whatever width they run at.

    With width W = max(``NARROW_MIN_ROWS``, Q // 8) < Q, the loop runs
    over all Q rows while more than W are live, then goes on from the same
    round over a (W, ...) gather of the live rows (empty slots are filler
    rows, :func:`_take_rows`) and scatters their state back; a filler
    slot writes nothing. Q <= W is the plain loop. Returns the final state
    and the number of rounds that ran narrow.
    """
    n_q = st[1].shape[0]
    width = max(NARROW_MIN_ROWS, n_q // 8)

    def loop(rows, st, more=None):
        def go(s):
            c = cond(rows, s)
            return c if more is None else c & more(rows, s)
        return jax.lax.while_loop(go, functools.partial(body, rows), st)

    if n_q <= width:
        return loop(rows, st), jnp.int32(0)
    st = loop(rows, st, lambda rows, s: jnp.sum(live(rows, s)) > width)
    slot = jnp.nonzero(live(rows, st), size=width, fill_value=n_q)[0]
    narrow = loop(jax.tree.map(lambda x: _take_rows(x, slot), rows),
                  st[:1] + tuple(_take_rows(x, slot) for x in st[1:]))
    merged = tuple(x.at[slot].set(y, mode="drop")
                   for x, y in zip(st[1:], narrow[1:]))
    return narrow[:1] + merged, narrow[0] - st[0]


NO_POS = jnp.int32(-1)  # sentinel position of an unfilled k-NN result slot
_NP_NO_POS = int(NO_POS)  # host-side value (np packing code, no tracing)


def dedup_mask(cand_pos: jax.Array, top_d: jax.Array,
               top_p: jax.Array) -> jax.Array:
    """(Q, R) mask of candidates already present in the (Q, k) result list.

    The k-safety primitive of the ``select="topk"`` protocol (shared by the
    single-host engine and the distributed batch kernel): the exactness
    fallback — and, under ``init="approx"``, the main loop — re-distances
    candidates that may have been merged before. A candidate can only be a
    duplicate if its position currently sits in ``top_p``: once evicted, its
    distance is >= the k-th best forever after (distances are immutable and
    the k-th best only decreases), so it can never re-enter. Unfilled slots
    hold ``NO_POS`` (-1) + INF and match no real candidate.
    """
    return jnp.any(
        (cand_pos[:, :, None] == top_p[:, None, :])
        & (top_d[:, None, :] < INF),
        axis=2,
    )


def merge_top_lists(dists: list, positions: list, k: int) -> tuple:
    """Merge ownership-disjoint (..., k_i) top lists into the global top-k.

    The one merge protocol shared by every partitioned exact-search caller
    (``serving.router.ShardedSearchRouter``, ``core.ingest.MutableIndex``):
    per-partition result lists are concatenated along the last axis —
    callers pass partitions in ascending file-offset order with positions
    already translated to global file offsets — and reduced with a stable
    ascending argsort on distance, so ties (and only ties) resolve toward
    the lower file position and sentinel (INF, ``NO_POS``) slots sink,
    surviving only when the whole datastore holds fewer than ``k`` series.
    Partitions own disjoint file ranges, so the concatenation is
    duplicate-free by construction and the k smallest entries are exactly
    the single-index answer.
    """
    d = np.concatenate([np.asarray(x) for x in dists], axis=-1)
    p = np.concatenate([np.asarray(x) for x in positions], axis=-1)
    order = np.argsort(d, axis=-1, kind="stable")[..., :k]
    return (
        np.take_along_axis(d, order, axis=-1),
        np.take_along_axis(p, order, axis=-1),
    )


@dataclasses.dataclass(frozen=True)
class EngineView:
    """The storage hooks that specialize the ONE RDC engine core.

    :func:`_engine_core` implements the whole batched protocol — LBC pass,
    candidate selection, masked rounds, BSF merge, exactness fallback —
    exactly once; everything layout-specific lives behind these hooks:

      n_rows        candidate rows the LBC pass covers (N for a single
                    index; the block-padded N_pad for a packed buffer)
      num_series    real series behind those rows, for k validation;
                    ``None`` skips the check (the caller already clamped k)
      segments      PAA word width of the stored SAX rows
      lower_bounds  ((Q, w) query PAA, impl) -> (Q, n_rows) squared lower
                    bounds; rows that are padding must come back +inf so
                    no selection or round mask can ever admit them
      positions     candidate row ids -> file positions (identity-order
                    ``index.pos`` lookup, or the packed ``gpos``
                    translation; :data:`NO_POS` at pad rows)
      gather_raw    file positions -> raw series rows; a clipped gather,
                    so a :data:`NO_POS` sentinel reads row 0 harmlessly —
                    its +inf lower bound keeps it outside every mask
      seed          ``None`` starts every BSF at +inf; else ((Q, n) queries,
                    impl) -> ((Q,) bsf, (Q,) pos, leaf reads) — the
                    approx-search seeding of the single-index path
    """

    n_rows: int
    num_series: Optional[int]
    segments: int
    lower_bounds: Callable
    positions: Callable
    gather_raw: Callable
    seed: Optional[Callable] = None


def _index_view(
    index: ParISIndex, *, leaf_cap: int, init: str,
    blocks: Optional[tuple] = None,
) -> EngineView:
    """Single-index hooks: identity positions + approx-seeded BSF.

    ``blocks`` is an optional ``(block_q, block_n)`` override for the
    lower-bound kernel; ``None`` (or ``None`` members) resolve through
    the tuning table inside ``ops`` — see ``repro.core.tuning``.
    """
    bpp = isax.padded_breakpoints(index.cardinality)
    block_q, block_n = blocks or (None, None)

    def lower_bounds(qps, impl):
        return ops.lower_bound_sq_batch(
            qps, index.sax, bpp, index.series_length, impl=impl,
            block_q=block_q, block_n=block_n,
        )

    if init == "approx":
        leaf = min(int(leaf_cap), index.num_series)

        def seed(queries, impl):
            bsf0, pos0 = approx_search_batch(index, queries, leaf, impl)
            return bsf0, pos0, leaf
    else:
        seed = None

    return EngineView(
        n_rows=index.num_series,
        num_series=index.num_series,
        segments=index.segments,
        lower_bounds=lower_bounds,
        positions=lambda idx: jnp.take(index.pos, idx, axis=0),
        gather_raw=lambda pos: jnp.take(index.raw, pos, axis=0,
                                        mode="clip"),
        seed=seed,
    )


def _engine_core(
    view: EngineView,
    queries: jax.Array,
    *,
    k: int,
    round_size: int,
    sort: bool,
    select: str,
    impl: str,
    eps_factor_sq: Optional[jax.Array] = None,
    budget_rounds: Optional[jax.Array] = None,
    seed0: Optional[tuple] = None,
    n_real: Optional[jax.Array] = None,
) -> tuple:
    """THE batched RDC loop — the single engine core behind every search.

    (Q, n) queries -> ((Q, k) dists, (Q, k) positions, (Q,) reads,
    (Q,) bsf updates, rounds). One ``while_loop`` drives all Q queries:
    per-query BSF vector, per-query candidate order, per-query round masks,
    and a joint early exit once no query's next lower bound beats its BSF.
    Storage layout (single index vs packed multi-component buffer) enters
    only through the :class:`EngineView` hooks.

    The rounds run narrow (:func:`_narrowing_loop`): once at most
    max(``NARROW_MIN_ROWS``, Q // 8) rows are live — their next bound (in
    the fallback scan, their K-th selected bound) still below their k-th
    distance — the main loop and the fallback scan go on over those rows
    alone. Each row's candidates, masks, distances and merges are the same
    at either width, so answers, reads, updates and rounds are too.

    ``n_real`` (a traced int) says that only rows ``[0, n_real)`` are real:
    the rest are pads, never live, so they neither extend a loop nor keep
    it wide, and their outputs are meaningless. Given it, the outputs gain
    a last element, the number of rounds (main loop and scan together)
    that ran narrow. ``None`` means every row is real and keeps the
    historical outputs.

    ``select="topk"`` keeps only the K smallest bounds per query
    (K = max(N/16, 4*round_size)), chosen by :func:`select_candidates` (two
    sorts of short rows from 2^18 rows up, one ``top_k`` below, which the
    TPU runs as a sort of the whole row); exactness is preserved by a
    fallback scan over the full row order that only runs for queries whose
    K-th bound still beats their k-th best distance when the truncated list
    is exhausted (rare — raw reads are ~1-4% of N on the paper's workloads).
    The path is k-safe: the fallback (and, under an approx seed, the main
    loop) re-distances already-seen candidates, and for k > 1 every merge
    masks candidates whose position already sits in the result list
    (:func:`dedup_mask`), so no entry can be duplicated. Unfilled result
    slots are (INF, :data:`NO_POS`).

    ``sort=False`` (the ADS+-style serial scan, row order, no early exit)
    requires a per-query-shared row order and is only offered by the
    single-index adapters.

    Service tiers: passing BOTH ``eps_factor_sq`` ((Q,) float32,
    :func:`tier_arrays`) and ``budget_rounds`` ((Q,) int32) switches the
    core to its TIERED variant, which appends a sixth output — the
    per-query achieved squared error factor. Every loop predicate and
    round mask compares ``lower_bound * eps_factor_sq`` against the BSF
    (factor 1.0 == exact semantics), rounds past a row's budget go
    inactive, and the core tracks the smallest lower bound each query
    skipped ONLY because of its tier, so the reported factor
    ``bsf / min_skipped_bound`` is a sound upper bound on the answer's
    squared error. Without tier arrays the returned 5-tuple — and the
    traced computation — are exactly the historical exact path, keeping
    it bit-identical (golden-tested). Tiers require ``sort=True`` (the
    frontier predicate is what an unsorted scan lacks).
    """
    if view.num_series is not None and not 1 <= k <= view.num_series:
        raise ValueError(f"k={k} outside [1, {view.num_series}]")
    tiered = eps_factor_sq is not None
    if tiered and budget_rounds is None:
        raise ValueError("tiered engine needs both eps_factor_sq and "
                         "budget_rounds (see tier_arrays)")
    if tiered and not sort:
        raise ValueError("service tiers require the sorted-candidate "
                         "engine (sort=True)")
    n_rows = view.n_rows
    n_q = queries.shape[0]
    rs = round_size
    qs = isax.znorm(queries)
    qps = isax.paa(qs, view.segments)

    if seed0 is not None:
        seed_d, seed_p = seed0
        top_d0 = jnp.concatenate(
            [seed_d[:, None], jnp.full((n_q, k - 1), INF)], axis=1
        )
        top_p0 = jnp.concatenate(
            [seed_p.astype(jnp.int32)[:, None],
             jnp.full((n_q, k - 1), NO_POS)], axis=1,
        )
        reads0 = jnp.zeros((n_q,), jnp.int32)
    elif view.seed is not None:
        bsf0, pos0, leaf = view.seed(queries, impl)
        top_d0 = jnp.concatenate(
            [bsf0[:, None], jnp.full((n_q, k - 1), INF)], axis=1
        )
        top_p0 = jnp.concatenate(
            [pos0.astype(jnp.int32)[:, None],
             jnp.full((n_q, k - 1), NO_POS)], axis=1,
        )
        reads0 = jnp.full((n_q,), leaf, jnp.int32)
    else:
        top_d0 = jnp.full((n_q, k), INF)
        top_p0 = jnp.full((n_q, k), NO_POS)
        reads0 = jnp.zeros((n_q,), jnp.int32)

    # --- LBC phase: ONE fused (Q, n_rows) pass over the SAX rows. ---
    lb = view.lower_bounds(qps, impl)

    # --- Per-query candidate orders. Selection ties break toward the lower
    # index, exactly like a stable ascending argsort of lb. The ``paris.*``
    # named scopes change op metadata only: a profile reads selection, the RDC
    # loop and the fallback scan by these names. ---
    sel_len = select_len(n_rows, rs) if sort and select == "topk" else n_rows
    n_rounds = -(-sel_len // rs)
    padded = n_rounds * rs
    if sort:
        with jax.named_scope("paris.select"):
            lb_sel, order = select_candidates(lb, sel_len)
            lb_sel_p = _pad_cols(lb_sel, padded, INF)
            order_p = _pad_cols(order, padded, 0)
    else:
        lb_sel = lb
        lb_sel_p = _pad_cols(lb_sel, padded, INF)
        shared_order_p = _pad_to(
            jnp.arange(n_rows, dtype=jnp.int32), padded, 0
        )

    # Per-row inputs of the rounds: what a narrowed loop gathers.
    real = jnp.arange(n_q) < (n_q if n_real is None else n_real)
    rows = {"qs": qs, "real": real}
    if tiered:
        rows.update(eps=eps_factor_sq, budget=budget_rounds)

    def _euclid_rows(qs, raws):
        # (Q, rs, n) per-query candidates -> (Q, rs) distances.
        return jax.vmap(
            lambda q, rw: ops.euclid_sq(q, rw, impl=impl)
        )(qs, raws)

    def _euclid_shared(qs, raws):
        # (rs, n) candidates shared by every query -> (Q, rs) distances.
        return jax.vmap(lambda q: ops.euclid_sq(q, raws, impl=impl))(qs)

    def merge(top_d, top_p, cand_pos, d):
        if k == 1:  # 1-NN: plain argmin/where, no concat + selection pass
            j = jnp.argmin(d, axis=1)
            dj = jnp.take_along_axis(d, j[:, None], axis=1)
            pj = jnp.take_along_axis(cand_pos, j[:, None], axis=1)
            better = dj < top_d  # strict: ties keep the incumbent
            return (
                jnp.where(better, dj, top_d),
                jnp.where(better, pj, top_p),
            )
        # k-safety: a re-distanced candidate (approx seed, fallback scan,
        # ties at the K-th bound) must not enter the list twice.
        d = jnp.where(dedup_mask(cand_pos, top_d, top_p), INF, d)
        md = jnp.concatenate([top_d, d], axis=1)
        mp = jnp.concatenate([top_p, cand_pos], axis=1)
        neg_d, sel = jax.lax.top_k(-md, k)  # the TPU sorts the k + rs row
        return -neg_d, jnp.take_along_axis(mp, sel, axis=1)

    def head(rows, r):
        return jax.lax.dynamic_slice_in_dim(
            rows["lb"], r * rs, 1, axis=1)[:, 0]

    def live(rows, st):
        # A round changes a row only through candidates below its k-th
        # distance; a tier merely narrows which of them it reads, and
        # records the rest in skip_lb. The serial scan has no frontier.
        if not sort:
            return rows["real"]
        return rows["real"] & (head(rows, st[0]) < st[1][:, -1])

    def cond(rows, st):
        r, top_d = st[0], st[1]
        more = r < n_rounds
        if sort:  # joint early exit: every query's next bound >= its BSF
            h = head(rows, r)
            if tiered:
                # A row is done when its scaled frontier meets its BSF
                # (epsilon early stop; factor 1.0 == exact) or its round
                # budget is spent.
                go = ((r < rows["budget"])
                      & (h * rows["eps"] < top_d[:, -1]))
            else:
                go = h < top_d[:, -1]
            more &= jnp.any(rows["real"] & go)
        return more

    def body(rows, st):
        if tiered:
            r, top_d, top_p, reads, updates, skip_lb = st
        else:
            r, top_d, top_p, reads, updates = st
        kth = top_d[:, -1]
        lbs = jax.lax.dynamic_slice_in_dim(rows["lb"], r * rs, rs, axis=1)
        if sort:
            idx = jax.lax.dynamic_slice_in_dim(
                rows["order"], r * rs, rs, axis=1)
            cand_pos = view.positions(idx)  # (Q, rs)
            raws = view.gather_raw(cand_pos)  # the "disk reads"
            d = _euclid_rows(rows["qs"], raws)
        else:
            idx = jax.lax.dynamic_slice_in_dim(shared_order_p, r * rs, rs)
            pos1 = view.positions(idx)  # (rs,) row-order scan
            raws = view.gather_raw(pos1)
            d = _euclid_shared(rows["qs"], raws)
            cand_pos = jnp.broadcast_to(pos1[None, :], d.shape)
        if tiered:
            # The tier mask is a subset of the exact mask (factor >= 1):
            # candidates the exact engine would have checked but the tier
            # skips feed the achieved-bound tracker.
            would = lbs < kth[:, None]
            mask = (
                (lbs * rows["eps"][:, None] < kth[:, None])
                & (r < rows["budget"])[:, None]
            )
            skip_lb = jnp.minimum(
                skip_lb,
                jnp.min(jnp.where(would & ~mask, lbs, INF), axis=1),
            )
        else:
            mask = lbs < kth[:, None]
        d = jnp.where(mask, d, INF)
        improved = jnp.min(d, axis=1) < kth
        top_d, top_p = merge(top_d, top_p, cand_pos, d)
        out = (
            r + 1,
            top_d,
            top_p,
            reads + jnp.sum(mask, axis=1, dtype=jnp.int32),
            updates + improved.astype(jnp.int32),
        )
        if tiered:
            out = out + (skip_lb,)
        return out

    st0 = (jnp.int32(0), top_d0, top_p0, reads0,
           jnp.zeros((n_q,), jnp.int32))
    if tiered:
        st0 = st0 + (jnp.full((n_q,), INF),)
    main_rows = dict(rows, lb=lb_sel_p)
    if sort:
        main_rows["order"] = order_p
    with jax.named_scope("paris.rdc"):
        (r, top_d, top_p, reads, updates, *skip), narrow = _narrowing_loop(
            cond, body, live, main_rows, st0)
    r_main = r
    skip_lb = skip[0] if tiered else None

    if sort and select == "topk" and sel_len < n_rows:
        # Exactness fallback: a query whose worst *selected* bound still
        # beats its BSF might have unselected qualifying candidates — scan
        # the full row order with per-query (bound, need) masks. The gate is
        # re-evaluated every round, so it tightens as BSFs improve. The
        # whole loop (including its padded-copy setup) lives inside a
        # lax.cond: in the common case no query needs it and the branch —
        # and its buffer copies — are skipped entirely. Its scope opens
        # inside the taken branch, so a batch that skips the scan runs no
        # op under ``paris.fallback`` (the cond op itself stays outside).
        # The scan runs narrow as the main loop does, with ``need`` for
        # live: most batches start it with a few rows in need, and then
        # only its distances and merges shrink; its gather is shared.
        kth_bound = lb_sel[:, -1]
        all_rounds = -(-n_rows // rs)
        pad_all = all_rounds * rs

        def flive(rows, fst):
            return rows["real"] & (rows["kth_bound"] < fst[1][:, -1])

        @jax.named_scope("paris.fallback")
        def run_fallback(st):
            idx_all = _pad_to(
                jnp.arange(n_rows, dtype=jnp.int32), pad_all, 0)
            scan_rows = dict(rows, lb=_pad_cols(lb, pad_all, INF),
                             kth_bound=kth_bound)

            def fcond(rows, fst):
                r2, top_d = fst[0], fst[1]
                if tiered:
                    active = (r_main + r2) < rows["budget"]
                    go = active & (rows["kth_bound"] * rows["eps"]
                                   < top_d[:, -1])
                else:
                    go = rows["kth_bound"] < top_d[:, -1]
                return (r2 < all_rounds) & jnp.any(rows["real"] & go)

            def fbody(rows, fst):
                if tiered:
                    r2, top_d, top_p, reads, updates, skip_lb = fst
                else:
                    r2, top_d, top_p, reads, updates = fst
                kth = top_d[:, -1]
                kth_bound = rows["kth_bound"]
                if tiered:
                    need = (
                        (kth_bound * rows["eps"] < kth)
                        & ((r_main + r2) < rows["budget"])
                    )
                else:
                    need = kth_bound < kth
                lbs = jax.lax.dynamic_slice_in_dim(
                    rows["lb"], r2 * rs, rs, axis=1)
                idx = jax.lax.dynamic_slice_in_dim(idx_all, r2 * rs, rs)
                pos1 = view.positions(idx)
                raws = view.gather_raw(pos1)
                d = _euclid_shared(rows["qs"], raws)
                # lbs >= kth_bound skips candidates the main loop already
                # processed (everything strictly below the K-th bound was
                # in the selected list); ties at the bound re-distance
                # harmlessly.
                if tiered:
                    gate = lbs * rows["eps"][:, None] < kth[:, None]
                else:
                    gate = lbs < kth[:, None]
                mask = (
                    gate
                    & (lbs >= kth_bound[:, None])
                    & need[:, None]
                )
                if tiered:
                    # Candidates the EXACT fallback would have checked
                    # but the tier gate/budget skipped feed the
                    # achieved-bound tracker, same as the main loop.
                    would = (lbs < kth[:, None]) & (
                        lbs >= kth_bound[:, None])
                    skip_lb = jnp.minimum(
                        skip_lb,
                        jnp.min(jnp.where(would & ~mask, lbs, INF),
                                axis=1),
                    )
                d = jnp.where(mask, d, INF)
                improved = jnp.min(d, axis=1) < kth
                cand_pos = jnp.broadcast_to(pos1[None, :], d.shape)
                top_d, top_p = merge(top_d, top_p, cand_pos, d)
                out = (
                    r2 + 1,
                    top_d,
                    top_p,
                    reads + jnp.sum(mask, axis=1, dtype=jnp.int32),
                    updates + improved.astype(jnp.int32),
                )
                if tiered:
                    out = out + (skip_lb,)
                return out

            return _narrowing_loop(fcond, fbody, flive, scan_rows, st)

        st1 = (jnp.int32(0), top_d, top_p, reads, updates)
        if tiered:
            st1 = st1 + (skip_lb,)
            need0 = jnp.any(
                real
                & (kth_bound * eps_factor_sq < top_d[:, -1])
                & (r_main < budget_rounds))
        else:
            need0 = jnp.any(real & (kth_bound < top_d[:, -1]))
        (r2, top_d, top_p, reads, updates, *skip), fb_narrow = jax.lax.cond(
            need0, run_fallback, lambda st: (st, jnp.int32(0)), st1)
        skip_lb = skip[0] if tiered else None
        narrow = narrow + fb_narrow
        fb_r2, fb_all_rounds = r2, all_rounds
        r = r + r2
    else:
        fb_r2 = None

    out = (top_d, top_p, reads, updates, r)
    if tiered:
        # Achieved squared error factor, per query: the BSF over the
        # smallest lower bound never distance-checked. Three sources of
        # unchecked candidates: (a) candidates a round mask (main loop or
        # fallback) skipped only because of the tier (skip_lb), (b) the
        # unprocessed tail of the selected list (its head bound — the
        # frontier — under-bounds all of it), (c) under select="topk",
        # unselected rows (>= the K-th selected bound) in rounds the
        # fallback never reached — charged only when the fallback did NOT
        # scan the whole row order; a completed scan leaves nothing
        # unchecked. If the minimum of those still exceeds the BSF
        # nothing better can exist and the answer is certified exact
        # (factor 1.0) — this also absorbs denom == 0 == bsf.
        kth_final = top_d[:, -1]
        frontier_at = jax.lax.dynamic_slice_in_dim(
            lb_sel_p, jnp.minimum(r_main, n_rounds - 1) * rs, 1, axis=1
        )[:, 0]
        frontier = jnp.where(r_main < n_rounds, frontier_at, INF)
        denom = jnp.minimum(skip_lb, frontier)
        if fb_r2 is not None:
            trunc = jnp.where(fb_r2 >= fb_all_rounds, INF, kth_bound)
            denom = jnp.minimum(denom, trunc)
        achieved_sq = jnp.where(
            denom >= kth_final, jnp.float32(1.0), kth_final / denom)
        out = out + (achieved_sq,)
    if n_real is not None:
        out = out + (narrow,)
    return out


@dataclasses.dataclass(frozen=True)
class PackedComponents:
    """A multi-component store (base + runs + deltas) packed for ONE sweep.

    Each component's leaf-sorted SAX rows are padded to a ``block``
    multiple and concatenated in ascending file-offset order, so the fused
    lower-bound kernel (:func:`ops.lower_bound_sq_multi`) covers the whole
    store in one (Q, N_pad) pass. The block alignment means appending a
    component only APPENDS blocks — earlier components' rows never move —
    which is what ``core.ingest.IncrementalPacker`` exploits: it keeps
    capacity-padded buffers (dead tail blocks masked by ``block_len == 0``)
    and rewrites only the components past the longest unchanged prefix on
    each snapshot swap, O(delta) per append. ``gpos`` maps packed
    rows to *global* file positions (:data:`NO_POS` at pad rows, so a pad
    that survives to a result list is already the sentinel), ``block_len``
    is the kernel's per-block validity table, and ``raw`` is the full
    file-order raw array (components cover contiguous, adjacent file
    ranges, so their concatenation IS the datastore) — candidate gathers
    index it directly by global position.
    """

    sax: jax.Array  # (N_pad, w) uint8, per-component leaf order
    gpos: jax.Array  # (N_pad,) int32 global file positions; NO_POS at pads
    block_len: jax.Array  # (N_pad // block,) int32 valid rows per block
    raw: jax.Array  # (N_total, n) f32, file order
    num_series: int  # real rows (N_total)
    block: int
    series_length: int
    segments: int
    cardinality: int


def pack_one_component(ix, off: int, block: int) -> tuple:
    """One component's packed parts: (sax, gpos, block_len) np arrays.

    The per-component packing primitive shared by :func:`pack_components`
    and the incremental packer (``core.ingest.IncrementalPacker``) — ONE
    definition, so an incrementally grown buffer is byte-identical to a
    from-scratch pack over the same components.
    """
    m = ix.num_series
    pad = (-m) % block
    sax = np.asarray(ix.sax)
    gp = np.asarray(ix.pos, np.int32) + np.int32(off)
    if pad:
        sax = np.concatenate(
            [sax, np.zeros((pad, sax.shape[1]), np.uint8)])
        gp = np.concatenate([gp, np.full((pad,), _NP_NO_POS, np.int32)])
    bl = np.full(((m + pad) // block,), block, np.int32)
    if pad:
        bl[-1] = block - pad
    return sax, gp, bl


def pack_components(
    components, block: Optional[int] = None
) -> PackedComponents:
    """Pack (index, file offset) components for the fused multi-sweep.

    ``components`` must come in ascending offset order and cover
    contiguous, adjacent file ranges starting at 0 — exactly what
    ``core.ingest.Snapshot.components()`` yields. Zero-series components
    are skipped. ``block=None`` resolves the packed layout's ``block_n``
    through the tuning table (``lb_multi`` entry for the store's total
    size; registry default 128 on a miss) — the block is a *layout*
    choice baked into the buffer, so it is picked here, once, not at
    query time.
    """
    comps = [(ix, off) for ix, off in components if ix.num_series]
    if not comps:
        raise ValueError("pack_components needs at least one nonempty "
                         "component")
    if block is None:
        total = sum(ix.num_series for ix, _ in comps)
        block = tuning.resolve_blocks(
            "lb_multi", q=8, n=max(total, 1))["block_n"]
    expect = 0
    for ix, off in comps:
        if off != expect:
            raise ValueError(
                f"components not contiguous: offset {off}, expected "
                f"{expect}")
        expect += ix.num_series
    sax_parts, gpos_parts, len_parts = [], [], []
    for ix, off in comps:
        sax, gp, bl = pack_one_component(ix, off, block)
        sax_parts.append(sax)
        gpos_parts.append(gp)
        len_parts.append(bl)
    first = comps[0][0]
    return PackedComponents(
        sax=jnp.asarray(np.concatenate(sax_parts)),
        gpos=jnp.asarray(np.concatenate(gpos_parts)),
        block_len=jnp.asarray(np.concatenate(len_parts)),
        raw=jnp.concatenate([ix.raw for ix, _ in comps]),
        num_series=expect,
        block=block,
        series_length=first.series_length,
        segments=first.segments,
        cardinality=first.cardinality,
    )


def _packed_view(
    sax: jax.Array,
    gpos: jax.Array,
    block_len: jax.Array,
    raw: jax.Array,
    *,
    block: int,
    series_length: int,
    segments: int,
    cardinality: int,
    num_series: Optional[int],
) -> EngineView:
    """Packed-buffer hooks: the fused multi-component sweep over the core.

    ONE masked lower-bound pass over the packed SAX buffer replaces the
    per-component engine calls, candidate positions go through the
    ``gpos`` global translation, and raw gathers hit the file-order
    concatenation directly. Pad rows carry (+inf, :data:`NO_POS`), so
    they can never pass a round mask and, if the store holds fewer than
    ``k`` series' worth of finite distances, they ARE the sentinel slots.
    No seed hook: a packed buffer has no global bucket structure, so the
    BSF starts at +inf — a few extra raw reads, never a different answer.
    Works both over a :class:`PackedComponents`' arrays (closed over as
    jit constants) and over traced buffer arguments
    (:func:`packed_engine_args`).
    """
    bpp = isax.padded_breakpoints(cardinality)

    def lower_bounds(qps, impl):
        return ops.lower_bound_sq_multi(
            qps, sax, bpp, series_length, block_len,
            impl=impl, block_n=block,
        )

    return EngineView(
        n_rows=sax.shape[0],
        num_series=num_series,
        segments=segments,
        lower_bounds=lower_bounds,
        positions=lambda idx: jnp.take(gpos, idx, axis=0),
        # NO_POS (and dead-block) rows clip to row 0 harmlessly: their
        # +inf lower bound keeps them out of every mask.
        gather_raw=lambda pos: jnp.take(raw, pos, axis=0, mode="clip"),
        seed=None,
    )


def _packed_engine_for(packed: PackedComponents, statics: tuple):
    """Per-packed-view jitted closures, cached on the view (same idiom —
    and same lifetime argument — as the per-index ``_engine_for`` cache).

    ``statics = (k, round_size, select, impl)`` compiles the exact
    engine; ``(..., impl, True)`` the tiered variant, whose closure takes
    ``(queries, eps_factor_sq, budget_rounds, seed_d, seed_p)`` — all
    traced, so one compile serves every tier mix and every seed. A
    ``(+inf, NO_POS)`` seed row is identical to the unseeded cold start.
    """
    cache = getattr(packed, "_engines", None)
    if cache is None:
        cache = {}
        object.__setattr__(packed, "_engines", cache)
    fn = cache.get(statics)
    if fn is not None:
        return fn
    k, round_size, select, impl = statics[:4]
    tiered = len(statics) > 4 and statics[4]

    def _view():
        return _packed_view(
            packed.sax, packed.gpos, packed.block_len, packed.raw,
            block=packed.block, series_length=packed.series_length,
            segments=packed.segments, cardinality=packed.cardinality,
            num_series=packed.num_series,
        )

    if tiered:
        @jax.jit
        def fn(queries, eps_factor_sq, budget_rounds, seed_d, seed_p):
            return _engine_core(
                _view(), queries,
                k=k, round_size=round_size, sort=True, select=select,
                impl=impl,
                eps_factor_sq=eps_factor_sq, budget_rounds=budget_rounds,
                seed0=(seed_d, seed_p),
            )
    else:
        @jax.jit
        def fn(queries):
            return _engine_core(
                _view(), queries,
                k=k, round_size=round_size, sort=True, select=select,
                impl=impl,
            )

    cache[statics] = fn
    return fn


@functools.partial(
    jax.jit,
    static_argnames=("block", "series_length", "segments", "cardinality",
                     "k", "round_size", "select", "impl"),
)
def packed_engine_args(
    sax: jax.Array,
    gpos: jax.Array,
    block_len: jax.Array,
    raw: jax.Array,
    queries: jax.Array,
    *,
    block: int,
    series_length: int,
    segments: int,
    cardinality: int,
    k: int,
    round_size: int,
    select: str = "topk",
    impl: str = "auto",
    eps_factor_sq: Optional[jax.Array] = None,
    budget_rounds: Optional[jax.Array] = None,
    seed_d: Optional[jax.Array] = None,
    seed_p: Optional[jax.Array] = None,
) -> tuple:
    """Shape-stable fused engine: packed buffers as jit ARGUMENTS.

    The per-object packed engines (:func:`_packed_engine_for`) close over
    their arrays as baked XLA constants, so every new snapshot's packed
    view costs a fresh trace + compile. This
    entry point instead traces per (buffer shapes, statics): an
    incrementally grown packed view whose capacity is stable across
    snapshot swaps (``core.ingest.IncrementalPacker`` doubles capacity and
    masks the dead tail blocks with ``block_len == 0``) reuses ONE
    compiled engine across every swap, which is what kills the O(total)
    post-swap rebuild+recompile spike. Callers clamp ``k`` themselves
    (``num_series`` is dynamic here, so the core's host-side validation is
    skipped).

    Tiered calls pass ``eps_factor_sq``/``budget_rounds`` (per-row traced
    arrays, :func:`tier_arrays`) and get the 6-tuple with the achieved
    factor appended; ``seed_d``/``seed_p`` optionally seed each query's
    BSF with a known (distance, global position) pair — the packed view
    has no bucket table of its own, so tiered callers compute the seed
    from a component's bucket table (:func:`packed_seed`) and hand it in.
    Exact calls leave all four ``None`` and trace the historical,
    golden-tested computation.
    """
    view = _packed_view(
        sax, gpos, block_len, raw,
        block=block, series_length=series_length, segments=segments,
        cardinality=cardinality, num_series=None,
    )
    seed0 = None if seed_d is None else (seed_d, seed_p)
    return _engine_core(
        view, queries,
        k=k, round_size=round_size, sort=True, select=select, impl=impl,
        eps_factor_sq=eps_factor_sq, budget_rounds=budget_rounds,
        seed0=seed0,
    )


def exact_knn_batch_packed(
    packed: PackedComponents,
    queries: jax.Array,
    k: int = 1,
    round_size: int = 4096,
    impl: str = "auto",
    select: str = "topk",
    stats: bool = False,
) -> tuple:
    """Batched exact k-NN over a packed multi-component store.

    One fused lower-bound pass + one RDC loop for base + runs + deltas
    together (vs one engine call per component); positions are global file
    offsets. Same clamp/sentinel protocol as :func:`exact_knn_batch`, and
    bit-exact vs a from-scratch single-index build over the concatenated
    data (property-tested in ``tests/test_ingest.py``).
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    k_eff = min(k, packed.num_series)
    fn = _packed_engine_for(packed, (k_eff, round_size, select, impl))
    top_d, top_p, reads, updates, rounds = fn(
        jnp.asarray(queries, jnp.float32))
    if k_eff < k:
        n_q = top_d.shape[0]
        top_d = jnp.concatenate(
            [top_d, jnp.full((n_q, k - k_eff), INF)], axis=1)
        top_p = jnp.concatenate(
            [top_p, jnp.full((n_q, k - k_eff), NO_POS)], axis=1)
    if stats:
        return top_d, top_p, reads, updates, rounds
    return top_d, top_p


_approx_search_batch_jit = jax.jit(
    approx_search_batch, static_argnames=("leaf_cap", "impl"))


def packed_seed(components, queries, leaf_cap: int = 256) -> tuple:
    """Approximate BSF seed for a packed multi-component engine call.

    The packed view has no global bucket table, so its BSF historically
    started cold at +inf. For tiered calls that gap matters twice over:
    the epsilon early stop cannot fire until the BSF is real, and a
    budget answer from a cold start can be arbitrarily bad. This seeds
    each query from the bucket table of the LARGEST live component
    (usually the base; on a deltas-only store, the largest delta — the
    seed stays available at every point of the ingest lifecycle), with
    positions translated to global file offsets. Returns
    ``((Q,) float32 seed distances, (Q,) int32 global seed positions)``
    — true distances at real positions, so the engine may re-encounter
    them and its dedup protocol keeps the result list duplicate-free.

    ``components`` is an iterable of (index, global offset) pairs in the
    ``core.ingest.Snapshot.components()`` shape; empty components are
    skipped.
    """
    comps = [(ix, off) for ix, off in components if ix.num_series]
    if not comps:
        raise ValueError("packed_seed needs at least one nonempty "
                         "component")
    ix, off = max(comps, key=lambda c: c[0].num_series)
    leaf = min(int(leaf_cap), ix.num_series)
    seed_d, seed_p = _approx_search_batch_jit(
        ix, jnp.asarray(queries, jnp.float32), leaf_cap=leaf)
    return seed_d, seed_p.astype(jnp.int32) + jnp.int32(off)


def knn_batch_tiered(
    index: ParISIndex,
    queries: jax.Array,
    tier,
    k: int = 1,
    round_size: int = 4096,
    impl: str = "auto",
    select: str = "topk",
    leaf_cap: int = 256,
) -> tuple:
    """Tiered batched k-NN over one index (see :class:`Tier`).

    (Q, n) queries -> ((Q, k) dists ascending, (Q, k) positions,
    (Q,) achieved epsilon). The exact tier routes through the same
    tiered engine with factor 1.0 — bit-for-bit the exact answer, with
    achieved epsilon 0. ``tier`` is one value for the whole batch or a
    sequence of per-query :class:`Tier` values; parameters are validated
    at :class:`Tier` construction. Same k clamp/sentinel protocol as
    :func:`exact_knn_batch`.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    qs = jnp.asarray(queries, jnp.float32)
    if isinstance(tier, (Tier, str)) or tier is None:
        tiers = [as_tier(tier)] * qs.shape[0]
    else:
        tiers = [as_tier(t) for t in tier]
        if len(tiers) != qs.shape[0]:
            raise ValueError(
                f"got {len(tiers)} tiers for {qs.shape[0]} queries")
    k_eff = min(k, index.num_series)
    fn = _engine_for(
        index,
        (k_eff, round_size, leaf_cap, True, select, impl, "approx", True),
    )
    eps_f, budget = tier_arrays(tiers)
    top_d, top_p, reads, updates, rounds, ach_sq = fn(qs, eps_f, budget)
    if k_eff < k:  # tiny index: pad missing neighbors with the sentinel
        n_q = top_d.shape[0]
        top_d = jnp.concatenate(
            [top_d, jnp.full((n_q, k - k_eff), INF)], axis=1)
        top_p = jnp.concatenate(
            [top_p, jnp.full((n_q, k - k_eff), NO_POS)], axis=1)
    return top_d, top_p, achieved_epsilon(ach_sq)


def knn_batch_packed_tiered(
    packed: PackedComponents,
    queries: jax.Array,
    tier,
    k: int = 1,
    round_size: int = 4096,
    impl: str = "auto",
    select: str = "topk",
    seed: Optional[tuple] = None,
) -> tuple:
    """Tiered batched k-NN over a packed multi-component store.

    Same contract as :func:`knn_batch_tiered`, over the fused packed
    sweep. ``seed`` is an optional ``((Q,) dist, (Q,) global pos)`` BSF
    seed (:func:`packed_seed`); without one the BSF starts cold at +inf,
    which weakens (never breaks) the budget tier's achieved bounds.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    qs = jnp.asarray(queries, jnp.float32)
    if isinstance(tier, (Tier, str)) or tier is None:
        tiers = [as_tier(tier)] * qs.shape[0]
    else:
        tiers = [as_tier(t) for t in tier]
        if len(tiers) != qs.shape[0]:
            raise ValueError(
                f"got {len(tiers)} tiers for {qs.shape[0]} queries")
    k_eff = min(k, packed.num_series)
    fn = _packed_engine_for(
        packed, (k_eff, round_size, select, impl, True))
    eps_f, budget = tier_arrays(tiers)
    if seed is None:
        n_q = qs.shape[0]
        seed_d = jnp.full((n_q,), INF)
        seed_p = jnp.full((n_q,), NO_POS)
    else:
        seed_d = jnp.asarray(seed[0], jnp.float32)
        seed_p = jnp.asarray(seed[1], jnp.int32)
    top_d, top_p, reads, updates, rounds, ach_sq = fn(
        qs, eps_f, budget, seed_d, seed_p)
    if k_eff < k:
        n_q = top_d.shape[0]
        top_d = jnp.concatenate(
            [top_d, jnp.full((n_q, k - k_eff), INF)], axis=1)
        top_p = jnp.concatenate(
            [top_p, jnp.full((n_q, k - k_eff), NO_POS)], axis=1)
    return top_d, top_p, achieved_epsilon(ach_sq)


def exact_search_batch_packed(
    packed: PackedComponents,
    queries: jax.Array,
    cfg: SearchConfig = SearchConfig(),
) -> SearchResult:
    """Batched exact 1-NN over a packed multi-component store.

    Only the sorted-candidate engine exists for the packed layout:
    ``cfg.sort=False`` (the ADS+-style serial scan) is refused rather
    than silently answered by the wrong algorithm — callers wanting that
    baseline go through the per-component engines.
    """
    if not cfg.sort:
        raise ValueError(
            "the packed engine has no sort=False (serial-scan) mode; use "
            "the per-component path")
    fn = _packed_engine_for(
        packed, (1, cfg.round_size, cfg.select, cfg.impl))
    top_d, top_p, reads, updates, rounds = fn(
        jnp.asarray(queries, jnp.float32))
    return SearchResult(top_d[:, 0], top_p[:, 0], reads, updates, rounds)


# Per-index engines take the index arrays as jit ARGUMENTS. A closed-over
# array is lowered as an HLO constant: at chip scale (gigabytes of raw
# series) lowering and compiling such a program exhausts the host's
# memory. As arguments, jit's own cache is keyed by shapes, so every
# same-shaped index (the router's equal shards) shares one compiled engine.


@functools.partial(jax.jit, static_argnames=("statics",))
def _index_engine(index: ParISIndex, queries: jax.Array,
                  eps_factor_sq: Optional[jax.Array] = None,
                  budget_rounds: Optional[jax.Array] = None,
                  n_real: Optional[jax.Array] = None, *,
                  statics: tuple) -> tuple:
    k, round_size, leaf_cap, sort, select, impl, init = statics[:7]
    blocks = statics[8] if len(statics) > 8 else None
    view = _index_view(index, leaf_cap=leaf_cap, init=init, blocks=blocks)
    return _engine_core(
        view, queries, k=k, round_size=round_size, sort=sort, select=select,
        impl=impl, eps_factor_sq=eps_factor_sq, budget_rounds=budget_rounds,
        n_real=n_real,
    )


def _engine_for(index: ParISIndex, statics: tuple):
    """The per-index engine callable for a statics tuple.

    ``statics = (k, round_size, leaf_cap, sort, select, impl, init)``
    gives the exact engine ``fn(queries)`` (historical 5-tuple return);
    appending ``True`` — ``(..., init, True)`` — the TIERED variant
    ``fn(queries, eps_factor_sq, budget_rounds)``, which returns the
    6-tuple with the achieved factor. Either takes the count of real rows
    as a fourth positional argument, ``fn(queries, None, None, n_real)``
    for the exact engine: the rows past it are pads, and the outputs gain
    the rounds that ran narrow (:func:`_engine_core`). Tier parameters
    and ``n_real`` being traced is the point: ONE compiled engine per
    shape serves every epsilon, budget and fill of a batch. A ninth element — ``(..., init, tiered,
    (block_q, block_n))`` — carries an explicit kernel block-shape
    override (None members resolve through the tuning table); it is part
    of jit's cache key, so two block shapes compile two engines.

    It stays a function of ``(index, statics)`` because that is the
    ``engine_for`` hook :func:`make_batch_engine` shares with
    ``coldtier._cold_engine_for``, and the seam the tuning tests spy on.
    """
    return functools.partial(_index_engine, index, statics=statics)


def _batch_engine(
    index: ParISIndex,
    queries: jax.Array,
    *,
    k: int,
    round_size: int,
    leaf_cap: int,
    sort: bool,
    select: str,
    impl: str,
    init: str,
) -> tuple:
    fn = _engine_for(
        index, (k, round_size, leaf_cap, sort, select, impl, init)
    )
    return fn(queries)


def pow2_bucket(n: int, lo: int = 1) -> int:
    """Smallest power of two >= max(n, lo).

    Dynamic sizes are quantized to pow2 buckets before they reach a jitted
    engine — batch shapes here and in the serving batchers, prompt lengths
    in ``serving.batcher.SlotBatcher`` — so jit traces one step per bucket
    instead of one per distinct size.
    """
    return 1 << (max(n, lo) - 1).bit_length()


def make_batch_engine(
    index: ParISIndex,
    *,
    k: Optional[int] = None,
    round_size: int = 4096,
    leaf_cap: int = 256,
    sort: bool = True,
    select: str = "topk",
    impl: str = "auto",
    min_bucket: int = 1,
    engine_for=None,
    block_q: Optional[int] = None,
    block_n: Optional[int] = None,
):
    """Build a reusable, shape-stable batch engine over one index.

    The factory behind every streaming caller (``SearchRequestBatcher``,
    ``ShardedSearchRouter``): it resolves the per-index jitted engine once
    (``_engine_for``, whose compiled programs direct ``exact_*_batch``
    calls share) and wraps it so any (Q, n) call is padded up to the power-of-two
    bucket shape — one trace per bucket instead of one per arrival count,
    and a router can stamp out S per-shard engines without retracing per
    query shape. Pad rows repeat row 0 and are discarded; the engine is
    told the real-row count (traced, so one program serves every fill),
    so pads are inert: they never extend the rounds, and a bucket holding
    few real rows runs its rounds narrow over those (:func:`_engine_core`).
    The bucket is
    padded on the host and put on the device that holds the index's raw
    rows where they are committed to one device (as the shards of
    ``build_sharded_index_on_devices`` are), so each shard's batches go
    straight to its own device; an uncommitted index takes the default
    device.

    ``k=None``: exact 1-NN, returns a ``SearchResult`` of (Q,) arrays.
    ``k >= 1``: exact k-NN, returns ((Q, k) dists ascending, (Q, k) pos)
    with the same clamp/sentinel protocol as :func:`exact_knn_batch`.

    ``engine(queries, tiers=[...])`` (k-NN mode only) answers each row at
    its own service tier and returns a third array — the per-query
    achieved epsilon (:func:`achieved_epsilon`). ``tiers=None`` or
    all-exact takes the historical exact path, unchanged; a mixed batch
    compiles ONE extra tiered engine per bucket shape (tier parameters
    are traced); pad rows carry a zero round budget as well.

    ``engine(queries, counts=True)`` (k-NN mode only) appends the
    engine's work counts to whatever the call returns: the (Q,) raw reads
    of the real rows, the round count and how many of those rounds ran
    narrow, all copied to the host (pad rows are left out). The answers
    are those of the plain call; 1-NN mode's ``SearchResult`` carries
    reads and rounds already.

    The returned callable exposes ``engine.bucket(qn)`` — the padded batch
    shape a Q-query call compiles at (callers use it for pad accounting) —
    and ``engine.takes_counts`` (True), which a wrapper of the engine that
    does not pass ``counts`` on leaves unset.

    ``engine_for`` swaps the per-index jitted-engine factory: the default
    :func:`_engine_for` serves in-memory :class:`ParISIndex` objects; the
    cold tier passes its own factory (``core.coldtier``) so a disk-backed
    shard rides the identical wrapper — same padding, tier, and sentinel
    protocol — over its callback-gather engines. Its engines are called
    ``fn(qs, None, None, qn)`` and ``fn(qs, eps_factor_sq, budget_rounds,
    qn)`` (see :func:`_engine_for`).

    ``block_q``/``block_n`` override the lower-bound kernel's block
    shapes for this engine; left ``None`` they resolve through the
    committed tuning table (``repro.core.tuning`` / ``TUNING.json``)
    inside ``ops`` at trace time, falling back to the registry defaults
    on a miss. Either way the answer is bit-exact — block shapes only
    re-tile the same math (tests/test_tuning.py pins the parity).
    """
    if k is not None and k < 1:
        raise ValueError(f"k must be None (1-NN mode) or >= 1, got {k}")
    if engine_for is None:
        engine_for = _engine_for
    k_eff = 1 if k is None else min(k, index.num_series)
    # Explicit block overrides extend the statics key (the compiled-engine
    # cache must distinguish block shapes); the historical 7/8-tuple keys
    # stay untouched when no override is given, so table-resolved and
    # pre-tuning callers share the same cached engines.
    extras = (() if block_q is None and block_n is None
              else (False, (block_q, block_n)))
    fn = engine_for(
        index,
        (k_eff, round_size, leaf_cap, sort, select, impl, "approx")
        + extras,
    )
    tier_statics = (
        k_eff, round_size, leaf_cap, sort, select, impl, "approx", True,
    ) + ((extras[1],) if extras else ())

    raw = getattr(index, "raw", None)
    home = (next(iter(raw.devices())) if isinstance(raw, jax.Array)
            and raw.committed and len(raw.devices()) == 1 else None)

    def bucket(qn: int) -> int:
        return pow2_bucket(qn, min_bucket)

    def engine(queries, tiers=None, counts=False):
        qs = np.asarray(queries, np.float32)
        if qs.ndim != 2:
            raise ValueError(f"engine takes (Q, n) queries, got {qs.shape}")
        if counts and k is None:
            raise ValueError(
                "counts=True is for k-NN mode; the 1-NN SearchResult "
                "carries raw_reads and rounds")
        qn = qs.shape[0]

        def with_counts(out, reads, rounds, narrow):
            # Host copies, sliced on the host: no per-size device program.
            # ``narrow`` is the engine's outputs past the historical ones:
            # an ``engine_for`` hook that leaves the narrow count out (a
            # stub warming up this wrapper) serves every call but counts.
            if not counts:
                return out
            return out + (np.asarray(reads)[:qn], int(rounds),
                          int(narrow[0]))
        if tiers is not None:
            tiers = [as_tier(t) for t in tiers]
            if len(tiers) != qn:
                raise ValueError(
                    f"got {len(tiers)} tiers for {qn} queries")
            if all(t.kind == "exact" for t in tiers):
                tiers = None  # pure-exact batch: historical path
            elif k is None:
                raise ValueError(
                    "service tiers need k-NN mode (k >= 1); the 1-NN "
                    "SearchResult mode answers tier='exact' only")
        b = bucket(qn)
        if b > qn:  # pad rows repeat a real query; sliced off below
            qs = np.concatenate(
                [qs, np.broadcast_to(qs[:1], (b - qn, qs.shape[1]))]
            )
        qs = jax.device_put(qs, home)
        if tiers is not None:
            eps_f, budget = tier_arrays(tiers)
            if b > qn:  # pad rows: factor 1, zero budget — inert rows
                eps_f = jnp.concatenate(
                    [eps_f, jnp.ones((b - qn,), jnp.float32)])
                budget = jnp.concatenate(
                    [budget, jnp.zeros((b - qn,), jnp.int32)])
            fnt = engine_for(index, tier_statics)
            top_d, top_p, reads, updates, rounds, ach_sq, *narrow = fnt(
                qs, eps_f, budget, qn)
            top_d, top_p, ach_sq = top_d[:qn], top_p[:qn], ach_sq[:qn]
            if k_eff < k:
                top_d = jnp.concatenate(
                    [top_d, jnp.full((qn, k - k_eff), INF)], axis=1)
                top_p = jnp.concatenate(
                    [top_p, jnp.full((qn, k - k_eff), NO_POS)], axis=1)
            return with_counts((top_d, top_p, achieved_epsilon(ach_sq)),
                               reads, rounds, narrow)
        top_d, top_p, reads, updates, rounds, *narrow = fn(
            qs, None, None, qn)
        if k is None:
            return SearchResult(
                top_d[:qn, 0], top_p[:qn, 0], reads[:qn], updates[:qn],
                rounds,
            )
        top_d, top_p = top_d[:qn], top_p[:qn]
        if k_eff < k:  # tiny index: sentinel-pad the missing neighbors
            top_d = jnp.concatenate(
                [top_d, jnp.full((qn, k - k_eff), INF)], axis=1)
            top_p = jnp.concatenate(
                [top_p, jnp.full((qn, k - k_eff), NO_POS)], axis=1)
        return with_counts((top_d, top_p), reads, rounds, narrow)

    engine.bucket = bucket
    engine.index = index
    engine.k = k
    engine.takes_counts = True
    return engine


def exact_search_batch(
    index: ParISIndex, queries: jax.Array, cfg: SearchConfig = SearchConfig()
) -> SearchResult:
    """Batched ParIS+ exact 1-NN: (Q, n) queries -> SearchResult of (Q,) arrays.

    All Q queries share one LBC pass and one RDC ``while_loop``; rounds are
    masked per query and the loop exits when every query is done.
    """
    top_d, top_p, reads, updates, rounds = _batch_engine(
        index,
        queries,
        k=1,
        round_size=cfg.round_size,
        leaf_cap=cfg.leaf_cap,
        sort=cfg.sort,
        select=cfg.select,
        impl=cfg.impl,
        init="approx",
    )
    return SearchResult(top_d[:, 0], top_p[:, 0], reads, updates, rounds)


def exact_knn_batch(
    index: ParISIndex,
    queries: jax.Array,
    k: int = 1,
    round_size: int = 4096,
    impl: str = "auto",
    select: str = "topk",
    sort: bool = True,
    leaf_cap: int = 256,
    stats: bool = False,
) -> tuple:
    """Batched exact k-NN: (Q, n) -> ((Q, k) dists ascending, (Q, k) pos).

    Rides the partial-selection fast path by default (``select="topk"``:
    the N/16 smallest bounds per query by :func:`select_candidates`, two
    sorts of short rows where a wide ``top_k`` would sort the whole row on
    the TPU, instead of a full argsort of all N) with an approx-seeded BSF:
    row 0 of the result list starts at the query's bucket-window best, rows
    1..k-1 at INF. Exactness is kept by the dedup-masked fallback protocol
    of :func:`_engine_core`.

    ``k`` is validated: ``k < 1`` raises; ``k > index.num_series`` is
    answered with the ``num_series`` real neighbors and the remaining slots
    filled with the (INF, :data:`NO_POS`) sentinel — never duplicated
    placeholders. ``stats=True`` appends the engine's per-query
    (raw_reads, bsf_updates) vectors and the scalar round count.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    k_eff = min(k, index.num_series)
    top_d, top_p, reads, updates, rounds = _batch_engine(
        index,
        queries,
        k=k_eff,
        round_size=round_size,
        leaf_cap=leaf_cap,
        sort=sort,
        select=select,
        impl=impl,
        init="approx",
    )
    if k_eff < k:  # tiny index: pad missing neighbors with the sentinel
        n_q = top_d.shape[0]
        top_d = jnp.concatenate(
            [top_d, jnp.full((n_q, k - k_eff), INF)], axis=1)
        top_p = jnp.concatenate(
            [top_p, jnp.full((n_q, k - k_eff), NO_POS)], axis=1)
    if stats:
        return top_d, top_p, reads, updates, rounds
    return top_d, top_p


@functools.partial(
    jax.jit, static_argnames=("round_size", "leaf_cap", "sort", "impl")
)
def _exact_search_impl(
    index: ParISIndex,
    query: jax.Array,
    *,
    round_size: int,
    leaf_cap: int,
    sort: bool,
    impl: str,
) -> SearchResult:
    n_series = index.num_series
    q, qp = _query_paa(index, query)
    bsf0, pos0 = approx_search(index, query, leaf_cap, impl)
    bpp = isax.padded_breakpoints(index.cardinality)

    # --- LBC phase: one vectorized pass over the whole SAX array. ---
    lb = ops.lower_bound_sq(qp, index.sax, bpp, index.series_length, impl=impl)

    # --- Candidate list (sorted for ParIS+; SAX order for the ADS+ mode). ---
    if sort:
        order_idx = jnp.argsort(lb)
        lb_sorted = jnp.take(lb, order_idx, axis=0)
    else:
        order_idx = jnp.arange(n_series, dtype=jnp.int32)
        lb_sorted = lb
    n_rounds = -(-n_series // round_size)
    padded = n_rounds * round_size
    order_idx = _pad_to(order_idx.astype(jnp.int32), padded, 0)
    lb_sorted = _pad_to(lb_sorted, padded, INF)

    # --- RDC phase: rounds of gather + batched ED, shared BSF in carry. ---
    def cond(st):
        r, bsf, *_ = st
        more = r < n_rounds
        if sort:  # sorted list => everything past a pruned head is pruned
            more &= jax.lax.dynamic_index_in_dim(
                lb_sorted, r * round_size, keepdims=False
            ) < bsf
        return more

    def body(st):
        r, bsf, bsfpos, reads, updates = st
        idx = jax.lax.dynamic_slice_in_dim(order_idx, r * round_size, round_size)
        lbs = jax.lax.dynamic_slice_in_dim(lb_sorted, r * round_size, round_size)
        mask = lbs < bsf
        cand_pos = jnp.take(index.pos, idx, axis=0)
        raws = jnp.take(index.raw, cand_pos, axis=0)  # the "disk reads"
        d = ops.euclid_sq(q, raws, impl=impl)
        d = jnp.where(mask, d, INF)
        j = jnp.argmin(d)
        better = d[j] < bsf
        return (
            r + 1,
            jnp.where(better, d[j], bsf),
            jnp.where(better, cand_pos[j], bsfpos),
            reads + jnp.sum(mask),
            updates + better.astype(jnp.int32),
        )

    st0 = (
        jnp.int32(0),
        bsf0,
        pos0.astype(jnp.int32),
        jnp.int32(leaf_cap),
        jnp.int32(0),
    )
    r, bsf, bsfpos, reads, updates = jax.lax.while_loop(cond, body, st0)
    return SearchResult(bsf, bsfpos, reads, updates, r)


def exact_search_single(
    index: ParISIndex, query: jax.Array, cfg: SearchConfig = SearchConfig()
) -> SearchResult:
    """The original one-query-at-a-time engine (full argsort candidate list).

    Kept as the benchmark baseline the batch engine is measured against
    (``benchmarks/bench_batch_query.py``) and as an independent
    implementation for parity tests. New callers should prefer
    :func:`exact_search` / :func:`exact_search_batch`.
    """
    return _exact_search_impl(
        index,
        query,
        round_size=cfg.round_size,
        leaf_cap=cfg.leaf_cap,
        sort=cfg.sort,
        impl=cfg.impl,
    )


def exact_search(
    index: ParISIndex, query: jax.Array, cfg: SearchConfig = SearchConfig()
) -> SearchResult:
    """ParIS+ exact 1-NN (``cfg.sort=False`` gives the ADS+-style serial scan).

    Thin Q=1 wrapper over :func:`exact_search_batch` — single-query callers
    ride the same engine as the batch path.
    """
    res = exact_search_batch(index, query[None, :], cfg)
    return SearchResult(
        res.dist_sq[0],
        res.position[0],
        res.raw_reads[0],
        res.bsf_updates[0],
        res.rounds,
    )


@functools.partial(
    jax.jit, static_argnames=("round_size", "leaf_cap", "workers", "impl")
)
def _nb_exact_search_impl(
    index: ParISIndex,
    query: jax.Array,
    *,
    round_size: int,
    leaf_cap: int,
    workers: int,
    impl: str,
) -> SearchResult:
    n_series = index.num_series
    q, qp = _query_paa(index, query)
    bsf0, pos0 = approx_search(index, query, leaf_cap, impl)
    bpp = isax.padded_breakpoints(index.cardinality)
    lb = ops.lower_bound_sq(qp, index.sax, bpp, index.series_length, impl=impl)

    per = -(-n_series // workers)
    rounds = -(-per // round_size)
    padded = workers * rounds * round_size
    idx_all = _pad_to(jnp.arange(n_series, dtype=jnp.int32), padded, 0)
    lb_all = _pad_to(lb, padded, INF)
    idx_blocks = idx_all.reshape(workers, rounds, round_size)
    lb_blocks = lb_all.reshape(workers, rounds, round_size)

    def worker(idx_b, lb_b):
        def step(carry, xs):
            bsf, bsfpos, reads, updates = carry
            idx, lbs = xs
            mask = lbs < bsf  # local BSF only — no sharing (nb- semantics)
            cand_pos = jnp.take(index.pos, idx, axis=0)
            raws = jnp.take(index.raw, cand_pos, axis=0)
            d = jnp.where(mask, ops.euclid_sq(q, raws, impl=impl), INF)
            j = jnp.argmin(d)
            better = d[j] < bsf
            carry = (
                jnp.where(better, d[j], bsf),
                jnp.where(better, cand_pos[j], bsfpos),
                reads + jnp.sum(mask),
                updates + better.astype(jnp.int32),
            )
            return carry, None

        init = (bsf0, pos0.astype(jnp.int32), jnp.int32(0), jnp.int32(0))
        (bsf, bsfpos, reads, updates), _ = jax.lax.scan(
            step, init, (idx_b, lb_b)
        )
        return bsf, bsfpos, reads, updates

    bsf_v, pos_v, reads_v, upd_v = jax.vmap(worker)(idx_blocks, lb_blocks)
    j = jnp.argmin(bsf_v)
    return SearchResult(
        bsf_v[j],
        pos_v[j],
        jnp.sum(reads_v) + leaf_cap,
        jnp.sum(upd_v),
        jnp.int32(rounds),
    )


def nb_exact_search(
    index: ParISIndex, query: jax.Array, cfg: SearchConfig = SearchConfig()
) -> SearchResult:
    """nb-ParIS+: independent workers, local BSFs, unsorted blocks (Fig. 8)."""
    return _nb_exact_search_impl(
        index,
        query,
        round_size=cfg.round_size,
        leaf_cap=cfg.leaf_cap,
        workers=cfg.workers,
        impl=cfg.impl,
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def brute_force(
    index: ParISIndex, query: jax.Array, impl: str = "auto"
) -> SearchResult:
    """UCR-Suite analogue: optimized full scan, no pruning, no index."""
    q = isax.znorm(query)
    d, j = ops.euclid_min(q, index.raw, impl=impl)
    n = jnp.int32(index.num_series)
    return SearchResult(d, j.astype(jnp.int32), n, jnp.int32(1), jnp.int32(1))


def exact_knn(
    index: ParISIndex,
    query: jax.Array,
    k: int = 1,
    round_size: int = 4096,
    impl: str = "auto",
    select: str = "topk",
) -> tuple:
    """Exact k-NN: sorted-candidate rounds pruning against the k-th best.

    Returns ((k,) squared distances ascending, (k,) file positions). Backs the
    paper's k-NN classifier experiment (Fig. 18). Thin Q=1 wrapper over
    :func:`exact_knn_batch` — partial selection + approx-seeded BSF by
    default, like the batch path.
    """
    top_d, top_p = exact_knn_batch(
        index, query[None, :], k=k, round_size=round_size, impl=impl,
        select=select,
    )
    return top_d[0], top_p[0]
