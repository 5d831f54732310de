"""The ParIS index as a flat, radix-bucketed CSR structure.

TPU adaptation of the ADS+/ParIS tree (DESIGN.md §2): the paper's index root
fans out on the first bit of each of the ``w`` segments (one RecBuf / root
subtree per value, at most ``2**w``); everything below the root exists to (a)
bound the series scanned by approximate search and (b) keep leaf writes
sequential. A pointer tree is hostile to TPUs, so we keep the radix partition
and flatten the subtrees:

  * ``sax``            (N, w) uint8 — summarizations, sorted by
                       (root_key, refined bit-plane key): exactly the leaf
                       order a fully split ADS+ tree would produce,
  * ``pos``            (N,) int32 — original "file offsets" of each series,
  * ``bucket_offsets`` (2**root_bits + 1,) int32 — CSR offsets of each root
                       subtree into the sorted arrays,
  * ``raw``            (N, n) f32 — the z-normalized raw series, in *file
                       order* (this array plays the role of the on-disk raw
                       file; exact search gathers from it through ``pos``).

Approximate search = O(1) bucket lookup + a bounded scan of one bucket.
Exact search = full SAX-array scan with lower-bound pruning (like the paper,
which also scans the flat SAX array rather than the tree).
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isax
from repro.kernels import ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ParISIndex:
    """Immutable iSAX index: sorted SAX words + root bucket table + raw data."""
    sax: jax.Array  # (N, w) uint8, index (sorted) order
    pos: jax.Array  # (N,) int32, index order -> file order
    bucket_offsets: jax.Array  # (2**root_bits + 1,) int32
    raw: jax.Array  # (N, n) f32, file order (the "raw data file")
    series_length: int = dataclasses.field(metadata=dict(static=True))
    segments: int = dataclasses.field(metadata=dict(static=True))
    cardinality: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_series(self) -> int:
        """Number of indexed series."""
        return self.sax.shape[0]

    @property
    def num_buckets(self) -> int:
        """Number of root buckets."""
        return self.bucket_offsets.shape[0] - 1

    def bucket(self, key) -> tuple:
        """(start, end) of a root bucket in index order."""
        return self.bucket_offsets[key], self.bucket_offsets[key + 1]


def sort_by_index_key(
    sax: jax.Array, cardinality: int, refine_bits: int = 4
) -> jax.Array:
    """Permutation sorting series into index (leaf) order.

    Primary key: root_key (MSB of each segment — the root radix partition).
    Secondary: bit-plane-interleaved refinement (ADS+ split-order analogue).
    LSD-style: stable argsort from the least-significant plane up, so the
    most-significant plane (the root key) dominates.
    """
    keys = isax.refine_keys(sax, refine_bits, cardinality)
    n = sax.shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    for key in reversed(keys):
        order = jnp.take(order, jnp.argsort(jnp.take(key, order), stable=True))
    return order


def bucket_offsets_from_keys(
    sorted_root_keys: jax.Array, num_buckets: int
) -> jax.Array:
    """CSR offsets from the sorted root keys (vectorized searchsorted)."""
    targets = jnp.arange(num_buckets + 1, dtype=sorted_root_keys.dtype)
    return jnp.searchsorted(sorted_root_keys, targets, side="left").astype(
        jnp.int32
    )


def build_index(
    raw: jax.Array,
    segments: int = isax.DEFAULT_SEGMENTS,
    cardinality: int = isax.DEFAULT_CARDINALITY,
    *,
    normalize: bool = True,
    refine_bits: int = 4,
    impl: str = "auto",
) -> ParISIndex:
    """One-shot (in-memory) index build: the semantic spec of the pipeline.

    ``core.build_pipeline`` produces byte-identical indices through the
    staged, double-buffered, out-of-core path; tests assert they agree.
    """
    if normalize:
        raw = isax.znorm(raw)
    bp = isax.gaussian_breakpoints(cardinality)
    sax, _ = ops.paa_isax(raw, bp, segments, impl=impl, normalize=False)
    order = sort_by_index_key(sax, cardinality, refine_bits)
    sax_sorted = jnp.take(sax, order, axis=0)
    root_sorted = isax.root_key(sax_sorted, cardinality)
    offsets = bucket_offsets_from_keys(root_sorted, 2 ** segments)
    return ParISIndex(
        sax=sax_sorted,
        pos=order.astype(jnp.int32),
        bucket_offsets=offsets,
        raw=raw,
        series_length=raw.shape[-1],
        segments=segments,
        cardinality=cardinality,
    )


def assemble_index(
    sax_sorted: np.ndarray,
    pos_sorted: np.ndarray,
    raw: jax.Array,
    segments: int,
    cardinality: int,
) -> ParISIndex:
    """Wrap pre-sorted host arrays (from the build pipeline) into an index."""
    sax_sorted = jnp.asarray(sax_sorted)
    root_sorted = isax.root_key(sax_sorted, cardinality)
    offsets = bucket_offsets_from_keys(root_sorted, 2 ** segments)
    return ParISIndex(
        sax=sax_sorted,
        pos=jnp.asarray(pos_sorted, jnp.int32),
        bucket_offsets=offsets,
        raw=raw,
        series_length=raw.shape[-1],
        segments=segments,
        cardinality=cardinality,
    )


def empty_index(
    series_length: int,
    segments: int = isax.DEFAULT_SEGMENTS,
    cardinality: int = isax.DEFAULT_CARDINALITY,
) -> ParISIndex:
    """A structurally valid zero-series index.

    The degenerate base of the live-ingest path (``core.ingest`` starts an
    index from nothing and grows it by delta shards) and the result of
    building from an empty :class:`~repro.core.datagen.SeriesSource`.
    Search engines cannot run over it (there is nothing to return) —
    callers skip zero-series components.
    """
    return ParISIndex(
        sax=jnp.zeros((0, segments), jnp.uint8),
        pos=jnp.zeros((0,), jnp.int32),
        bucket_offsets=jnp.zeros((2 ** segments + 1,), jnp.int32),
        raw=jnp.zeros((0, series_length), jnp.float32),
        series_length=series_length,
        segments=segments,
        cardinality=cardinality,
    )


@dataclasses.dataclass(frozen=True)
class ShardedIndex:
    """S self-contained :class:`ParISIndex` shards over file-order slices.

    Shard ``s`` owns the contiguous file-position range
    ``[offsets[s], offsets[s+1])`` of the original datastore; its internal
    positions are shard-local (0-based), so a global answer is
    ``local_pos + offsets[s]``. Because shards partition the file range,
    per-shard k-NN result lists are ownership-disjoint by construction —
    the same duplicate-free-merge invariant ``core.distributed`` relies on.
    """

    shards: tuple  # (S,) ParISIndex
    offsets: tuple  # (S + 1,) file-order partition bounds

    @property
    def num_shards(self) -> int:
        """Number of shards."""
        return len(self.shards)

    @property
    def num_series(self) -> int:
        """Total series across all shards."""
        return self.offsets[-1]


def build_sharded_index(index: ParISIndex, num_shards: int) -> ShardedIndex:
    """Split an assembled index into S self-contained file-order shards.

    The datastore (``raw``, file order) is cut into S contiguous slices
    (sizes differ by at most one when S does not divide N). Each shard's
    SAX rows are *selected* from the full index's sorted arrays rather than
    rebuilt: the leaf-order sort is stable, so a subsequence of the sorted
    full index is exactly what a fresh ``build_index`` over the slice would
    produce — shards are byte-identical to independently built indices, and
    per-series summarizations/distances are bitwise unchanged.
    """
    n = index.num_series
    if not 1 <= num_shards <= n:
        raise ValueError(f"num_shards={num_shards} outside [1, {n}]")
    base, rem = divmod(n, num_shards)
    bounds = [0]
    for s in range(num_shards):
        bounds.append(bounds[-1] + base + (1 if s < rem else 0))
    sax = np.asarray(index.sax)
    pos = np.asarray(index.pos)
    shards = []
    for s in range(num_shards):
        lo, hi = bounds[s], bounds[s + 1]
        mask = (pos >= lo) & (pos < hi)
        shards.append(
            assemble_index(
                sax[mask],
                pos[mask] - lo,
                index.raw[lo:hi],
                index.segments,
                index.cardinality,
            )
        )
    return ShardedIndex(tuple(shards), tuple(bounds))


def build_sharded_index_on_devices(
    shares,
    segments: int = isax.DEFAULT_SEGMENTS,
    cardinality: int = isax.DEFAULT_CARDINALITY,
    *,
    refine_bits: int = 4,
    impl: str = "auto",
) -> ShardedIndex:
    """Build one shard per device from S raw file-order shares in place.

    Share ``s`` is an (N_s, n) array held by one device; :func:`build_index`
    runs there, with its constants made on that device too, so no device
    ever holds another share's rows or the whole collection. Shard ``s``
    owns the file range that follows the earlier shares (offsets are the
    cumulative share lengths). Z-normalisation and summarisation are per
    series and the leaf-order sort is stable, so each shard equals what
    :func:`build_sharded_index` cuts from an index over the concatenated
    collection with the same bounds. The builds run in a thread each, so
    one device's compiles and dispatches do not wait for another's.
    """
    shares = list(shares)
    if not shares:
        raise ValueError("no shares to build")
    for raw in shares:
        if len(raw.devices()) != 1:
            raise ValueError(
                f"each share must sit on one device, got {len(raw.devices())}")

    def build(raw):
        with jax.default_device(next(iter(raw.devices()))):
            return build_index(raw, segments, cardinality,
                               refine_bits=refine_bits, impl=impl)

    with ThreadPoolExecutor(len(shares)) as pool:
        shards = tuple(pool.map(build, shares))
    bounds = np.cumsum([0] + [raw.shape[0] for raw in shares])
    return ShardedIndex(shards, tuple(int(b) for b in bounds))


def validate_index(index: ParISIndex) -> dict:
    """Structural invariants (used by tests and the builder's self-check)."""
    sax_file_order = np.zeros((index.num_series, index.segments), np.uint8)
    pos = np.asarray(index.pos)
    sax_file_order[pos] = np.asarray(index.sax)
    expect_sax, _ = isax.convert_to_sax(
        index.raw, index.segments, index.cardinality, normalize=False
    )
    root = np.asarray(isax.root_key(index.sax, index.cardinality))
    off = np.asarray(index.bucket_offsets)
    ok_perm = np.array_equal(np.sort(pos), np.arange(index.num_series))
    ok_sax = np.array_equal(sax_file_order, np.asarray(expect_sax))
    ok_sorted = bool(np.all(np.diff(root) >= 0))
    ok_offsets = bool(
        off[0] == 0
        and off[-1] == index.num_series
        and np.all(np.diff(off) >= 0)
        and all(
            np.all(root[off[k]: off[k + 1]] == k)
            for k in np.unique(root)
        )
    )
    return dict(
        permutation=ok_perm, sax=ok_sax, sorted=ok_sorted, offsets=ok_offsets
    )
