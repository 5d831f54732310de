"""The cold storage tier: disk-resident raw series behind a pointer index.

ParIS+ is a disk-based index — its headline result is that queries touch
only the raw-series ranges their surviving candidate leaves name, while
everything else stays on disk. This module is that read path for the
``e{N}`` epoch format: a demoted component keeps its SAX summaries,
positions and bucket table hot in RAM (a few bytes per series) and
leaves the raw matrix on disk, read lazily through ``np.memmap`` and an
LRU :class:`~repro.core.block_cache.BlockCache`.

Cold epoch layout — the durable component format with ONE change::

    e{N}/
      keys.npy        (m,) uint64 sorted packed refine keys
      sax.npy         (m, w) uint8, leaf order
      pos.npy         (m,) int32 component-local positions (leaf order)
      raw_leaf.npy    (m, n) f32 znormed raw, LEAF order (not file order)
      meta.json       {num_series, base, series_length, cold: true}

Raw rows are stored in leaf (index-sorted) order, unlike the hot
format's file order. That single permutation is what makes the pointer
index real: a root bucket's series occupy one CONTIGUOUS row range
``[bucket_offsets[key], bucket_offsets[key+1])``, so the catalog entry
``key -> (row_offset, run_length)`` names an actual byte range of
``raw_leaf.npy``, and the approximate-search seed window (a leaf-order
slice) is one contiguous disk read.

The pointer-index catalog (``COLD_CATALOG.json``, next to the MANIFEST)
maps every cold epoch's non-empty buckets to their ``(row_offset,
run_length)`` ranges, plus the per-epoch ``data_offset``/``row_bytes``
that turn a row range into a byte range. It is versioned and committed
atomically (tmp + rename + fsync), and maintained incrementally: a
demotion ADDS one epoch's entries (:func:`catalog_add`), recovery
reconciles it against the committed manifest (:func:`reconcile_catalog`)
— never a full rebuild from the data.

Demotion commit protocol (crash points swept by tests/test_coldtier.py)::

    1. spill the merged component as a cold epoch (fsync'd, orphan until
       referenced),
    2. commit the catalog entry (atomic; from here GC will never sweep
       the dir — ``durable.gc_orphans`` honors catalog references),
    3. commit the manifest (format 2) listing the epoch under ``cold``,
    4. publish the in-memory snapshot; GC the retired hot dirs.

    A crash between 2 and 3 leaves a catalog entry the manifest does not
    confirm; recovery prunes it (and then GCs the dir) — the store
    reopens exactly at the last committed manifest, bit-exact.

Search: :class:`ColdShard` plugs into the ONE RDC engine core
(``core.search._engine_core``) as an :class:`~repro.core.search.
EngineView` sibling of the in-memory and packed views. Its
``gather_raw`` hook routes each round's candidate gather through
``jax.pure_callback`` into the block cache — the engine's "disk reads"
become actual disk reads — and its BSF seed replicates the in-memory
approximate search bit-for-bit (same :func:`~repro.core.search.
bucket_window_start` window, read as one contiguous range). Answers are
bit-exact vs the all-in-memory engine, including through the ``Tier``
epsilon/budget paths (property-tested).
"""

from __future__ import annotations

import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isax
from repro.core.block_cache import BlockCache, ColdReader
from repro.core.durable import (
    COLD_CATALOG, COLD_CATALOG_TMP, ComponentRef, Fault, Manifest,
    _fire, _fsync_dir, _fsync_path,
)
from repro.core.index import bucket_offsets_from_keys
from repro.core.search import (
    INF, NO_POS, EngineView, SearchConfig, SearchResult, Tier,
    achieved_epsilon, as_tier, bucket_window_start, make_batch_engine,
    tier_arrays,
)
from repro.kernels import ops

CATALOG_FORMAT = 1
COLD_RAW = "raw_leaf.npy"
_COLD_FILES = ("keys.npy", "sax.npy", "pos.npy", COLD_RAW)


# --------------------------------------------------------------- catalog
def read_catalog(workdir: str) -> dict:
    """The committed pointer-index catalog ({} epochs when none exists)."""
    path = os.path.join(workdir, COLD_CATALOG)
    if not os.path.exists(path):
        return dict(format=CATALOG_FORMAT, epochs={})
    with open(path) as f:
        doc = json.load(f)
    if doc.get("format") != CATALOG_FORMAT:
        raise ValueError(
            f"unsupported cold catalog format {doc.get('format')!r} in "
            f"{workdir}")
    return doc


def write_catalog(workdir: str, cat: dict, fault: Fault = None) -> None:
    """Atomically commit the catalog (tmp write -> fsync -> rename)."""
    tmp = os.path.join(workdir, COLD_CATALOG_TMP)
    _fire(fault, "catalog:tmp")
    with open(tmp, "w") as f:
        json.dump(cat, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    _fire(fault, "catalog:replace")
    os.replace(tmp, os.path.join(workdir, COLD_CATALOG))
    _fsync_dir(workdir)
    _fire(fault, "catalog:done")


def bucket_entries(bucket_offsets) -> dict:
    """Sparse ``key -> [row_offset, run_length]`` map of non-empty buckets."""
    off = np.asarray(bucket_offsets)
    out = {}
    for key in np.flatnonzero(np.diff(off)):
        out[str(int(key))] = [int(off[key]), int(off[key + 1] - off[key])]
    return out


def epoch_entry(workdir: str, name: str, *, base: int, num_series: int,
                series_length: int, bucket_offsets) -> dict:
    """One epoch's catalog entry, pointer ranges resolved to bytes.

    ``data_offset`` is where the ``.npy`` payload starts inside
    ``raw_leaf.npy`` (header size), so a bucket's raw bytes are
    ``data_offset + row_offset * row_bytes`` for ``run_length *
    row_bytes`` — usable by any reader without parsing the header.
    """
    path = os.path.join(workdir, name, COLD_RAW)
    row_bytes = int(series_length) * 4  # float32 rows
    data_offset = os.path.getsize(path) - num_series * row_bytes
    return dict(
        base=int(base), num_series=int(num_series),
        series_length=int(series_length), row_bytes=row_bytes,
        data_offset=int(data_offset),
        buckets=bucket_entries(bucket_offsets),
    )


def byte_range(entry: dict, key: int) -> Optional[tuple]:
    """(byte offset, byte length) of one bucket inside ``raw_leaf.npy``."""
    span = entry["buckets"].get(str(int(key)))
    if span is None:
        return None
    row_off, run_len = span
    rb = entry["row_bytes"]
    return entry["data_offset"] + row_off * rb, run_len * rb


def catalog_add(workdir: str, name: str, entry: dict,
                fault: Fault = None) -> None:
    """Incrementally add one epoch's pointer entries (atomic commit)."""
    cat = read_catalog(workdir)
    cat["epochs"][name] = entry
    write_catalog(workdir, cat, fault)


def reconcile_catalog(workdir: str, man: Manifest, shards,
                      fault: Fault = None) -> tuple:
    """Make the catalog agree with the committed manifest (recovery).

    Prunes entries for epochs the manifest's ``cold`` list does not
    confirm (the crash window between the catalog and manifest commits
    of an interrupted demotion — after the prune, ``gc_orphans`` may
    sweep the dir) and self-heals missing entries from the loaded
    shards' bucket tables (a lost/deleted catalog is rebuildable because
    the epoch files are the source of truth). Returns (pruned, healed)
    dir-name lists; writes only when something changed.
    """
    cat = read_catalog(workdir)
    by_dir = {s.dir: s for s in shards}
    live = {ref.dir for ref in man.cold}
    pruned = [d for d in cat["epochs"] if d not in live]
    healed = [d for d in live if d not in cat["epochs"]]
    if not pruned and not healed:
        return [], []
    for d in pruned:
        del cat["epochs"][d]
    for d in healed:
        s = by_dir[d]
        cat["epochs"][d] = epoch_entry(
            workdir, d, base=s.base, num_series=s.num_series,
            series_length=s.series_length,
            bucket_offsets=s.bucket_offsets)
    write_catalog(workdir, cat, fault)
    return pruned, healed


# ----------------------------------------------------------- cold epochs
def spill_cold_component(
    workdir: str,
    name: str,
    keys: np.ndarray,
    sax: np.ndarray,
    pos_local: np.ndarray,
    raw_leaf: np.ndarray,
    *,
    base: int,
    series_length: int,
    fault: Fault = None,
) -> ComponentRef:
    """Write one cold epoch dir (fsync'd) — ``raw_leaf`` in LEAF order.

    Same contract as :func:`~repro.core.durable.spill_component`: the
    dir is complete before this returns; a crash mid-spill leaves a
    partial dir neither the manifest nor the catalog references, which
    recovery removes.
    """
    d = os.path.join(workdir, name)
    _fire(fault, f"spill:{name}:mkdir")
    os.makedirs(d, exist_ok=True)
    arrays = dict(zip(_COLD_FILES, (
        np.asarray(keys), np.asarray(sax),
        np.asarray(pos_local, np.int32),
        np.asarray(raw_leaf, np.float32))))
    for fname, arr in arrays.items():
        _fire(fault, f"spill:{name}:{fname}")
        path = os.path.join(d, fname)
        np.save(path, arr)
        _fsync_path(path)
    _fire(fault, f"spill:{name}:meta")
    meta = dict(num_series=int(len(keys)), base=int(base),
                series_length=int(series_length), cold=True)
    mpath = os.path.join(d, "meta.json")
    with open(mpath, "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())
    _fsync_dir(d)
    _fire(fault, f"spill:{name}:done")
    return ComponentRef(dir=name, base=int(base),
                        num_series=int(len(keys)))


class ColdShard:
    """One immutable cold component: hot summaries, disk-resident raw.

    Hot in RAM: the leaf-ordered SAX rows, component-local positions,
    the CSR bucket table, the sorted refine keys (so a future compaction
    could linear-merge without recomputing), and the inverse permutation
    ``inv`` (file position -> leaf row) that turns the engine's
    file-position gathers into ``raw_leaf.npy`` row reads. On disk: the
    raw matrix, behind a :class:`~repro.core.block_cache.ColdReader`.

    The shard owns the global file range ``[base, base + num_series)``
    exactly like a :class:`~repro.core.ingest.DeltaShard`; its search
    answers carry component-local positions that callers translate by
    ``base``, so every downstream merge (``merge_top_lists``, the router
    reduction) already knows how to read it.
    """

    def __init__(self, *, sax, pos, keys, reader: ColdReader, base: int,
                 dir: str, series_length: int, segments: int,
                 cardinality: int):
        self.sax = jnp.asarray(sax)
        pos_np = np.asarray(pos, np.int32)
        self.pos = jnp.asarray(pos_np)
        self.keys = np.asarray(keys)
        self.reader = reader
        self.base = int(base)
        self.dir = dir
        self.series_length = int(series_length)
        self.segments = int(segments)
        self.cardinality = int(cardinality)
        root = isax.root_key(self.sax, cardinality)
        self.bucket_offsets = bucket_offsets_from_keys(root, 2 ** segments)
        inv = np.empty((len(pos_np),), np.int32)
        inv[pos_np] = np.arange(len(pos_np), dtype=np.int32)
        self.inv = jnp.asarray(inv)
        self._engines: dict = {}

    @property
    def num_series(self) -> int:
        """Series in this cold shard."""
        return self.sax.shape[0]

    @property
    def num_buckets(self) -> int:
        """Number of root buckets."""
        return self.bucket_offsets.shape[0] - 1

    def bucket(self, key) -> tuple:
        """(start, end) of a root bucket in leaf order (ParISIndex API)."""
        return self.bucket_offsets[key], self.bucket_offsets[key + 1]

    # The disk boundary: every traced raw access goes through this one
    # callback, so the engine's per-round candidate gathers and the seed
    # window read are the ONLY places the raw file is touched.
    def _read(self, rows: jax.Array) -> jax.Array:
        out = jax.ShapeDtypeStruct(
            rows.shape + (self.series_length,), jnp.float32)
        return jax.pure_callback(self._read_host, out, rows)

    def _read_host(self, rows) -> np.ndarray:
        rows = np.asarray(rows)
        flat = self.reader.rows(rows.ravel())
        return flat.reshape(rows.shape + (self.series_length,))


def load_cold_shard(workdir: str, ref: ComponentRef, *, cache: BlockCache,
                    segments: int, cardinality: int) -> ColdShard:
    """Reopen one committed cold epoch: summaries in RAM, raw mmap'd."""
    d = os.path.join(workdir, ref.dir)
    keys, sax, pos = (
        np.load(os.path.join(d, f)) for f in _COLD_FILES[:3])
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    if meta["num_series"] != ref.num_series or meta["base"] != ref.base:
        raise ValueError(
            f"cold component {ref.dir} meta {meta} disagrees with "
            f"manifest {ref}")
    return ColdShard(
        sax=sax, pos=pos, keys=keys,
        reader=ColdReader(os.path.join(d, COLD_RAW), cache),
        base=ref.base, dir=ref.dir,
        series_length=int(meta["series_length"]),
        segments=segments, cardinality=cardinality)


# --------------------------------------------------------------- engines
def _cold_view(shard: ColdShard, *, leaf_cap: int, init: str,
               blocks=None) -> EngineView:
    """Cold-shard hooks for the ONE engine core.

    Identical to ``core.search._index_view`` except where the raw matrix
    is touched: ``gather_raw`` maps file positions through the hot
    inverse permutation and reads leaf rows via the block-cache
    callback, and the approx seed reads its leaf window as one
    contiguous range — same :func:`~repro.core.search.
    bucket_window_start` window, same distance/argmin math, so the
    seeded BSF is bit-identical to the in-memory path's. ``blocks`` is
    the optional explicit (block_q, block_n) kernel override; ``None``
    members resolve through the tuning table.
    """
    bpp = isax.padded_breakpoints(shard.cardinality)
    m = shard.num_series
    block_q, block_n = blocks or (None, None)

    def lower_bounds(qps, impl):
        return ops.lower_bound_sq_batch(
            qps, shard.sax, bpp, shard.series_length, impl=impl,
            block_q=block_q, block_n=block_n)

    def gather_raw(pos):
        # Same clip semantics as the in-memory take(..., mode="clip"):
        # a NO_POS sentinel reads a real row harmlessly (its +inf lower
        # bound keeps it outside every mask).
        rows = jnp.take(shard.inv, jnp.clip(pos, 0, m - 1), axis=0)
        return shard._read(rows)

    if init == "approx":
        leaf = min(int(leaf_cap), m)

        def seed(queries, impl):
            qs = isax.znorm(queries)
            qps = isax.paa(qs, shard.segments)
            qsax = isax.sax_from_paa(qps, shard.cardinality)
            keys = isax.root_key(qsax, shard.cardinality)
            s = bucket_window_start(shard.bucket_offsets, keys, leaf, m)
            # Leaf-order window == contiguous raw_leaf rows: ONE ranged
            # read per query, the pointer-index payoff.
            rows = s[:, None] + jnp.arange(leaf, dtype=s.dtype)[None, :]
            raws = shard._read(rows)
            wpos = jnp.take(shard.pos, rows, axis=0)

            def one(q, rw, wp):
                d = ops.euclid_sq(q, rw, impl=impl)
                j = jnp.argmin(d)
                return d[j], wp[j]

            bsf0, pos0 = jax.vmap(one)(qs, raws, wpos)
            return bsf0, pos0, leaf
    else:
        seed = None

    return EngineView(
        n_rows=m,
        num_series=m,
        segments=shard.segments,
        lower_bounds=lower_bounds,
        positions=lambda idx: jnp.take(shard.pos, idx, axis=0),
        gather_raw=gather_raw,
        seed=seed,
    )


def _cold_engine_for(shard: ColdShard, statics: tuple):
    """Cached per-shard jitted engine (the cold ``_engine_for``).

    Same statics key and same call and output contract as
    ``core.search._engine_for``, the optional real-row count included;
    the compiled closure bakes the hot arrays in as constants and crosses
    to the host only at the ``pure_callback`` raw reads.
    """
    from repro.core.search import _engine_core

    fn = shard._engines.get(statics)
    if fn is not None:
        return fn
    k, round_size, leaf_cap, sort, select, impl, init = statics[:7]
    blocks = statics[8] if len(statics) > 8 else None

    @jax.jit
    def fn(queries, eps_factor_sq=None, budget_rounds=None, n_real=None):
        view = _cold_view(shard, leaf_cap=leaf_cap, init=init, blocks=blocks)
        return _engine_core(
            view, queries, k=k, round_size=round_size, sort=sort,
            select=select, impl=impl, eps_factor_sq=eps_factor_sq,
            budget_rounds=budget_rounds, n_real=n_real)

    shard._engines[statics] = fn
    return fn


def cold_exact_knn_batch(
    shard: ColdShard,
    queries,
    k: int = 1,
    round_size: int = 4096,
    impl: str = "auto",
    select: str = "topk",
    sort: bool = True,
    leaf_cap: int = 256,
    stats: bool = False,
) -> tuple:
    """Exact k-NN over one cold shard (``exact_knn_batch`` contract).

    Positions are component-local; callers translate by ``shard.base``
    exactly like any other component's answer.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    k_eff = min(k, shard.num_series)
    fn = _cold_engine_for(
        shard, (k_eff, round_size, leaf_cap, sort, select, impl, "approx"))
    top_d, top_p, reads, updates, rounds = fn(
        jnp.asarray(queries, jnp.float32))
    if k_eff < k:  # tiny shard: pad missing neighbors with the sentinel
        n_q = top_d.shape[0]
        top_d = jnp.concatenate(
            [top_d, jnp.full((n_q, k - k_eff), INF)], axis=1)
        top_p = jnp.concatenate(
            [top_p, jnp.full((n_q, k - k_eff), NO_POS)], axis=1)
    if stats:
        return top_d, top_p, reads, updates, rounds
    return top_d, top_p


def cold_knn_batch_tiered(
    shard: ColdShard,
    queries,
    tier,
    k: int = 1,
    round_size: int = 4096,
    impl: str = "auto",
    select: str = "topk",
    leaf_cap: int = 256,
) -> tuple:
    """Tiered k-NN over one cold shard (``knn_batch_tiered`` contract)."""
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
    k_eff = min(k, shard.num_series)
    fn = _cold_engine_for(
        shard,
        (k_eff, round_size, leaf_cap, True, select, impl, "approx", True))
    eps_f, budget = tier_arrays(tiers)
    top_d, top_p, reads, updates, rounds, ach_sq = fn(qs, eps_f, budget)
    if k_eff < k:
        n_q = top_d.shape[0]
        top_d = jnp.concatenate(
            [top_d, jnp.full((n_q, k - k_eff), INF)], axis=1)
        top_p = jnp.concatenate(
            [top_p, jnp.full((n_q, k - k_eff), NO_POS)], axis=1)
    return top_d, top_p, achieved_epsilon(ach_sq)


def cold_exact_search_batch(
    shard: ColdShard, queries, cfg: SearchConfig = SearchConfig()
) -> SearchResult:
    """Exact 1-NN over one cold shard (``exact_search_batch`` contract)."""
    fn = _cold_engine_for(
        shard,
        (1, cfg.round_size, cfg.leaf_cap, cfg.sort, cfg.select, cfg.impl,
         "approx"))
    top_d, top_p, reads, updates, rounds = fn(
        jnp.asarray(queries, jnp.float32))
    return SearchResult(top_d[:, 0], top_p[:, 0], reads, updates, rounds)


def make_cold_batch_engine(
    shard: ColdShard,
    *,
    k: Optional[int] = None,
    round_size: int = 4096,
    leaf_cap: int = 256,
    sort: bool = True,
    select: str = "topk",
    impl: str = "auto",
    min_bucket: int = 1,
):
    """A routable, shape-stable batch engine over one cold shard.

    The cold counterpart of :func:`~repro.core.search.make_batch_engine`
    — in fact the SAME wrapper (pow2 bucket padding, tier plumbing,
    sentinel protocol), specialized only through the cold engine
    factory, so ``ShardedSearchRouter`` can serve a ``ColdShard``
    replica group exactly like an in-memory shard's.
    """
    return make_batch_engine(
        shard, k=k, round_size=round_size, leaf_cap=leaf_cap, sort=sort,
        select=select, impl=impl, min_bucket=min_bucket,
        engine_for=_cold_engine_for)
