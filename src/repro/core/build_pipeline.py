"""Staged, double-buffered index construction (paper §3.1–3.2, Figs. 3–5).

Reproduces the paper's three-stage scheduling with the CPU work moved to the
accelerator and the thread synchronization moved to a task queue:

  Stage 1 — Coordinator: reads raw-series chunks from the SeriesSource (the
    "disk") into one half of a double buffer while workers process the other
    half. Chunk size = the paper's double-buffer-size knob (Fig. 11).
  Stage 2 — IndexBulkLoading: converts a chunk to iSAX (the paa_isax kernel),
    computes radix keys, and — in ParIS+ mode — also does the tree-building
    work *incrementally* (sorts the chunk into leaf order), overlapping with
    the Coordinator's reads. In ParIS mode this work is deferred.
  Stage 3 — IndexConstruction: at every memory-limit epoch, turns the
    accumulated summaries into leaf order and materializes them ("OutBuf
    flush") as an epoch shard on disk. In ParIS mode this includes the whole
    sort (a stop-the-world CPU phase, like ParIS's IndexConstruction workers);
    in ParIS+ mode the runs are already sorted, so the epoch flush is a linear
    merge + write — I/O-bound, which is exactly the paper's ParIS+ claim.

  Finalize — epoch shards are merge-sorted into the final index (the paper
    keeps subtrees on disk; we keep one sorted CSR file per epoch and merge).

Dynamic work assignment (the paper's atomic fetch&increment over RecBufs) is
the executor's task queue; it is also the straggler-mitigation story for the
host-side ingestion path at pod scale (slow readers don't stall converters).

Per-stage wall-clock times are recorded so benchmarks can reproduce the
paper's Figs. 9–13 (stage breakdown, worker sweep, buffer sweep, size sweep).
Each stage's interval is also a profiler span, ``paris.build.<stage>``
(read, convert, construct, flush, finalize, assemble), timed by the same
helper as its ``BuildStats`` field.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import tempfile
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isax
from repro.core.datagen import SeriesSource
from repro.core.index import assemble_index, empty_index
from repro.kernels import ops


@dataclasses.dataclass
class BuildStats:
    """Per-stage wall-clock timings for one pipelined index build."""
    read_time: float = 0.0  # Stage 1: "disk" -> buffer
    convert_time: float = 0.0  # Stage 2: ConvertToSAX (+ ParIS+ presort)
    construct_time: float = 0.0  # Stage 3: sort/merge into leaf order
    flush_time: float = 0.0  # Stage 3: epoch shard writes
    finalize_time: float = 0.0  # final multi-epoch merge
    assemble_time: float = 0.0  # raw upload, z-normalisation, index arrays
    total_time: float = 0.0
    epochs: int = 0
    chunks: int = 0

    @property
    def cpu_time(self) -> float:
        """Total CPU-stage time (convert + construct)."""
        return self.convert_time + self.construct_time

    @property
    def overlap_efficiency(self) -> float:
        """Fraction of CPU work hidden behind I/O (1.0 = fully hidden).

        Read, flush, finalize and assemble time is not CPU-stage work, so
        it is not counted as exposed.
        """
        busy = self.cpu_time
        if busy <= 0:
            return 1.0
        if self.total_time <= 0:
            # Mid-build (total_time not stamped yet): the exposed-time
            # estimate below would read as "fully hidden" — report zero
            # overlap instead of a spuriously perfect figure.
            return 0.0
        exposed = max(self.total_time - self.read_time - self.flush_time
                      - self.finalize_time - self.assemble_time, 0.0)
        return max(0.0, min(1.0, 1.0 - exposed / busy))


_STAGE_LOCK = threading.Lock()  # convert stages end on worker threads


@contextlib.contextmanager
def _stage(stats: BuildStats, name: str):
    """Time one stage interval: the profiler span ``paris.build.<name>``
    and the same interval added to ``stats.<name>_time``."""
    field = f"{name}_time"
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"paris.build.{name}"):
            yield
    finally:
        dt = time.perf_counter() - t0
        with _STAGE_LOCK:
            setattr(stats, field, getattr(stats, field) + dt)


def _host_refine_key(sax: np.ndarray, refine_bits: int, cardinality: int
                     ) -> np.ndarray:
    """Packed bit-plane key as uint64 (host numpy is x64-capable)."""
    bits_per_symbol = (cardinality - 1).bit_length()
    w = sax.shape[-1]
    s = sax.astype(np.uint64)
    key = np.zeros(sax.shape[:-1], np.uint64)
    weights = (1 << np.arange(w - 1, -1, -1, dtype=np.uint64))
    for plane in range(refine_bits):
        bits = (s >> np.uint64(bits_per_symbol - 1 - plane)) & np.uint64(1)
        key = (key << np.uint64(w)) | (bits * weights).sum(-1, dtype=np.uint64)
    return key


def _merge_sorted(keys_a, keys_b, payloads_a, payloads_b):
    """Stable linear merge of two sorted runs (vectorized, no Python loop)."""
    na, nb = len(keys_a), len(keys_b)
    out_pos_a = np.arange(na) + np.searchsorted(keys_b, keys_a, side="left")
    out_pos_b = np.arange(nb) + np.searchsorted(keys_a, keys_b, side="right")
    keys = np.empty(na + nb, keys_a.dtype)
    keys[out_pos_a] = keys_a
    keys[out_pos_b] = keys_b
    merged = []
    for pa, pb in zip(payloads_a, payloads_b):
        buf = np.empty((na + nb, *pa.shape[1:]), pa.dtype)
        buf[out_pos_a] = pa
        buf[out_pos_b] = pb
        merged.append(buf)
    return keys, merged


def merge_runs(runs):
    """log2(k) pairwise-merge passes over (keys, [payloads...]) runs.

    Linear merges only — the ParIS+ property the epoch finalize and the
    live-ingest compactor (``core.ingest``) both rely on. Runs must be
    ordered by file offset: ``_merge_sorted`` breaks key ties toward the
    left run, so offset order makes ties resolve by original position —
    exactly a stable sort over the concatenated input.
    """
    if not runs:
        raise ValueError("merge_runs needs at least one run")
    while len(runs) > 1:
        nxt = []
        for i in range(0, len(runs) - 1, 2):
            (ka, pa), (kb, pb) = runs[i], runs[i + 1]
            nxt.append(_merge_sorted(ka, kb, pa, pb))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return runs[0]


_merge_runs = merge_runs  # backwards-compatible private alias


def bulk_load_chunk(
    chunk_np: np.ndarray,
    offset: int,
    *,
    segments: int,
    cardinality: int,
    refine_bits: int = 4,
    breakpoints=None,
    impl: str = "auto",
    presort: bool = True,
):
    """Stage-2 IndexBulkLoading on one chunk: (keys, sax, pos) host arrays.

    The reusable core of the builder's ConvertToSAX task — znorm + the
    paa_isax kernel + packed refine keys + (optionally) the ParIS+
    incremental presort into leaf order. ``offset`` is the chunk's global
    file position, baked into ``pos``. Shared by :class:`PipelineBuilder`
    (one call per double-buffered chunk) and the live-ingest delta-shard
    builder (``core.ingest.build_delta_shard``, one call per appended
    batch), so both paths produce byte-identical sorted runs.
    """
    if breakpoints is None:
        breakpoints = isax.gaussian_breakpoints(cardinality)
    x = jnp.asarray(isax.znorm(jnp.asarray(chunk_np)))
    sax, _ = ops.paa_isax(x, breakpoints, segments, impl=impl,
                          normalize=False)
    sax = np.asarray(jax.device_get(sax))
    keys = _host_refine_key(sax, refine_bits, cardinality)
    pos = np.arange(offset, offset + len(sax), dtype=np.int32)
    if presort:
        order = np.argsort(keys, kind="stable")
        keys, sax, pos = keys[order], sax[order], pos[order]
    return keys, sax, pos


class PipelineBuilder:
    """ParIS/ParIS+ index builder. ``mode``: "paris+", "paris", or "serial"."""

    def __init__(
        self,
        segments: int = isax.DEFAULT_SEGMENTS,
        cardinality: int = isax.DEFAULT_CARDINALITY,
        *,
        mode: str = "paris+",
        n_workers: int = 4,
        refine_bits: int = 4,
        mem_limit_series: Optional[int] = None,
        impl: str = "auto",
        workdir: Optional[str] = None,
    ):
        if mode not in ("paris+", "paris", "serial"):
            raise ValueError(f"unknown mode {mode!r}")
        self.segments = segments
        self.cardinality = cardinality
        self.mode = mode
        self.n_workers = max(0 if mode == "serial" else 1, n_workers)
        self.refine_bits = refine_bits
        self.mem_limit_series = mem_limit_series
        self.impl = impl
        self.workdir = workdir
        self._bp = isax.gaussian_breakpoints(cardinality)

    # -- Stage 2 task: ConvertToSAX (+ presort in ParIS+ mode) ------------
    def _bulk_load(self, chunk_np: np.ndarray, offset: int,
                   stats: BuildStats):
        # In ParIS+ mode the incremental "tree building" (presort into leaf
        # order) happens here, overlapped with the Coordinator's next read.
        with _stage(stats, "convert"):
            keys, sax, pos = bulk_load_chunk(
                chunk_np, offset,
                segments=self.segments, cardinality=self.cardinality,
                refine_bits=self.refine_bits, breakpoints=self._bp,
                impl=self.impl, presort=self.mode == "paris+",
            )
        return offset, keys, sax, pos

    # -- Stage 3: epoch construction + shard flush -------------------------
    def _construct_epoch(self, runs, epoch_dir: str, stats: BuildStats):
        with _stage(stats, "construct"):
            # Runs are keyed by file offset so that equal-key ties always
            # break by original position — the pipeline is byte-identical
            # to the one-shot build_index() regardless of worker completion
            # order.
            runs = [r[1:] for r in sorted(runs, key=lambda r: r[0])]
            if self.mode == "paris+":
                keys, (sax, pos) = _merge_runs(runs)  # linear merges only
            else:
                keys = np.concatenate([r[0] for r in runs])
                sax = np.concatenate([r[1][0] for r in runs])
                pos = np.concatenate([r[1][1] for r in runs])
                order = np.argsort(keys, kind="stable")  # stop-the-world
                keys, sax, pos = keys[order], sax[order], pos[order]
        with _stage(stats, "flush"):
            os.makedirs(epoch_dir, exist_ok=True)
            np.save(os.path.join(epoch_dir, "keys.npy"), keys)
            np.save(os.path.join(epoch_dir, "sax.npy"), sax)
            np.save(os.path.join(epoch_dir, "pos.npy"), pos)
        stats.epochs += 1

    def build(self, source: SeriesSource):
        """Run the pipeline; returns (ParISIndex, BuildStats).

        An empty source produces an empty (zero-series) index. On failure
        with a caller-owned ``workdir``, every epoch shard directory this
        run created is removed — a later build into the same workdir never
        sees partial ``e{N}`` shards.
        """
        stats = BuildStats()
        t_start = time.perf_counter()
        workdir = self.workdir or tempfile.mkdtemp(prefix="paris_build_")
        own_workdir = self.workdir is None
        epoch_runs: List = []
        epoch_dirs: List[str] = []
        series_in_mem = 0
        mem_limit = self.mem_limit_series or (1 << 62)
        lock = threading.Lock()
        ok = False

        def collect(fut: Future):
            offset, keys, sax, pos = fut.result()
            with lock:
                epoch_runs.append((offset, keys, [sax, pos]))

        def flush_epoch(runs):
            # Record the shard dir BEFORE writing so a mid-write failure
            # still cleans it up (caller-owned workdir, see finally).
            d = os.path.join(workdir, f"e{len(epoch_dirs)}")
            epoch_dirs.append(d)
            self._construct_epoch(runs, d, stats)

        try:
            if self.mode == "serial":
                for i in range(source.num_chunks):
                    with _stage(stats, "read"):
                        chunk, off = source.read(i)
                    offset, keys, sax, pos = self._bulk_load(
                        chunk, off, stats)
                    epoch_runs.append((offset, keys, [sax, pos]))
                    stats.chunks += 1
                    series_in_mem += len(chunk)
                    if series_in_mem >= mem_limit:
                        flush_epoch(epoch_runs)
                        epoch_runs, series_in_mem = [], 0
            else:
                with ThreadPoolExecutor(self.n_workers) as pool:
                    pending: List[Future] = []
                    for i in range(source.num_chunks):
                        with _stage(stats, "read"):
                            chunk, off = source.read(i)  # Coordinator: B1
                        # Double buffering: at most 2 chunks in flight — wait
                        # for the older half before reusing it.
                        while len(pending) >= 2:
                            pending.pop(0).result()
                        fut = pool.submit(self._bulk_load, chunk, off, stats)
                        fut.add_done_callback(collect)
                        pending.append(fut)
                        stats.chunks += 1
                        series_in_mem += len(chunk)
                        if series_in_mem >= mem_limit:
                            for f in pending:  # barrier (Alg. 4 line 9)
                                f.result()
                            pending.clear()
                            with lock:
                                runs, epoch_runs = epoch_runs, []
                            flush_epoch(runs)
                            series_in_mem = 0
                    for f in pending:
                        f.result()
            if epoch_runs:
                with lock:
                    runs, epoch_runs = epoch_runs, []
                flush_epoch(runs)

            if not epoch_dirs:
                # Empty source: no chunks were read, no epochs flushed.
                # merge_runs([]) has nothing to return — hand back an empty
                # index of the source's series length instead of crashing.
                index = empty_index(source.length, self.segments,
                                    self.cardinality)
                stats.total_time = time.perf_counter() - t_start
                ok = True
                return index, stats

            # Finalize: merge epoch shards into the CSR index.
            with _stage(stats, "finalize"):
                shards = []
                for d in epoch_dirs:
                    shards.append((
                        np.load(os.path.join(d, "keys.npy")),
                        [np.load(os.path.join(d, "sax.npy")),
                         np.load(os.path.join(d, "pos.npy"))],
                    ))
                keys, (sax_sorted, pos_sorted) = merge_runs(shards)
            # Assemble: the raw upload, z-normalisation and the index's
            # device arrays; waits for the device so the stage is whole.
            with _stage(stats, "assemble"):
                raw = isax.znorm(
                    jnp.asarray(np.asarray(source.data, np.float32)))
                index = jax.block_until_ready(assemble_index(
                    sax_sorted, pos_sorted, raw, self.segments,
                    self.cardinality))
            stats.total_time = time.perf_counter() - t_start
            ok = True
            return index, stats
        finally:
            if own_workdir:
                shutil.rmtree(workdir, ignore_errors=True)
            elif not ok:
                # Caller-owned workdir + a failed run: remove the epoch
                # shards this run created (partial or complete) so the
                # directory is not left littered with unusable e{N} dirs.
                for d in epoch_dirs:
                    shutil.rmtree(d, ignore_errors=True)
