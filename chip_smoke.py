#!/usr/bin/env python3
"""Bring-up smoke: the served ParIS+ search path on a TPU at a real size.

    python3 chip_smoke.py                  # one chip
    python3 chip_smoke.py --chips 4        # the four-chip mesh search only
    python3 chip_smoke.py --series 262144  # a smaller collection

One process drives every phase; each prints one line of what it checked:

  device     JAX must find a TPU, else the script exits non-zero at once;
  kernels    every Pallas kernel (impl="pallas", compiled) against its jnp
             reference (impl="ref") on the same chip;
  build      PipelineBuilder(mode="paris+") over N random walks of length
             256 (w = 16, cardinality 256), compile time reported apart;
  serve      a 2-shard ShardedSearchRouter at k = 8 and one direct
             exact_knn_batch at k = 1, 64 queries each: half dataset series
             plus seeded noise, half fresh walks;
  reference  a plain chunked brute force on the chip, sum((x - q)**2) over
             z-normalised rows, written apart from the code under test;
  memory     peak device bytes.

With ``--chips 4`` only the mesh search (make_distributed_batch_search over
a 4-device mesh, k = 8) runs, against the same reference. The last line of
standard output is one JSON object, printed only when every check passed.
Times here are a bring-up smoke, not a benchmark. Data comes from
``--seed``; nothing outside the checkout is read.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LENGTH, SEGMENTS, CARD = 256, 16, 256
N_QUERIES, K, SHARDS = 64, 8, 2
MESH_BATCH = 32  # queries per mesh call (see mesh_search)
NOISE = 0.5  # sd of the noise added to dataset series (walk steps are 1)
KERNEL_ROWS = 65536  # rows of the kernel-parity inputs
KERNEL_TOL = 1e-6  # relative: lower bounds and distances, Pallas vs jnp
DIST_TOL = 1e-5  # relative: engine distances against the brute force
TIE_TOL = 1e-6  # relative: reference distances this close are a tie
REF_CHUNK = 1 << 18  # rows per brute-force step
RESULT_TIMEOUT_S = 900.0

_failures: list = []


def report(phase: str, ok: bool, line: str) -> None:
    """Print one phase line; remember a failed check for the exit code."""
    print(f"[{phase}] {'ok' if ok else 'FAILED'}: {line}", flush=True)
    if not ok:
        _failures.append(phase)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, as it reports."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += secs


def check_device(chips: int) -> dict:
    """Exit non-zero unless JAX's default backend is a TPU with ``chips``."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"[device] FAILED: JAX backend is {backend!r}, "
                         "not 'tpu'; this smoke runs on the chip only")
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    report("device", len(devs) >= chips,
           f"platform={info['platform']} kind={info['kind']} "
           f"count={info['count']} (need {chips})")
    return info


def _max_rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), 1e-30)))


def kernel_parity(seed: int, rows: int = KERNEL_ROWS) -> None:
    """Each Pallas kernel against its jnp reference on the same device."""
    import jax.numpy as jnp
    from repro.core import datagen, isax
    from repro.kernels import ops

    series = jnp.asarray(datagen.random_walk(rows, LENGTH, seed=seed + 7))
    bp = isax.gaussian_breakpoints(CARD)
    bpp = isax.padded_breakpoints(CARD)
    sax, _ = ops.paa_isax(series, bp, SEGMENTS, impl="ref")
    qs = isax.znorm(jnp.asarray(
        datagen.random_walk(N_QUERIES, LENGTH, seed=seed + 8)))
    qps = isax.paa(qs, SEGMENTS)
    data = isax.znorm(series)

    def pair(fn, *args, **kw):
        return fn(*args, impl="pallas", **kw), fn(*args, impl="ref", **kw)

    for name, (got, want) in {
        "lb_batch": pair(ops.lower_bound_sq_batch, qps, sax, bpp, LENGTH),
        "lb_single_rows": pair(ops.lower_bound_sq, qps[0], sax, bpp, LENGTH),
        "lb_single_cols": pair(ops.lower_bound_sq, qps[0], sax, bpp, LENGTH,
                               transposed=True),
        "euclid": pair(ops.euclid_sq, qs[0], data),
    }.items():
        err = _max_rel(got, want)
        report("kernels", err <= KERNEL_TOL,
               f"{name} max relative error {err:.3g} (limit {KERNEL_TOL})")

    # Packed multi-component sweep: two components, each padded to blocks.
    block = 128
    cut = rows // 3
    parts = [sax[:cut], sax[cut:]]
    packed, lens, real = [], [], []
    off = 0
    for part in parts:
        m = part.shape[0]
        pad = (-m) % block
        packed.append(jnp.pad(part, ((0, pad), (0, 0))))
        full = np.full(((m + pad) // block,), block, np.int32)
        full[-1] = block - pad if pad else block
        lens.append(full)
        real.append(np.arange(off, off + m))
        off += m + pad
    got, want = pair(ops.lower_bound_sq_multi, qps, jnp.concatenate(packed),
                     bpp, LENGTH, jnp.asarray(np.concatenate(lens)),
                     block_n=block)
    real = np.concatenate(real)
    pad_rows = np.setdiff1d(np.arange(off), real)
    err = _max_rel(np.asarray(got)[:, real], np.asarray(want)[:, real])
    pads_inf = bool(np.all(np.isinf(np.asarray(got)[:, pad_rows])))
    report("kernels", err <= KERNEL_TOL and pads_inf,
           f"lb_multi max relative error {err:.3g} (limit {KERNEL_TOL}), "
           f"pad lanes +inf: {pads_inf}")

    (d_p, i_p), (d_r, i_r) = pair(ops.euclid_min, qs[0], data)
    err = _max_rel(d_p, d_r)
    report("kernels", int(i_p) == int(i_r) and err <= KERNEL_TOL,
           f"euclid_min argmin {int(i_p)} vs {int(i_r)}, relative error "
           f"{err:.3g}")

    (sax_p, paa_p), (sax_r, paa_r) = pair(ops.paa_isax, series, bp, SEGMENTS)
    paa_p, paa_r = np.asarray(paa_p), np.asarray(paa_r)
    sax_p, sax_r = np.asarray(sax_p), np.asarray(sax_r)
    paa_err = float(np.max(np.abs(paa_p - paa_r)))
    # A symbol may differ only where a breakpoint separates the two PAA
    # values: the kernel's symbols must be exactly those of its own PAA.
    own = np.asarray(isax.sax_from_paa(jnp.asarray(paa_p), CARD))
    flips = int(np.sum(sax_p != sax_r))
    report("kernels", bool(np.array_equal(sax_p, own)) and paa_err <= 1e-5,
           f"paa_isax PAA max abs error {paa_err:.3g}, symbol flips "
           f"{flips} of {sax_r.size}, all at breakpoints: "
           f"{bool(np.array_equal(sax_p, own))}")


def make_queries(raw: np.ndarray, seed: int) -> np.ndarray:
    """Half dataset series plus seeded noise, half fresh random walks."""
    from repro.core import datagen
    rng = np.random.default_rng(seed + 1)
    half = N_QUERIES // 2
    picks = rng.choice(raw.shape[0], half, replace=False)
    near = raw[picks] + NOISE * rng.standard_normal(
        (half, raw.shape[1])).astype(np.float32)
    fresh = datagen.random_walk(N_QUERIES - half, raw.shape[1],
                                seed=seed + 2)
    return np.concatenate([near, fresh]).astype(np.float32)


def build(raw: np.ndarray, clock: CompileClock, impl: str = "auto"):
    """The ParIS+ staged pipeline over the whole collection."""
    from repro.core import PipelineBuilder, SeriesSource
    c0, t0 = clock.total, time.perf_counter()
    index, stats = PipelineBuilder(
        SEGMENTS, CARD, mode="paris+", impl=impl).build(
            SeriesSource.from_array(raw, chunk_series=1 << 16))
    index.raw.block_until_ready()
    secs, comp = time.perf_counter() - t0, clock.total - c0
    report("build", index.num_series == raw.shape[0],
           f"N={index.num_series} in {secs:.2f} s, of which compile "
           f"{comp:.2f} s (read {stats.read_time:.2f}, convert "
           f"{stats.convert_time:.2f}, construct {stats.construct_time:.2f}"
           f", finalize {stats.finalize_time:.2f})")
    return index


def _latency_line(lat_ms) -> str:
    lat = np.asarray(lat_ms)
    return f"p50 {np.percentile(lat, 50):.1f} ms, max {lat.max():.1f} ms"


def serve_router(index, queries: np.ndarray, clock: CompileClock,
                 impl: str = "auto") -> tuple:
    """64 queries through a 2-shard router at k = 8; every future awaited."""
    from repro.serving import ShardedSearchRouter
    router = ShardedSearchRouter(
        index, SHARDS, k=K, replicas=1, max_batch=N_QUERIES,
        min_bucket=N_QUERIES, max_wait_ms=5.0, impl=impl)
    router.start()
    c0, t0 = clock.total, time.perf_counter()
    for f in [router.submit(q) for q in queries]:  # warm-up: compiles
        f.result(timeout=RESULT_TIMEOUT_S)
    warm, comp = time.perf_counter() - t0, clock.total - c0
    done = np.zeros(len(queries))
    futs, sent = [], np.zeros(len(queries))
    for i, q in enumerate(queries):
        sent[i] = time.perf_counter()
        f = router.submit(q)
        f.add_done_callback(
            lambda _, i=i: done.__setitem__(i, time.perf_counter()))
        futs.append(f)
    answers = [f.result(timeout=RESULT_TIMEOUT_S) for f in futs]
    router.stop()
    stats = router.stats()
    lat_ms = (done - sent) * 1e3
    report("serve", True,
           f"router {SHARDS} shards k={K}: {len(answers)} answers, "
           f"{_latency_line(lat_ms)}, warm-up {warm:.2f} s (compile "
           f"{comp:.2f} s), batches {stats['batches']}")
    dists = np.stack([np.asarray(d) for d, _ in answers])
    pos = np.stack([np.asarray(p) for _, p in answers])
    return dists, pos


def serve_direct(index, queries: np.ndarray, clock: CompileClock,
                 impl: str = "auto") -> tuple:
    """One direct exact_knn_batch call at k = 1 (warm-up, then timed)."""
    import jax
    import jax.numpy as jnp
    from repro.core import exact_knn_batch
    qs = jnp.asarray(queries)

    def call():
        return jax.block_until_ready(exact_knn_batch(
            index, qs, k=1, impl=impl, stats=True))

    c0, t0 = clock.total, time.perf_counter()
    call()
    warm, comp = time.perf_counter() - t0, clock.total - c0
    t0 = time.perf_counter()
    d, p, reads, _, rounds = call()
    ms = (time.perf_counter() - t0) * 1e3
    reads_frac = float(np.mean(np.asarray(reads))) / index.num_series
    report("serve", True,
           f"direct exact_knn_batch k=1: Q={len(queries)} in one call, "
           f"{_latency_line([ms])}, mean raw reads / N {reads_frac:.4f}, "
           f"rounds {int(rounds)}, warm-up {warm:.2f} s (compile "
           f"{comp:.2f} s)")
    return np.asarray(d), np.asarray(p)


def reference_knn(raw: np.ndarray, queries: np.ndarray, k: int) -> tuple:
    """Brute-force k nearest neighbours on the device, chunk by chunk.

    Rows and queries are z-normalised here; distances are the plain
    ``sum((x - q)**2)``, one query at a time, so no matmul form rounds
    them. Returns (Q, k) ascending squared distances and file positions.
    """
    import jax
    import jax.numpy as jnp

    def znorm(x):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        return (x - mu) / (jnp.std(x, axis=-1, keepdims=True) + 1e-8)

    @jax.jit
    def step(top_d, top_p, qz, chunk, base):
        x = znorm(chunk)
        d = jax.lax.map(lambda q: jnp.sum((x - q) ** 2, axis=-1), qz)
        p = base + jnp.arange(chunk.shape[0], dtype=jnp.int32)
        all_d = jnp.concatenate([top_d, d], axis=1)
        all_p = jnp.concatenate(
            [top_p, jnp.broadcast_to(p, d.shape)], axis=1)
        neg, sel = jax.lax.top_k(-all_d, k)
        return -neg, jnp.take_along_axis(all_p, sel, axis=1)

    qz = znorm(jnp.asarray(queries))
    top_d = jnp.full((len(queries), k), jnp.inf, jnp.float32)
    top_p = jnp.full((len(queries), k), -1, jnp.int32)
    for s in range(0, raw.shape[0], REF_CHUNK):
        top_d, top_p = step(top_d, top_p, qz,
                            jnp.asarray(raw[s:s + REF_CHUNK]), jnp.int32(s))
    return np.asarray(top_d), np.asarray(top_p)


def compare(name: str, got_d, got_p, ref_d, ref_p) -> None:
    """Positions identical except at reference ties; distances close.

    ``ref_d``/``ref_p`` hold one neighbour more than the answer, so a tie
    between the k-th and the (k+1)-th reference distance is seen.
    """
    k = got_d.shape[1]
    rel = np.abs(got_d - ref_d[:, :k]) / np.maximum(ref_d[:, :k], 1e-30)
    tie_prev = np.zeros_like(rel, bool)
    tie_next = np.zeros_like(rel, bool)
    gap = np.abs(np.diff(ref_d, axis=1)) <= TIE_TOL * ref_d[:, 1:]
    tie_prev[:, 1:] = gap[:, :k - 1]
    tie_next[:, :] = gap[:, :k]
    moved = got_p != ref_p[:, :k]
    bad_pos = moved & ~(tie_prev | tie_next)
    ok = not bad_pos.any() and float(rel.max()) <= DIST_TOL
    report("reference", ok,
           f"{name}: {got_p.shape[0]}x{k} answers, positions differing "
           f"{int(moved.sum())} (all at ties: {not bad_pos.any()}), max "
           f"relative distance error {float(rel.max()):.3g} "
           f"(limit {DIST_TOL})")


def memory(devices=None) -> None:
    """Peak device bytes, as each device reports them."""
    import jax
    peaks = []
    for dev in devices or jax.local_devices():
        stats = dev.memory_stats() or {}
        peaks.append(f"{dev.id}:{stats.get('peak_bytes_in_use', 'n/a')}")
    report("memory", True, "peak_bytes_in_use " + " ".join(peaks))


def mesh_search(index, queries: np.ndarray, clock: CompileClock,
                chips: int, impl: str = "auto") -> tuple:
    """make_distributed_batch_search over a ``chips``-device mesh, k = 8.

    The queries go in calls of ``MESH_BATCH``: at Q = 64 the step's own
    temporaries (the (Q, N/chips) bounds, their top_k and the pre-gathered
    candidates) are about 1.07 GB a device, which would lift a device's
    peak above half of ``raw_sorted`` and hide what the check is for.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import distributed as dist
    axes = ("shard",)
    mesh = jax.make_mesh((chips,), axes)
    dindex = dist.dist_index_from(index, dist.index_shardings(mesh, axes))
    rows = dindex.raw_sorted.size * dindex.raw_sorted.dtype.itemsize
    step = jax.jit(dist.make_distributed_batch_search(
        mesh, axes, series_length=index.series_length,
        segments=index.segments, cardinality=index.cardinality, k=K,
        impl=impl))
    batches = [jnp.asarray(queries[i:i + MESH_BATCH])
               for i in range(0, len(queries), MESH_BATCH)]
    c0, t0 = clock.total, time.perf_counter()
    jax.block_until_ready(step(dindex, batches[0]))
    warm, comp = time.perf_counter() - t0, clock.total - c0
    results, ms = [], []
    for qs in batches:
        t0 = time.perf_counter()
        results.append(jax.block_until_ready(step(dindex, qs)))
        ms.append((time.perf_counter() - t0) * 1e3)
    reads = np.concatenate([np.asarray(r.raw_reads) for r in results])
    report("mesh", True,
           f"{chips}-device mesh k={K}: Q={len(queries)} in calls of "
           f"{MESH_BATCH}, per call {_latency_line(ms)}, mean raw reads / N "
           f"{float(reads.mean()) / index.num_series:.4f}, raw_sorted "
           f"{rows} bytes, warm-up {warm:.2f} s (compile {comp:.2f} s)")
    return (np.concatenate([np.asarray(r.dist_sq) for r in results]),
            np.concatenate([np.asarray(r.position) for r in results]), rows)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--series", type=int, default=1 << 22,
                    help="collection size N (default 4,194,304)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh search")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    info = check_device(args.chips)
    clock = CompileClock()
    if args.chips == 1:
        kernel_parity(args.seed)

    from repro.core import datagen
    t0 = time.perf_counter()
    raw = datagen.random_walk(args.series, LENGTH, seed=args.seed)
    queries = make_queries(raw, args.seed)
    print(f"[data] {args.series} walks of length {LENGTH} and "
          f"{len(queries)} queries made in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    index = build(raw, clock)

    if args.chips == 1:
        router_d, router_p = serve_router(index, queries, clock)
        gc.collect()  # the router's shard copies go before the next phase
        direct_d, direct_p = serve_direct(index, queries, clock)
        ref_d, ref_p = reference_knn(raw, queries, K + 1)
        compare("router k=8", router_d, router_p, ref_d, ref_p)
        compare("direct k=1", direct_d, direct_p, ref_d, ref_p)
        memory()
    else:
        import jax
        mesh_d, mesh_p, rows = mesh_search(index, queries, clock, args.chips)
        del index
        ref_d, ref_p = reference_knn(raw, queries, K + 1)
        compare(f"mesh {args.chips} devices k={K}", mesh_d, mesh_p, ref_d,
                ref_p)
        devs = jax.local_devices()
        memory(devs)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs[1:args.chips]]
        report("memory", all(p is not None and p < rows / 2 for p in peaks),
               f"devices 1-{args.chips - 1} peak below half of raw_sorted "
               f"({rows // 2} bytes)")

    if _failures:
        raise SystemExit(f"smoke failed in: {sorted(set(_failures))}")
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
