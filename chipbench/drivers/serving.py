"""The served search path of one configuration, shared by the query drivers.

Set-up makes the collection on the device, builds the index
there (``build_index``, the one-shot build the pipeline's output equals),
makes the queries, hands the index to a ``ShardedSearchRouter`` and drops
every other reference to it, then warms up every batch shape the window
can flush. After the window the router is stopped and freed, the
collection is made again from the seed, and a sample of the answers drawn
from the seed is compared with the plain brute force at the timed size.
"""

from __future__ import annotations

import functools
import gc
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, manifest, reference

RESULT_WAIT_S = 60.0  # how long past the window's close answers may come
# The collection and the query pool come from this seed whatever the run's
# seed: how hard a query is depends on the data, and a p95 over one window
# moved more between seeds than between two runs of one seed. The run's
# seed orders the queries and the arrivals and draws the checked sample.
WORK_SEED = 0


@functools.partial(jax.jit, static_argnames=("k",))
def _stub_outputs(qs, *, k: int):
    """Zeros shaped as the engine's 5-tuple for a padded batch ``qs``."""
    z = jnp.sum(qs[:, :1], axis=1) * 0
    d = jnp.broadcast_to(z[:, None], (qs.shape[0], k))
    zi = z.astype(jnp.int32)
    return d, d.astype(jnp.int32), zi, zi, jnp.int32(0)


class Served:
    """Collection, index, router and queries of one query cell."""

    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.config, self.traffic, self.log = config, traffic, log
        self.rng = harness.seeds(seed)[1]
        self.k_data, self.k_query, self.k_warm = jax.random.split(
            harness.seeds(WORK_SEED)[0], 3)
        data = config["data"]
        self.n, self.length = data["num_series"], data["series_length"]
        self.gen = manifest.module("generators", data["generator"])
        self.k = traffic["k"]
        self.router = None
        self.queries = None
        self.attempted = 0
        self.answers = {}  # request id -> (dists, positions)
        self.errors = {}  # request id -> exception
        self.lock = threading.Lock()

    def setup(self, count: int) -> None:
        """Collection, ``count`` queries, index, router, warm-up."""
        from repro.core import build_index
        from repro.serving import ShardedSearchRouter
        t = time.perf_counter()
        ix, r = self.config["index"], self.config["router"]
        raw = self.collection()
        self.queries = self.query_pool(raw, count)
        warm = self.gen.queries(self.k_warm, raw, self.traffic["queries"],
                                r["max_batch"], np.random.default_rng(0))
        self.log(f"[setup] data {self.n} x {self.length} and {count} "
                 f"queries in {time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        index = build_index(raw, ix["segments"], ix["cardinality"],
                            refine_bits=ix["refine_bits"], impl=self.config["impl"])
        jax.block_until_ready(index)
        del raw
        self.router = ShardedSearchRouter(
            index, r["shards"], k=self.k, replicas=r["replicas"],
            max_batch=r["max_batch"], min_bucket=r["min_bucket"],
            max_wait_ms=r["max_wait_ms"], round_size=r["round_size"],
            select=r["select"], impl=self.config["impl"],
            leaf_cap=r["leaf_cap"])
        del index
        gc.collect()
        self.log(f"[setup] index and router in "
                 f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        self._warm_batch_shapes()
        self.router.start()
        for f in [self.router.submit(q) for q in warm]:
            f.result(timeout=600)
        self.log(f"[setup] warm-up in {time.perf_counter() - t:.3f} s")

    def collection(self) -> jax.Array:
        """The (N, n) collection on the device."""
        return self.gen.series(self.k_data, self.n, self.length)

    def query_pool(self, raw: jax.Array, count: int) -> np.ndarray:
        """``count`` host queries: the same set for every seed, in the
        seed's order."""
        pool = self.gen.queries(self.k_query, raw, self.traffic["queries"],
                                count, np.random.default_rng(WORK_SEED))
        return pool[self.rng.permutation(count)]

    def _warm_batch_shapes(self) -> None:
        """Run the batch engine's own padding and slicing for every batch
        size the window can flush, over a stub search: those small
        programs compile here, and the one real engine shape compiles in
        the warm-up batch.

        This leans on ``make_batch_engine``'s documented ``engine_for``
        hook (the one the cold tier passes its engines through): warming
        the 64 sizes through the router would run the real engine 64
        times. The program has no warm-up entry of its own yet."""
        from repro.core.search import make_batch_engine
        r = self.config["router"]
        shard_rows = -(-self.n // r["shards"])

        def stub(index, statics):
            return lambda qs, *_: _stub_outputs(qs, k=statics[0])

        engine = make_batch_engine(
            types.SimpleNamespace(num_series=shard_rows), k=self.k,
            round_size=r["round_size"], leaf_cap=r["leaf_cap"],
            select=r["select"], impl=self.config["impl"],
            min_bucket=r["min_bucket"],
            engine_for=stub)
        for qn in range(1, r["max_batch"] + 1):
            out = engine(np.zeros((qn, self.length), np.float32))
            np.asarray(out[0]), np.asarray(out[1])

    def submit(self, rid: int, on_done=None):
        """Submit query ``rid``; its answer or error is kept by id."""
        fut = self.router.submit(self.queries[rid % len(self.queries)])

        def done(f):
            now = time.perf_counter()
            exc = f.exception()
            with self.lock:
                if exc is None:
                    self.answers[rid] = f.result()
                else:
                    self.errors[rid] = exc
            if on_done is not None:
                on_done(rid, now, exc)

        fut.add_done_callback(done)
        return fut

    def release(self) -> None:
        """Stop the router and free the index on the device."""
        self.router.stop()
        self.router = None
        gc.collect()

    def check(self) -> dict:
        """A seeded sample of the answers against the brute force."""
        limits = self.traffic["check"]["limits"]
        ids = sorted(self.answers)
        size = min(self.traffic["check"]["sample"], len(ids))
        out = {"unanswered": (self.attempted - len(ids), 0)}
        if not ids:
            return out
        pick = sorted(self.rng.choice(ids, size, replace=False))
        t = time.perf_counter()
        raw = self.collection()
        qs = self.queries[[rid % len(self.queries) for rid in pick]]
        got_d = np.stack([np.asarray(self.answers[i][0]) for i in pick])
        got_p = np.stack([np.asarray(self.answers[i][1]) for i in pick])
        ref_d, ref_p = reference.knn(raw, qs, self.k + 1)
        del raw
        found = reference.compare_knn(got_d, got_p, ref_d, ref_p)
        self.log(f"[check] {size} answers of {len(ids)} compared in "
                 f"{time.perf_counter() - t:.3f} s")
        for name, value in found.items():
            out[name] = (value, limits[name])
        return out
