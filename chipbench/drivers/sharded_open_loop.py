"""Open loop over a collection cut into file-order shares, one per chip.

The configuration's ``router.shards`` gives S. Share ``s`` (rows
``[s * N/S, (s + 1) * N/S)``) is made on chip ``s`` from the work key
folded with ``s`` and built there by ``build_sharded_index_on_devices``;
one ``ShardedSearchRouter`` serves the shards, each shard's engine on its
own chip. Work that compiles a program per chip (making the shares and
the query pool, warming each shard) runs in a thread per chip, so the
chips' compiles overlap. The traffic and the timing are
:mod:`open_loop`'s; the window's counters add the router's mean shard
wait (first to last shard answer) and host merge time per answered
query. The check compares a sample of the answers with
:mod:`chipbench.reference_sharded`: the float32 brute force over each
share on its own chip, merged on the host.
"""

from __future__ import annotations

import gc
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from chipbench import reference, reference_sharded
from chipbench.drivers import open_loop
from chipbench.drivers.serving import WORK_SEED, Served, _stub_outputs
from repro.core import build_sharded_index_on_devices, make_batch_engine
from repro.serving import ShardedSearchRouter


def _each(fn, items: list) -> list:
    """``fn`` over ``items``, one thread each, results in order."""
    with ThreadPoolExecutor(len(items)) as pool:
        return list(pool.map(fn, items))


class ShardedServed(Served):
    """Shares, shards, router and queries of one multi-chip query cell."""

    def __init__(self, config: dict, traffic: dict, seed: int, log):
        super().__init__(config, traffic, seed, log)
        shards = config["router"]["shards"]
        if self.n % shards:
            raise ValueError(f"{self.n} series do not split into {shards} "
                             "equal shares")
        self.devices = jax.devices()[:shards]

    def collection(self) -> list:
        """The collection as S shares, share ``s`` made on chip ``s``."""
        rows = self.n // len(self.devices)
        return _each(lambda s: self.gen.series(
            jax.device_put(jax.random.fold_in(self.k_data, s),
                           self.devices[s]), rows, self.length),
            list(range(len(self.devices))))

    def query_pool(self, shares: list, count: int) -> np.ndarray:
        """``count`` host queries, the same set for every seed, in the
        seed's order. Each share gives the mix from the same key (the same
        local rows, noise and fresh walks); a fixed draw picks the share of
        each near query, so its stored series is uniform over all rows."""
        pools = _each(lambda raw: self.gen.queries(
            self.k_query, raw, self.traffic["queries"], count,
            np.random.default_rng(WORK_SEED)), shares)
        owner = np.random.default_rng(WORK_SEED + 1).integers(
            0, len(shares), count)
        pool = np.stack(pools)[owner, np.arange(count)]
        return pool[self.rng.permutation(count)]

    def setup(self, count: int) -> None:
        """Shares, ``count`` queries, shards, router, warm-up."""
        t = time.perf_counter()
        ix, r = self.config["index"], self.config["router"]
        shares = self.collection()
        self.queries = self.query_pool(shares, count)
        warm = self.gen.queries(self.k_warm, shares[0],
                                self.traffic["queries"], r["max_batch"],
                                np.random.default_rng(0))
        self.log(f"[setup] data {self.n} x {self.length} on "
                 f"{len(shares)} chips and {count} queries in "
                 f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        index = build_sharded_index_on_devices(
            shares, ix["segments"], ix["cardinality"],
            refine_bits=ix["refine_bits"], impl=self.config["impl"])
        jax.block_until_ready(index.shards)
        del shares
        self.router = ShardedSearchRouter(
            index, k=self.k, replicas=r["replicas"],
            max_batch=r["max_batch"], min_bucket=r["min_bucket"],
            max_wait_ms=r["max_wait_ms"], round_size=r["round_size"],
            select=r["select"], impl=self.config["impl"],
            leaf_cap=r["leaf_cap"])
        del index
        gc.collect()
        self.log(f"[setup] shards and router in "
                 f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        self._warm_batch_shapes()
        self.router.start()
        for f in [self.router.submit(q) for q in warm]:
            f.result(timeout=600)
        self.log(f"[setup] warm-up in {time.perf_counter() - t:.3f} s")

    def _warm_batch_shapes(self) -> None:
        """The base's stub warm-up, over each shard: every batch size's
        slicing compiles on every chip (the real engine compiles on each
        in the warm-up batch)."""
        r = self.config["router"]

        def stub(index, statics):
            return lambda qs, *_: _stub_outputs(qs, k=statics[0])

        def warm(shard):
            engine = make_batch_engine(
                shard, k=self.k, round_size=r["round_size"],
                leaf_cap=r["leaf_cap"], select=r["select"],
                impl=self.config["impl"], min_bucket=r["min_bucket"],
                engine_for=stub)
            for qn in range(1, r["max_batch"] + 1):
                out = engine(np.zeros((qn, self.length), np.float32))
                np.asarray(out[0]), np.asarray(out[1])

        _each(warm, list(self.router.sharded.shards))

    def check(self) -> dict:
        """A seeded sample of the answers against the per-share brute
        force, merged on the host."""
        limits = self.traffic["check"]["limits"]
        ids = sorted(self.answers)
        size = min(self.traffic["check"]["sample"], len(ids))
        out = {"unanswered": (self.attempted - len(ids), 0)}
        if not ids:
            return out
        pick = sorted(self.rng.choice(ids, size, replace=False))
        t = time.perf_counter()
        shares = self.collection()
        qs = self.queries[[rid % len(self.queries) for rid in pick]]
        got_d = np.stack([np.asarray(self.answers[i][0]) for i in pick])
        got_p = np.stack([np.asarray(self.answers[i][1]) for i in pick])
        ref_d, ref_p = reference_sharded.knn(shares, qs, self.k + 1)
        del shares
        found = reference.compare_knn(got_d, got_p, ref_d, ref_p)
        self.log(f"[check] {size} answers of {len(ids)} compared in "
                 f"{time.perf_counter() - t:.3f} s")
        for name, value in found.items():
            out[name] = (value, limits[name])
        return out


class Driver(open_loop.Driver):
    """One open-loop query cell over per-chip shards."""

    def __init__(self, config, traffic, seed, seconds, log):
        super().__init__(config, traffic, seed, seconds, log,
                         served=ShardedServed(config, traffic, seed, log))

    def measure(self, tracer):
        before = self.served.router.stats()
        window = super().measure(tracer)
        after = self.served.router.stats()
        merges = after["merges"] - before["merges"]
        if merges:
            for name in ("shard_wait_ms", "merge_ms"):
                key = name + "_sum"
                window.counters[name] = (after[key] - before[key]) / merges
        return window
