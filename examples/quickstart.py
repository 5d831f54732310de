"""Quickstart: build a ParIS+ index and answer exact 1-NN queries.

    PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax.numpy as jnp
import numpy as np

from repro.core import (PipelineBuilder, SearchConfig, SeriesSource,
                        brute_force, exact_search, random_walk)


def main():
    n, length = 100_000, 256
    print(f"generating {n} random-walk series of length {length} ...")
    raw = random_walk(n, length, seed=0)

    print("building the index through the ParIS+ staged pipeline ...")
    t0 = time.time()
    index, stats = PipelineBuilder(mode="paris+", n_workers=4).build(
        SeriesSource.from_array(raw, chunk_series=16384))
    print(f"  built in {stats.total_time:.2f}s "
          f"(read {stats.read_time:.2f}s, convert {stats.convert_time:.2f}s,"
          f" construct {stats.construct_time:.3f}s,"
          f" overlap {stats.overlap_efficiency:.0%})")
    print(f"  {index.num_series} series, {index.num_buckets} root buckets")

    rng = np.random.default_rng(7)
    for i in range(5):
        q = jnp.asarray(rng.standard_normal(length).cumsum(), jnp.float32)
        t0 = time.time()
        res = exact_search(index, q, SearchConfig())
        t_idx = time.time() - t0
        t0 = time.time()
        ref = brute_force(index, q)
        t_brute = time.time() - t0
        ok = int(res.position) == int(ref.position)
        print(f"query {i}: 1-NN at offset {int(res.position)} "
              f"dist={float(res.dist_sq) ** 0.5:.3f} "
              f"reads={int(res.raw_reads)}/{n} "
              f"({t_idx * 1e3:.1f}ms vs brute {t_brute * 1e3:.1f}ms) "
              f"exact={ok}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
