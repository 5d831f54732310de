"""Index construction invariants + exact-search correctness (the paper's
core claim: the index answers exactly, orders faster)."""


import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PipelineBuilder, SearchConfig, SeriesSource, brute_force, build_index,
    exact_knn, exact_search, isax, nb_exact_search,
)
from repro.core.index import validate_index
from repro.core.classifier import KnnClassifier

RNG = np.random.default_rng(3)


def _queries(n, length=256):
    return [jnp.asarray(RNG.standard_normal(length).cumsum(),
                        jnp.float32) for _ in range(n)]


def test_index_invariants(small_index):
    inv = validate_index(small_index)
    assert all(inv.values()), inv


def test_pipeline_matches_oneshot_all_modes(walk_20k):
    ref = build_index(jnp.asarray(walk_20k))
    src = SeriesSource.from_array(walk_20k, chunk_series=4096)
    for mode in ("paris+", "paris", "serial"):
        idx, stats = PipelineBuilder(
            mode=mode, n_workers=3, mem_limit_series=8000).build(src)
        assert np.array_equal(np.asarray(idx.sax), np.asarray(ref.sax)), mode
        assert np.array_equal(np.asarray(idx.pos), np.asarray(ref.pos)), mode
        assert np.array_equal(np.asarray(idx.bucket_offsets),
                              np.asarray(ref.bucket_offsets)), mode
        assert stats.epochs == 3


@pytest.mark.parametrize("cfg", [
    SearchConfig(),  # ParIS+
    SearchConfig(round_size=512),
    SearchConfig(sort=False),  # ADS+-style serial order
])
def test_exact_search_equals_brute_force(small_index, cfg):
    for q in _queries(4):
        want = brute_force(small_index, q)
        got = exact_search(small_index, q, cfg)
        assert int(got.position) == int(want.position)
        np.testing.assert_allclose(float(got.dist_sq), float(want.dist_sq),
                                   rtol=1e-4)
        assert int(got.raw_reads) <= small_index.num_series


def test_nb_variant_exact_but_weaker_pruning(small_index):
    reads_nb, reads_plus = 0, 0
    for i in range(4):
        # cold-init regime (weak first BSF): where sharing the BSF matters
        base = np.asarray(small_index.raw[RNG.integers(
            0, small_index.num_series)])
        q = jnp.asarray(base + RNG.standard_normal(256) * 1.5, jnp.float32)
        want = brute_force(small_index, q)
        nb = nb_exact_search(small_index, q, SearchConfig(
            round_size=512, workers=8, leaf_cap=4))
        plus = exact_search(small_index, q, SearchConfig(round_size=512,
                                                         leaf_cap=4))
        np.testing.assert_allclose(float(nb.dist_sq), float(want.dist_sq),
                                   rtol=1e-4)
        np.testing.assert_allclose(float(plus.dist_sq), float(want.dist_sq),
                                   rtol=1e-4)
        reads_nb += int(nb.raw_reads)
        reads_plus += int(plus.raw_reads)
    # Fig. 20: shared-BSF + sorted candidates reads no more raw series.
    assert reads_plus <= reads_nb


def test_knn_matches_oracle(small_index):
    q = _queries(1)[0]
    d, p = exact_knn(small_index, q, k=8)
    zq = isax.znorm(q)
    oracle = np.asarray(isax.euclid_sq(zq, small_index.raw))
    top = np.argsort(oracle)[:8]
    assert np.array_equal(np.asarray(p), top)
    np.testing.assert_allclose(np.asarray(d), oracle[top], rtol=1e-4)


def test_pruning_is_effective(small_index):
    """The index must prune the vast majority of raw reads (the paper's
    economics: ParIS+ reads ~1-5% of the data on random-walk workloads)."""
    reads = []
    for q in _queries(6):
        r = exact_search(small_index, q)
        reads.append(int(r.raw_reads) / small_index.num_series)
    assert np.mean(reads) < 0.25, reads


def test_classifier_agrees_with_brute(small_index):
    labels = RNG.integers(0, 5, small_index.num_series)
    clf = KnnClassifier(small_index, labels, k=3)
    for q in _queries(3):
        assert clf.predict(q) == clf.predict_brute(q)


def test_search_on_tiny_and_degenerate_inputs():
    # constant series (znorm eps path), duplicates, tiny N
    raw = np.concatenate([
        np.ones((4, 64), np.float32),
        RNG.standard_normal((60, 64)).cumsum(axis=1).astype(np.float32),
        np.tile(RNG.standard_normal(64).cumsum().astype(np.float32),
                (3, 1)),
    ])
    idx = build_index(jnp.asarray(raw), segments=8)
    assert all(validate_index(idx).values())
    q = jnp.asarray(raw[66])
    got = exact_search(idx, q, SearchConfig(round_size=16, leaf_cap=8))
    want = brute_force(idx, q)
    np.testing.assert_allclose(float(got.dist_sq), float(want.dist_sq),
                               atol=1e-4)


@pytest.mark.parametrize("tiered", [False, True])
def test_engine_takes_index_as_arguments(small_index, tiered):
    """The engine's program holds no copy of the index: closed-over arrays
    are lowered as constants, which at chip scale (GBs of raw series) no
    host can compile."""
    from repro.core import search
    statics = (4, 4096, 256, True, "topk", "auto", "approx") + (
        (True,) if tiered else ())
    fn = search._engine_for(small_index, statics)
    args = (jnp.zeros((8, 256), jnp.float32),)
    if tiered:
        args += (jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.int32))
    text = fn.func.lower(small_index, *args, **fn.keywords).as_text()
    assert len(text) < small_index.raw.nbytes // 20
