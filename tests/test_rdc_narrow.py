"""Narrow RDC rounds: once at most max(8, Q/8) rows of a batch are live,
the main loop and the exactness scan go on over those rows alone
(``search._narrowing_loop``). Each row's candidates, masks, distances and
merges stay the same, so answers, raw reads, BSF updates, rounds and the
tiers' achieved factors are bit for bit those of the engine that never
narrows; pads past the real-row count are never live.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index, exact_knn_batch, search
from repro.core.index import build_sharded_index

N, L, ROUND, LEAF = 1 << 13, 64, 128, 4  # K = N/16: 4 main rounds of 64
RNG = np.random.default_rng(16)


@pytest.fixture(scope="module")
def raw():
    return RNG.standard_normal((N, L)).cumsum(axis=1).astype(np.float32)


@pytest.fixture(scope="module")
def views(raw):
    index = build_index(jnp.asarray(raw))
    sharded = build_sharded_index(index, 3)
    packed = search.pack_components(
        list(zip(sharded.shards, sharded.offsets)), block=128)
    return {"index": index, "packed": packed}


def _batch(raw, q, rng):
    """Easy near-duplicates and hard fresh walks, interleaved."""
    near = raw[rng.integers(0, N, q // 2)] + rng.normal(0, 0.3, (q // 2, L))
    fresh = rng.standard_normal((q - q // 2, L)).cumsum(axis=1)
    qs = np.empty((q, L), np.float32)
    qs[0::2], qs[1::2] = fresh, near
    return jnp.asarray(qs)


def _view(views, name):
    if name == "index":
        return search._index_view(views["index"], leaf_cap=LEAF,
                                  init="approx")
    p = views["packed"]
    return search._packed_view(
        p.sax, p.gpos, p.block_len, p.raw, block=p.block,
        series_length=p.series_length, segments=p.segments,
        cardinality=p.cardinality, num_series=p.num_series)


def _run(views, name, qs, k, tiers=None, n_real=None):
    # A fresh jit each call: the narrowing constant is read at trace time.
    def fn(qs, *tier):
        kw = dict(eps_factor_sq=tier[0], budget_rounds=tier[1]) if tier else {}
        return search._engine_core(
            _view(views, name), qs, k=k, round_size=ROUND, sort=True,
            select="topk", impl="ref", n_real=n_real, **kw)

    args = search.tier_arrays(tiers) if tiers else ()
    return [np.asarray(x) for x in jax.jit(fn)(qs, *args)]


@pytest.mark.parametrize("view", ["index", "packed"])
@pytest.mark.parametrize("tiered", [False, True])
@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("q", [16, 32, 64])
def test_narrow_rounds_bit_identical_to_wide(raw, views, monkeypatch, q, k,
                                             tiered, view):
    qs = _batch(raw, q, np.random.default_rng(q * 1000 + k))
    tiers = None
    if tiered:
        mix = [search.Tier.exact(), search.Tier.epsilon(0.1),
               search.Tier.budget(2), search.Tier.epsilon(0.5)]
        tiers = [mix[i % len(mix)] for i in range(q)]
    narrow = _run(views, view, qs, k, tiers)
    monkeypatch.setattr(search, "NARROW_MIN_ROWS", 1 << 30)
    wide = _run(views, view, qs, k, tiers)
    assert len(narrow) == len(wide) == (6 if tiered else 5)
    for got, want in zip(narrow, wide):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k", [1, 10, 100])
def test_three_real_rows_in_a_bucket_of_64(raw, views, k):
    """Pads are inert: each real row is answered, read and counted as in a
    batch of just the real rows."""
    qs = _batch(raw, 4, np.random.default_rng(k))[1:]
    engine = search.make_batch_engine(
        views["index"], k=k, round_size=ROUND, leaf_cap=LEAF, impl="ref",
        min_bucket=64)
    assert engine.bucket(3) == 64
    d, p, reads, rounds, _ = engine(qs, counts=True)
    want_d, want_p, want_reads, _, want_rounds = exact_knn_batch(
        views["index"], qs, k=k, round_size=ROUND, leaf_cap=LEAF,
        impl="ref", stats=True)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(want_d))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(want_p))
    np.testing.assert_array_equal(reads, np.asarray(want_reads))
    assert rounds == int(want_rounds)


@pytest.mark.parametrize("bucket", [8, 64])
def test_narrow_rounds_counted(raw, views, bucket):
    """Three fresh walks at k = 100 reach the exactness scan. In a bucket
    of 64 they are its only live rows from the start, so every round runs
    narrow; a bucket of 8 never narrows."""
    qs = jnp.asarray(np.random.default_rng(7).standard_normal(
        (3, L)).cumsum(axis=1).astype(np.float32))
    engine = search.make_batch_engine(
        views["index"], k=100, round_size=ROUND, leaf_cap=LEAF, impl="ref",
        min_bucket=bucket)
    _, _, _, rounds, narrow = engine(qs, counts=True)
    main_rounds = search.select_len(N, ROUND) // ROUND
    assert rounds > main_rounds  # the scan ran
    assert narrow == (rounds if bucket > 8 else 0)
