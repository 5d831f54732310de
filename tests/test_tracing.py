"""The program's own instrumentation: named scopes in the engine's op
metadata, profiler spans in the batcher's flush and the build stages, and
the engine's opt-in work counts.

Spans are read back from a profile taken on the CPU with
``jax.profiler``; their arguments come back as the events' stats.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import (
    PipelineBuilder, SeriesSource, build_index, exact_knn_batch,
)
from repro.core.search import _index_engine, make_batch_engine
from repro.serving import SearchRequestBatcher, ShardedSearchRouter

RNG = np.random.default_rng(5)
ROUND = 256
STAGES = ("read", "convert", "construct", "flush", "finalize", "assemble")


@pytest.fixture(scope="module")
def walk():
    return RNG.standard_normal((4096, 64)).cumsum(axis=1).astype(np.float32)


@pytest.fixture(scope="module")
def index(walk):
    return build_index(jnp.asarray(walk))


@pytest.fixture(scope="module")
def queries(walk):
    # Stored rows plus noise and fresh walks: some rounds, some fallback.
    noisy = walk[[3, 700, 2900]] + RNG.normal(0, 0.3, (3, 64))
    fresh = RNG.standard_normal((2, 64)).cumsum(axis=1)
    return np.concatenate([noisy, fresh]).astype(np.float32)


def _host_spans(trace_dir, prefix):
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    return [(ev.name, {k: v for k, v in ev.stats})
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


def test_engine_op_metadata_carries_the_named_scopes(index, queries):
    statics = (4, ROUND, 256, True, "topk", "ref", "approx")
    text = _index_engine.lower(
        index, jnp.asarray(queries), statics=statics).as_text(
            debug_info=True)
    for scope in ("paris.select", "paris.rdc", "paris.fallback"):
        assert f"/{scope}/" in text, scope
    # Selection's top_k, the loop and the fallback's loop, by path.
    assert "paris.select/top_k" in text
    assert "paris.rdc/while" in text
    assert "paris.fallback/while" in text


@pytest.mark.parametrize("qn", [3, 8])
def test_engine_counts_match_the_plain_call(index, queries, qn):
    qs = np.resize(queries, (qn, queries.shape[1]))
    engine = make_batch_engine(index, k=4, round_size=ROUND, min_bucket=8)
    d, p = engine(qs)
    d2, p2, reads, rounds, narrow = engine(qs, counts=True)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d))
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(p))
    _, _, want_reads, _, want_rounds = exact_knn_batch(
        index, jnp.asarray(qs), k=4, round_size=ROUND, stats=True)
    assert isinstance(reads, np.ndarray) and reads.shape == (qn,)
    np.testing.assert_array_equal(reads, np.asarray(want_reads))
    assert rounds == int(want_rounds)
    assert narrow == 0  # a bucket of 8 rows never narrows
    with pytest.raises(ValueError, match="k-NN mode"):
        make_batch_engine(index, k=None, round_size=ROUND)(qs, counts=True)


def test_router_flush_records_its_spans(index, queries, tmp_path):
    qn = len(queries)
    router = ShardedSearchRouter(index, 1, k=4, max_batch=8, min_bucket=8,
                                 max_wait_ms=1000.0, round_size=ROUND)
    futs = [router.submit(q) for q in queries]
    jax.profiler.start_trace(str(tmp_path))
    try:
        router.drain()
    finally:
        jax.profiler.stop_trace()
    for f in futs:
        f.result(timeout=60)
    spans = _host_spans(str(tmp_path), "paris.flush")
    flush = [s for n, s in spans if n == "paris.flush"]
    resolve = [s for n, s in spans if n == "paris.flush.resolve"]
    assert len(flush) == len(resolve) == 1
    assert flush[0]["qn"] == qn and flush[0]["bucket"] == 8
    assert 0 <= flush[0]["wait_ms_max"] <= flush[0]["wait_ms_sum"]
    _, _, reads, _, rounds = exact_knn_batch(
        index, jnp.asarray(queries), k=4, round_size=ROUND, stats=True)
    assert resolve[0]["reads"] == int(np.sum(reads))  # real rows only
    assert resolve[0]["rounds"] == int(rounds)
    assert resolve[0]["narrow_rounds"] == 0  # a bucket of 8 rows
    assert resolve[0]["rows"] == index.num_series
    assert resolve[0]["qn"] == qn


def test_batcher_calls_an_engine_without_counts_plainly(
        index, queries, tmp_path):
    # A wrapper of the engine that does not pass ``counts`` on (no
    # ``takes_counts``) gets the plain call; its resolve span has no counts.
    engine = make_batch_engine(index, k=4, round_size=ROUND, min_bucket=8)

    def plain(qs, tiers=None):
        return engine(qs, tiers=tiers)

    plain.bucket = engine.bucket
    batcher = SearchRequestBatcher(index, k=4, max_batch=8,
                                   max_wait_ms=1000.0, engine=plain)
    futs = [batcher.submit(q) for q in queries]
    jax.profiler.start_trace(str(tmp_path))
    try:
        batcher.drain()
    finally:
        jax.profiler.stop_trace()
    d, p = (np.asarray(a) for a in engine(queries))
    for i, f in enumerate(futs):
        got_d, got_p = f.result(timeout=60)
        np.testing.assert_array_equal(got_d, d[i])
        np.testing.assert_array_equal(got_p, p[i])
    resolve = [s for n, s in _host_spans(str(tmp_path), "paris.flush")
               if n == "paris.flush.resolve"]
    assert resolve == [{"qn": len(queries), "rows": index.num_series}]


def test_build_records_every_stage_span(walk, tmp_path):
    builder = PipelineBuilder(16, 256, n_workers=2)
    source = SeriesSource.from_array(walk, chunk_series=1024)
    builder.build(source)  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, stats = builder.build(source)
    finally:
        jax.profiler.stop_trace()
    names = [n for n, _ in _host_spans(str(tmp_path), "paris.build.")]
    assert names.count("paris.build.read") == 4
    assert names.count("paris.build.convert") == 4
    assert set(names) == {f"paris.build.{s}" for s in STAGES}
    for stage in STAGES:
        assert getattr(stats, f"{stage}_time") > 0, stage
