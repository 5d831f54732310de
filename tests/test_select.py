"""Candidate selection: the two-stage top-K over strided groups returns
exactly what ``lax.top_k(-lb, K)`` returns (values negated back, row
indices, ties toward the lower index), and its ``lax.cond`` falls back to
the full ``top_k`` where the union of the groups' heads can miss the top K.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_index, datagen, exact_knn_batch, isax, search
from repro.core.search import (
    SELECT_GROUP, SELECT_KEEP, SELECT_TWO_STAGE_MIN, select_candidates,
)

N = SELECT_TWO_STAGE_MIN
RNG = np.random.default_rng(5)


@pytest.fixture
def full_calls(monkeypatch):
    """Count executions of the full ``top_k`` selection at run time."""
    calls = []
    full = search._full_select

    def counted(lb, sel_len):
        jax.debug.callback(lambda: calls.append(1))
        return full(lb, sel_len)

    monkeypatch.setattr(search, "_full_select", counted)
    return calls


def _assert_same_as_top_k(lb, sel_len):
    # A fresh function each call: no trace is shared between tests, so the
    # counting callback of this test's fixture is the one compiled in.
    got_v, got_i = jax.jit(lambda x: select_candidates(x, sel_len))(lb)
    neg, want_i = jax.lax.top_k(-lb, sel_len)
    want_v = np.asarray(-neg)
    assert got_v.dtype == jnp.float32 and got_i.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_v).view(np.int32),
                                  want_v.view(np.int32))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


@pytest.mark.parametrize("n", [N, N + 1000])
def test_random_bounds_equal_top_k(full_calls, n):
    lb = jnp.asarray(RNG.random((8, n), np.float32) * 50.0)
    _assert_same_as_top_k(lb, n // 16)
    assert not full_calls  # the union held the top K: no fallback


@pytest.mark.parametrize("levels", [4, 4096])
def test_ties_across_the_kth_equal_top_k(full_calls, levels):
    """Bounds on a few levels tie across the K-th: 4 levels make every
    group's head end on the K-th value (the fallback runs), 4,096 leave
    ties at the K-th that the two stages resolve by row index."""
    lb = jnp.asarray(RNG.integers(0, levels, (3, N)).astype(np.float32))
    _assert_same_as_top_k(lb, N // 16)
    assert bool(full_calls) == (levels == 4)


def test_inf_pad_rows_equal_top_k(full_calls):
    """+inf pad lanes, as the packed view's padding returns them."""
    lb = RNG.random((8, N), np.float32)
    lb[:, N - 3 * N // 8:] = np.inf  # a tail of pad blocks
    lb[:, 5::97] = np.inf  # scattered pad lanes
    _assert_same_as_top_k(jnp.asarray(lb), N // 16)
    assert not full_calls


def test_inf_at_the_kth_falls_back(full_calls):
    """Fewer than K finite bounds: the K-th is +inf, the check cannot pass,
    and the +inf ties resolve toward the lower index as in top_k."""
    lb = np.full((2, N), np.inf, np.float32)
    lb[:, ::32] = RNG.random((2, N // 32), np.float32)
    _assert_same_as_top_k(jnp.asarray(lb), N // 16)
    assert full_calls


def test_crowded_group_takes_full_sort(full_calls):
    """More than SELECT_KEEP of a query's top K in one strided group: the
    union misses some of them, so the full top_k must select."""
    n = N
    groups = n // SELECT_GROUP
    lb = RNG.random((8, n), np.float32) + 1.0
    crowd = np.arange(0, n, groups)  # every slot of strided group 0
    lb[3, crowd] = RNG.random(crowd.size, np.float32) * 1e-3
    assert crowd.size > SELECT_KEEP
    _assert_same_as_top_k(jnp.asarray(lb), n // 16)
    assert full_calls


def test_full_top_k_runs_only_under_its_scope_in_the_cond():
    """The full top_k of the two-stage path sits under ``paris.select.full``
    inside the cond's branch: a batch that passes the check runs no op
    under that scope, which is what the engagement metric reads."""
    text = jax.jit(lambda x: select_candidates(x, N // 16)).lower(
        jax.ShapeDtypeStruct((8, N), jnp.float32)).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    scoped = {p for p in paths if "paris.select.full" in p}
    assert scoped and all(
        re.search(r"/cond/branch_\d+_fun/paris\.select\.full/", p)
        for p in scoped)
    top_k = {p for p in paths if "top_k" in p}
    assert top_k and top_k <= scoped


def test_small_or_other_lengths_keep_one_top_k(full_calls):
    """Below SELECT_TWO_STAGE_MIN rows, or where K is not N/16, the
    selection is the single top_k (counted once per trace)."""
    lb = jnp.asarray(RNG.random((4, N // 4), np.float32))
    _assert_same_as_top_k(lb, lb.shape[1] // 16)
    lb = jnp.asarray(RNG.random((4, N), np.float32))
    _assert_same_as_top_k(lb, N // 8)
    assert len(full_calls) == 2


@pytest.fixture(scope="module")
def index_2e18():
    raw = datagen.random_walk(N, 64, seed=23)
    return raw, build_index(jnp.asarray(raw), 16, 256)


def test_engine_at_two_stage_size_equals_brute_force(index_2e18):
    """exact_knn_batch at N = 2^18 (round_size 1,024, so K = N/16 and the
    two stages select) against a float64 brute force over the z-normalised
    collection: positions away from ties, and distances."""
    raw, index = index_2e18
    assert search.select_len(N, 1024) == N // 16
    near = raw[RNG.integers(0, N, 4)] + RNG.normal(0, 0.5, (4, 64))
    fresh = RNG.standard_normal((4, 64)).cumsum(axis=1)
    qs = np.concatenate([near, fresh]).astype(np.float32)
    k = 10
    got_d, got_p = exact_knn_batch(index, jnp.asarray(qs), k=k,
                                   round_size=1024, impl="ref")
    xs = np.asarray(isax.znorm(jnp.asarray(raw)), np.float64)
    zq = np.asarray(isax.znorm(jnp.asarray(qs)), np.float64)
    for i in range(len(qs)):
        d = ((xs - zq[i]) ** 2).sum(axis=1)
        order = np.argsort(d, kind="stable")[:k + 1]
        want_d = d[order]
        np.testing.assert_allclose(np.asarray(got_d[i]), want_d[:k],
                                   rtol=1e-4)
        apart = np.diff(want_d) > 1e-6 * want_d[1:]
        for j in range(k):
            if (j == 0 or apart[j - 1]) and apart[j]:
                assert int(got_p[i, j]) == int(order[j]), (i, j)


def test_engine_reads_and_rounds_unchanged(index_2e18, monkeypatch):
    """The engine with the two stages gives the single top_k engine's
    answers, raw reads, BSF updates and rounds, bit for bit (leaf_cap=4:
    a weak seed, so the rounds run on and the exactness scan may too)."""
    raw, index = index_2e18
    qs = jnp.asarray(np.concatenate([
        raw[RNG.integers(0, N, 3)] + RNG.normal(0, 1.0, (3, 64)),
        RNG.standard_normal((5, 64)).cumsum(axis=1)]).astype(np.float32))

    def run():
        engine = jax.jit(lambda ix, q: search._engine_core(
            search._index_view(ix, leaf_cap=4, init="approx"), q, k=5,
            round_size=1024, sort=True, select="topk", impl="ref"))
        return [np.asarray(x) for x in engine(index, qs)]

    two_stage = run()
    monkeypatch.setattr(search, "SELECT_TWO_STAGE_MIN", 1 << 40)
    for got, want in zip(two_stage, run()):
        np.testing.assert_array_equal(got, want)
