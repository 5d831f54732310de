"""Batched search engine: parity with the single-query path, the k-safe
partial-selection k-NN path, the tiny-index regressions, and the
mesh-sharded batched step."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SearchConfig, approx_search, approx_search_batch, brute_force,
    build_index, exact_knn, exact_knn_batch, exact_search,
    exact_search_batch, exact_search_single,
)
from repro.core import isax
from repro.core.search import select_len

RNG = np.random.default_rng(17)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _queries(n, length=256):
    return jnp.asarray(
        RNG.standard_normal((n, length)).cumsum(axis=1), jnp.float32)


# Q=5 deliberately does not divide the kernel's sublane pad block (8).
@pytest.mark.parametrize("sort", [True, False])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_exact_search_batch_matches_single_loop(small_index, sort, impl):
    qs = _queries(5)
    cfg = SearchConfig(round_size=512, sort=sort, impl=impl)
    got = exact_search_batch(small_index, qs, cfg)
    for i in range(qs.shape[0]):
        want = exact_search_single(small_index, qs[i], cfg)
        assert int(got.position[i]) == int(want.position), (sort, impl, i)
        # identical candidate math end-to-end: same floats, not just close
        assert float(got.dist_sq[i]) == float(want.dist_sq), (sort, impl, i)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_exact_knn_batch_matches_single_loop(small_index, impl):
    qs = _queries(3)
    got_d, got_p = exact_knn_batch(
        small_index, qs, k=8, round_size=512, impl=impl)
    for i in range(qs.shape[0]):
        want_d, want_p = exact_knn(
            small_index, qs[i], k=8, round_size=512, impl=impl)
        assert np.array_equal(np.asarray(got_p[i]), np.asarray(want_p))
        np.testing.assert_array_equal(
            np.asarray(got_d[i]), np.asarray(want_d))


def test_batch_wrappers_equal_brute_force(small_index):
    qs = _queries(4)
    res = exact_search_batch(small_index, qs)
    for i in range(4):
        want = brute_force(small_index, qs[i])
        assert int(res.position[i]) == int(want.position)
        np.testing.assert_allclose(
            float(res.dist_sq[i]), float(want.dist_sq), rtol=1e-4)


def test_topk_select_equals_full_sort(small_index):
    """Partial selection + fallback must stay exact vs the full sort."""
    qs = _queries(4)
    # leaf_cap=4 gives a weak initial BSF -> the fallback path is exercised
    topk = exact_search_batch(small_index, qs, SearchConfig(
        round_size=256, leaf_cap=4, select="topk"))
    full = exact_search_batch(small_index, qs, SearchConfig(
        round_size=256, leaf_cap=4, select="sort"))
    np.testing.assert_array_equal(
        np.asarray(topk.position), np.asarray(full.position))
    np.testing.assert_allclose(
        np.asarray(topk.dist_sq), np.asarray(full.dist_sq), rtol=1e-5)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_knn_topk_matches_full_sort(small_index, impl):
    """select="topk" k-NN must be bit-exact with the full-sort path."""
    qs = _queries(4)
    for k in (1, 4, 8):
        td, tp = exact_knn_batch(
            small_index, qs, k=k, round_size=512, impl=impl, select="topk")
        sd, sp = exact_knn_batch(
            small_index, qs, k=k, round_size=512, impl=impl, select="sort")
        assert np.array_equal(np.asarray(tp), np.asarray(sp)), (impl, k)
        np.testing.assert_array_equal(np.asarray(td), np.asarray(sd))
        for i in range(qs.shape[0]):  # k-safety: no duplicated entries
            assert len(set(np.asarray(tp[i]).tolist())) == k, (impl, k, i)


def test_knn_unsorted_scan_matches_topk(small_index):
    """The ADS+-style serial scan (sort=False) returns the same k-NN."""
    qs = _queries(3)
    td, tp = exact_knn_batch(small_index, qs, k=8, round_size=512)
    ud, up = exact_knn_batch(small_index, qs, k=8, round_size=512,
                             sort=False)
    assert np.array_equal(np.asarray(tp), np.asarray(up))
    np.testing.assert_array_equal(np.asarray(td), np.asarray(ud))


def _zero_segment_means(x, segments):
    shaped = x.reshape(x.shape[0], segments, -1)
    return (shaped - shaped.mean(axis=2, keepdims=True)).reshape(x.shape)


def test_knn_topk_fallback_adversarial():
    """Truncated selection insufficient -> the cond-gated fallback restores
    exactness without duplicating re-distanced candidates.

    Every series gets identical (all-zero) segment means, so every lower
    bound within a query ties and the selected top-K list is an arbitrary
    128-candidate prefix; the true neighbors are planted far beyond it.
    The fallback then re-scans the full SAX order — including everything
    the main loop already merged — so parity with select="sort" holds only
    if the dedup masking is airtight.
    """
    n, length, seg, rs = 2048, 64, 8, 32
    rng = np.random.default_rng(123)
    raw = _zero_segment_means(
        rng.standard_normal((n, length)).astype(np.float32), seg)
    raw /= raw.std(axis=1, keepdims=True)  # store znormed (paper layout)
    q = _zero_segment_means(
        rng.standard_normal((1, length)).astype(np.float32), seg)[0]
    qz = np.asarray(isax.znorm(jnp.asarray(q)), np.float32)
    for j in range(8):  # plant the true 8-NN beyond any selected prefix
        delta = _zero_segment_means(
            rng.standard_normal((1, length)).astype(np.float32), seg)[0]
        near = qz + delta * 0.01 * (j + 1)
        raw[1500 + j] = near / near.std()
    idx = build_index(jnp.asarray(raw), segments=seg)
    qs = jnp.asarray(np.stack([q, rng.standard_normal(length)]), jnp.float32)

    sel = select_len(n, rs)
    assert sel < n  # the selection really is truncated
    main_rounds = -(-sel // rs)
    for k in (1, 4, 8):
        td, tp, reads, _, rounds = exact_knn_batch(
            idx, qs, k=k, round_size=rs, select="topk", stats=True)
        sd, sp = exact_knn_batch(idx, qs, k=k, round_size=rs, select="sort")
        assert np.array_equal(np.asarray(tp), np.asarray(sp)), k
        np.testing.assert_array_equal(np.asarray(td), np.asarray(sd))
        # the lax.cond fallback fired: extra rounds ran and raw reads grew
        # past everything the truncated main loop could have fetched
        assert int(rounds) > main_rounds, k
        assert np.all(np.asarray(reads) > 256 + sel), k
        # and it found the planted neighbors outside the selected prefix
        want = np.argsort(
            np.asarray(isax.euclid_sq(isax.znorm(qs[0]), idx.raw)),
            kind="stable")[:k]
        assert np.array_equal(np.asarray(tp[0]), want), k


def test_exact_knn_k_exceeds_index():
    """k > num_series: sentinel (-1, INF) slots, never duplicated entries."""
    rng = np.random.default_rng(21)
    raw = jnp.asarray(
        rng.standard_normal((5, 64)).cumsum(axis=1), jnp.float32)
    idx = build_index(raw, segments=8)
    qs = jnp.asarray(
        rng.standard_normal((3, 64)).cumsum(axis=1), jnp.float32)
    d, p = exact_knn_batch(idx, qs, k=8, round_size=16)
    d, p = np.asarray(d), np.asarray(p)
    assert np.all(p[:, 5:] == -1)
    assert np.all(np.isinf(d[:, 5:]))
    for i in range(3):  # the real slots hold each series exactly once
        assert sorted(p[i, :5].tolist()) == [0, 1, 2, 3, 4]
        assert np.all(np.isfinite(d[i, :5]))
    d1, p1 = exact_knn(idx, qs[0], k=8, round_size=16)
    assert np.array_equal(np.asarray(p1), p[0])
    with pytest.raises(ValueError):
        exact_knn_batch(idx, qs, k=0)


def test_approx_search_tiny_index_regression():
    """leaf_cap > num_series used to flip the window clip's bounds."""
    raw = jnp.asarray(
        RNG.standard_normal((12, 64)).cumsum(axis=1), jnp.float32)
    idx = build_index(raw, segments=8)
    q = raw[3]
    d, p = approx_search(idx, q, leaf_cap=256)  # cap >> N
    # the window now covers the whole index, so this IS the exact answer
    want = brute_force(idx, q)
    assert int(p) == int(want.position)
    np.testing.assert_allclose(float(d), float(want.dist_sq), atol=1e-4)
    ds, ps = approx_search_batch(idx, raw[:5], leaf_cap=256)
    for i in range(5):
        w = brute_force(idx, raw[i])
        assert int(ps[i]) == int(w.position)


def test_approx_search_uses_callers_impl(monkeypatch):
    """The seed scan runs the distance kernel the caller chose."""
    from repro.kernels import ops
    raw = jnp.asarray(
        RNG.standard_normal((40, 64)).cumsum(axis=1), jnp.float32)
    idx = build_index(raw, segments=8)
    seen = []
    real = ops.euclid_sq

    def spy(q, data, *, impl="auto", **kw):
        seen.append(impl)
        return real(q, data, impl=impl, **kw)

    monkeypatch.setattr(ops, "euclid_sq", spy)
    approx_search(idx, raw[0], leaf_cap=8, impl="ref")
    approx_search_batch(idx, raw[:3], leaf_cap=8, impl="pallas")
    exact_knn_batch(idx, raw[:2], k=2, impl="ref", round_size=16)
    assert seen and seen[:2] == ["ref", "pallas"]
    assert set(seen[2:]) == {"ref"}
    with pytest.raises(ValueError, match="unknown impl"):
        approx_search(idx, raw[0], leaf_cap=8, impl="Pallas")


def test_batch_search_tiny_index():
    raw = jnp.asarray(
        RNG.standard_normal((30, 64)).cumsum(axis=1), jnp.float32)
    idx = build_index(raw, segments=8)
    qs = jnp.asarray(
        RNG.standard_normal((3, 64)).cumsum(axis=1), jnp.float32)
    res = exact_search_batch(idx, qs, SearchConfig(round_size=16, leaf_cap=8))
    for i in range(3):
        want = brute_force(idx, qs[i])
        assert int(res.position[i]) == int(want.position)
        np.testing.assert_allclose(
            float(res.dist_sq[i]), float(want.dist_sq), rtol=1e-4)


def test_single_query_wrapper_matches_legacy(small_index):
    q = _queries(1)[0]
    new = exact_search(small_index, q, SearchConfig(round_size=512))
    old = exact_search_single(small_index, q, SearchConfig(round_size=512))
    assert int(new.position) == int(old.position)
    assert float(new.dist_sq) == float(old.dist_sq)


def test_distributed_batch_search_exact():
    out_code = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import isax, index as idx_mod, datagen, distributed as dist
raw = datagen.random_walk(4096, 128, seed=9)
index = idx_mod.build_index(jnp.asarray(raw))
mesh = jax.make_mesh((8,), ("shard",))
sh = dist.index_shardings(mesh, ("shard",))
dindex = dist.dist_index_from(index, sh)
rng = np.random.default_rng(3)
# cold-BSF regime (weak initial bound) + easy random queries
qs = np.concatenate([
    np.stack([np.asarray(raw[i]) + rng.standard_normal(128) * 1.5
              for i in rng.integers(0, 4096, 3)]),
    rng.standard_normal((3, 128)).cumsum(axis=1)]).astype(np.float32)
ok = True
# round_size=128: sel_len == n_local (no fallback compiled);
# round_size=32: sel_len = 128 < n_local=512 -> the exactness-fallback
# branch (cross-shard need bit, kth_bound masking) is exercised too.
for rs in (128, 32):
    step = jax.jit(dist.make_distributed_batch_search(
        mesh, ("shard",), series_length=128, round_size=rs, leaf_cap=4))
    res = step(dindex, jnp.asarray(qs))
    for i in range(len(qs)):
        d = np.asarray(
            isax.euclid_sq(isax.znorm(jnp.asarray(qs[i])), index.raw))
        ok &= abs(float(res.dist_sq[i]) - d.min()) < 1e-3
        ok &= int(res.position[i]) == int(d.argmin())
# Padded-index k-NN: 13 series over 8 shards pads to 16 rows (shard 7 is
# ALL filler); filler rows must never leak into the result lists and
# k > num_series overflow slots must be the (INF, -1) sentinel.
tiny_raw = jnp.asarray(
    rng.standard_normal((13, 128)).cumsum(axis=1), np.float32)
tiny = idx_mod.build_index(tiny_raw)
dtiny = dist.dist_index_from(tiny, sh)
step_t = jax.jit(dist.make_distributed_batch_search(
    mesh, ("shard",), series_length=128, round_size=2, leaf_cap=2, k=14))
res_t = step_t(dtiny, jnp.asarray(qs[:2]))
for i in range(2):
    p = np.asarray(res_t.position[i])
    d = np.asarray(res_t.dist_sq[i])
    ok &= sorted(p[:13].tolist()) == list(range(13))
    ok &= bool(np.all(p[13:] == -1) and np.all(np.isinf(d[13:])))
    ref = np.sort(np.asarray(
        isax.euclid_sq(isax.znorm(jnp.asarray(qs[i])), tiny.raw)))
    ok &= np.allclose(d[:13], ref, rtol=1e-3)
# k-NN (k=4) at rs=32 exercises the per-shard top-list protocol
# (all_gather merge + dedup-masked fallback) end to end.
step4 = jax.jit(dist.make_distributed_batch_search(
    mesh, ("shard",), series_length=128, round_size=32, leaf_cap=4, k=4))
res4 = step4(dindex, jnp.asarray(qs))
for i in range(len(qs)):
    d = np.asarray(
        isax.euclid_sq(isax.znorm(jnp.asarray(qs[i])), index.raw))
    want = np.argsort(d, kind="stable")[:4]
    got = np.asarray(res4.position[i])
    ok &= np.array_equal(got, want)
    ok &= np.allclose(np.asarray(res4.dist_sq[i]), np.sort(d)[:4],
                      rtol=1e-3)
    ok &= len(set(got.tolist())) == 4
print("BATCH_DIST", ok)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", out_code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "BATCH_DIST True" in out.stdout
