"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracle,
swept over shapes and dtypes, exactly as the assignment requires."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import isax
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _series(n_rows, length, dtype=np.float32):
    return jnp.asarray(
        RNG.normal(size=(n_rows, length)).cumsum(axis=1).astype(dtype))


@pytest.mark.parametrize("n_rows", [64, 1000, 4096])
@pytest.mark.parametrize("w", [8, 16])
@pytest.mark.parametrize("card", [64, 256])
def test_lower_bound_pallas_vs_ref(n_rows, w, card):
    length = 256
    series = _series(n_rows, length)
    bp = isax.gaussian_breakpoints(card)
    bpp = isax.padded_breakpoints(card)
    sax, _ = ref.paa_isax(series, w, bp)
    q = isax.znorm(_series(1, length)[0])
    qp = isax.paa(q, w)
    want = ops.lower_bound_sq(qp, sax, bpp, length, impl="ref")
    got = ops.lower_bound_sq(qp, sax, bpp, length, impl="pallas",
                             block_n=256)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-5)
    gotT = ops.lower_bound_sq(qp, sax, bpp, length, impl="pallas",
                              block_n=256, transposed=True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(gotT),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_rows", [64, 1000])
@pytest.mark.parametrize("n_q", [1, 5, 8])  # 5: doesn't divide block_q=8
@pytest.mark.parametrize("w", [8, 16])
def test_lower_bound_batch_pallas_vs_ref(n_rows, n_q, w):
    length = 256
    card = 256
    series = _series(n_rows, length)
    bp = isax.gaussian_breakpoints(card)
    bpp = isax.padded_breakpoints(card)
    sax, _ = ref.paa_isax(series, w, bp)
    qs = isax.znorm(_series(n_q, length))
    qps = isax.paa(qs, w)
    want = ref.lower_bound_sq_batch(qps, sax, bpp, length)
    # the batch oracle must agree row-wise with the single-query oracle
    rows = jnp.stack([
        ops.lower_bound_sq(qps[i], sax, bpp, length, impl="ref")
        for i in range(n_q)])
    np.testing.assert_allclose(np.asarray(want), np.asarray(rows),
                               rtol=1e-5, atol=1e-4)
    got = ops.lower_bound_sq_batch(qps, sax, bpp, length, impl="pallas",
                                   block_n=256)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("parts", [(64,), (170, 130), (60, 1, 300, 7)])
@pytest.mark.parametrize("block", [128, 256])
def test_lower_bound_multi_pallas_vs_ref(parts, block):
    """Fused multi-component sweep: packed components, pad lanes -> +inf."""
    length, w, card = 256, 16, 256
    n = sum(parts)
    series = _series(n, length)
    bp = isax.gaussian_breakpoints(card)
    bpp = isax.padded_breakpoints(card)
    sax, _ = ref.paa_isax(series, w, bp)
    saxn = np.asarray(sax)
    # pack each "component" padded to a block multiple, like
    # core.search.pack_components does for base + runs + deltas
    packed, lens, real = [], [], []
    lo = off = 0
    for m in parts:
        pad = (-m) % block
        packed.append(np.concatenate(
            [saxn[lo: lo + m], np.zeros((pad, w), np.uint8)]))
        bl = np.full(((m + pad) // block,), block, np.int32)
        if pad:
            bl[-1] = block - pad
        lens.append(bl)
        real.extend(range(off, off + m))  # packed rows holding real series
        lo += m
        off += m + pad
    sax_packed = jnp.asarray(np.concatenate(packed))
    block_len = jnp.asarray(np.concatenate(lens))
    real = np.asarray(real)
    qs = isax.znorm(_series(5, length))
    qps = isax.paa(qs, w)
    want = ref.lower_bound_sq_batch(qps, sax, bpp, length)
    got_ref = ops.lower_bound_sq_multi(
        qps, sax_packed, bpp, length, block_len, impl="ref", block_n=block)
    got_pl = ops.lower_bound_sq_multi(
        qps, sax_packed, bpp, length, block_len, impl="pallas",
        block_n=block)
    for got in (got_ref, got_pl):
        got = np.asarray(got)
        np.testing.assert_allclose(got[:, real], np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
        pad_rows = np.setdiff1d(np.arange(got.shape[1]), real)
        assert np.all(np.isinf(got[:, pad_rows]))


@pytest.mark.parametrize("kernel", ["rows", "cols", "batch", "multi"])
def test_lower_bound_pallas_inf_padded_table(kernel):
    """A breakpoint table padded with +-inf gives the same (finite) bounds
    as the +-BIG padding: the one-hot lookup never multiplies inf by 0."""
    length, w, card, block = 256, 16, 256, 128
    series = _series(256, length)
    bpp = isax.padded_breakpoints(card)
    bpp_inf = bpp.at[0].set(-jnp.inf).at[-1].set(jnp.inf)
    sax, _ = ref.paa_isax(series, w, isax.gaussian_breakpoints(card))
    qps = isax.paa(isax.znorm(_series(3, length)), w)
    calls = {
        "rows": lambda t, impl: ops.lower_bound_sq(
            qps[0], sax, t, length, impl=impl, block_n=block),
        "cols": lambda t, impl: ops.lower_bound_sq(
            qps[0], sax, t, length, impl=impl, block_n=block,
            transposed=True),
        "batch": lambda t, impl: ops.lower_bound_sq_batch(
            qps, sax, t, length, impl=impl, block_n=block),
        "multi": lambda t, impl: ops.lower_bound_sq_multi(
            qps, sax, t, length, jnp.full((2,), block, jnp.int32),
            impl=impl, block_n=block),
    }
    want = np.asarray(calls[kernel](bpp, "ref"))
    got = np.asarray(calls[kernel](bpp_inf, "pallas"))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_lower_bound_multi_rejects_bad_table():
    length, w, card = 256, 16, 256
    series = _series(128, length)
    bpp = isax.padded_breakpoints(card)
    sax, _ = ref.paa_isax(series, w, isax.gaussian_breakpoints(card))
    qs = isax.paa(isax.znorm(_series(2, length)), w)
    with pytest.raises(ValueError):  # N not a block multiple
        ops.lower_bound_sq_multi(qs, sax[:100], bpp, length,
                                 jnp.ones((1,), jnp.int32), block_n=128)
    with pytest.raises(ValueError):  # wrong table length
        ops.lower_bound_sq_multi(qs, sax, bpp, length,
                                 jnp.ones((2,), jnp.int32), block_n=128)


def test_lower_bound_sisd_matches():
    series = _series(96, 128)
    bp = isax.gaussian_breakpoints(256)
    bpp = isax.padded_breakpoints(256)
    sax, _ = ref.paa_isax(series, 16, bp)
    q = isax.znorm(_series(1, 128)[0])
    qp = isax.paa(q, 16)
    want = ops.lower_bound_sq(qp, sax, bpp, 128, impl="ref")
    got = ops.lower_bound_sq(qp, sax, bpp, 128, impl="sisd")
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_rows,length,w", [(128, 256, 16), (777, 128, 8),
                                             (256, 512, 32)])
@pytest.mark.parametrize("normalize", [True, False])
def test_paa_isax_pallas_vs_ref(n_rows, length, w, normalize):
    series = _series(n_rows, length)
    bp = isax.gaussian_breakpoints(256)
    sax_r, paa_r = ops.paa_isax(series, bp, w, impl="ref",
                                normalize=normalize)
    sax_p, paa_p = ops.paa_isax(series, bp, w, impl="pallas", block_b=64,
                                normalize=normalize)
    assert np.array_equal(np.asarray(sax_r), np.asarray(sax_p))
    np.testing.assert_allclose(np.asarray(paa_r), np.asarray(paa_p),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("n_rows,length", [(64, 256), (500, 128), (1024, 64)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_euclid_pallas_vs_ref(n_rows, length, dtype):
    data = _series(n_rows, length, np.float32)  # pallas kernels take f32
    q = _series(1, length, np.float32)[0]
    want = ops.euclid_sq(q, data, impl="ref")
    got = ops.euclid_sq(q, data, impl="pallas", block_b=128)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-3)


def test_euclid_min_pallas_vs_ref():
    data = _series(513, 128)
    q = _series(1, 128)[0]
    d_r, i_r = ops.euclid_min(q, data, impl="ref")
    d_p, i_p = ops.euclid_min(q, data, impl="pallas", block_b=128)
    assert int(i_r) == int(i_p)
    np.testing.assert_allclose(float(d_r), float(d_p), rtol=1e-5)


def test_batched_euclid_matches_rowwise():
    data = isax.znorm(_series(200, 128))
    qs = isax.znorm(_series(7, 128))
    mat = isax.batched_euclid_sq(qs, data)
    for i in range(7):
        row = ref.euclid_sq(qs[i], data)
        np.testing.assert_allclose(np.asarray(mat[i]), np.asarray(row),
                                   rtol=2e-3, atol=2e-2)


_OPS = ["lower_bound_sq", "lower_bound_sq_batch", "lower_bound_sq_multi",
        "paa_isax", "euclid_sq", "euclid_min"]


@pytest.mark.parametrize("op,impl", [
    (op, impl) for op in _OPS for impl in ("interpret", "Pallas", "sisd")
    if (op, impl) != ("lower_bound_sq", "sisd")])
def test_unknown_impl_rejected(op, impl):
    """A misspelt impl raises instead of silently running interpret mode;
    "sisd" is the lower-bound-only baseline."""
    length, w = 128, 8
    series = _series(128, length)
    bp = isax.gaussian_breakpoints(256)
    bpp = isax.padded_breakpoints(256)
    sax, paa = ref.paa_isax(series, w, bp)
    call = {
        "lower_bound_sq": lambda: ops.lower_bound_sq(
            paa[0], sax, bpp, length, impl=impl),
        "lower_bound_sq_batch": lambda: ops.lower_bound_sq_batch(
            paa[:2], sax, bpp, length, impl=impl),
        "lower_bound_sq_multi": lambda: ops.lower_bound_sq_multi(
            paa[:2], sax, bpp, length, jnp.full((1,), 128, jnp.int32),
            impl=impl),
        "paa_isax": lambda: ops.paa_isax(series, bp, w, impl=impl),
        "euclid_sq": lambda: ops.euclid_sq(series[0], series, impl=impl),
        "euclid_min": lambda: ops.euclid_min(series[0], series, impl=impl),
    }[op]
    with pytest.raises(ValueError, match="unknown impl"):
        call()
