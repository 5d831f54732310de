"""The plain reference that decides ``correct``, and the comparisons.

Written apart from the code under test and importing none of it: the
z-normalisation, the PAA, the iSAX breakpoints and the leaf-order key are
the paper's definitions, restated here; nothing the program made (index
arrays, tables, breakpoints) is read.

k-NN: a chunked brute force on the chip, ``sum((x - q)**2)`` over
z-normalised rows in float32, with no matmul form to round it.

Index: SAX words from the segment means and the N(0, 1) quantiles, and the
leaf order (a stable sort on the bit-plane key of the top ``refine_bits``
bits of every symbol), against which the built index is compared.

``low=True`` gives the control: the same computation in bfloat16, the
precision below the configuration's float32 (inputs and differences
rounded to bfloat16, sums in float32). It has to come out as not correct.
"""

from __future__ import annotations

import functools
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

TIE_TOL = 1e-6  # relative gap below which two reference distances tie
DIST_FLOOR = 1e-6  # distances are compared relative to at least this
NEAR_BP = 1e-4  # a segment mean this close to a breakpoint may round across
CHUNK_ROWS = 1 << 18
QUERY_BLOCK = 8


def znorm(x: jax.Array) -> jax.Array:
    """Subtract the mean, divide by the population deviation (+1e-8)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.sqrt(jnp.mean((x - mu) ** 2, axis=-1, keepdims=True))
    return (x - mu) / (sd + 1e-8)


def _round(x: jax.Array, low: bool) -> jax.Array:
    """``x`` rounded to bfloat16 when ``low``. ``reduce_precision`` is a
    rounding the compiler keeps: on the TPU a cast to bfloat16 and back
    is folded away as excess precision."""
    if not low:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("k", "low"))
def _knn_step(top_d, top_p, qz, chunk, base, *, k: int, low: bool):
    x = _round(znorm(chunk), low)
    q = _round(qz, low)
    blocks = q.reshape(-1, QUERY_BLOCK, q.shape[1])
    d = jax.lax.map(
        lambda qb: jnp.sum(_round(x[None] - qb[:, None], low) ** 2, axis=-1),
        blocks)
    d = d.reshape(q.shape[0], x.shape[0])
    p = base + jnp.arange(x.shape[0], dtype=jnp.int32)
    all_d = jnp.concatenate([top_d, d], axis=1)
    all_p = jnp.concatenate([top_p, jnp.broadcast_to(p, d.shape)], axis=1)
    neg, sel = jax.lax.top_k(-all_d, k)
    return -neg, jnp.take_along_axis(all_p, sel, axis=1)


def knn(raw: jax.Array, queries: np.ndarray, k: int,
        low: bool = False) -> tuple:
    """(Q, k) ascending squared distances and positions over ``raw``.

    ``raw`` is the (N, n) collection on the device as the harness made it
    (not z-normalised); rows go through in chunks of ``CHUNK_ROWS``.
    """
    nq = len(queries)
    pad = (-nq) % QUERY_BLOCK
    q = np.concatenate([queries, np.repeat(queries[:1], pad, 0)])
    qz = znorm(jnp.asarray(q, jnp.float32))
    top_d = jnp.full((len(q), k), jnp.inf, jnp.float32)
    top_p = jnp.full((len(q), k), -1, jnp.int32)
    for s in range(0, raw.shape[0], CHUNK_ROWS):
        top_d, top_p = _knn_step(top_d, top_p, qz, raw[s:s + CHUNK_ROWS],
                                 jnp.int32(s), k=k, low=low)
    return np.asarray(top_d)[:nq], np.asarray(top_p)[:nq]


def compare_knn(got_d, got_p, ref_d, ref_p) -> dict:
    """Positions equal except at reference ties; worst distance error.

    ``ref_d``/``ref_p`` hold one neighbour more than the answers, so a tie
    between the k-th and the (k+1)-th reference distance is seen.
    Returns ``bad_positions`` (answers at the wrong position, not at a
    tie) and ``dist_rel_err`` (the largest relative distance error).
    """
    got_d = np.asarray(got_d, np.float64)
    ref_d = np.asarray(ref_d, np.float64)
    k = got_d.shape[1]
    rel = np.abs(got_d - ref_d[:, :k]) / np.maximum(ref_d[:, :k], DIST_FLOOR)
    gap = np.abs(np.diff(ref_d, axis=1)) <= TIE_TOL * np.maximum(
        ref_d[:, 1:], DIST_FLOOR)
    tie = gap[:, :k].copy()  # tied with the next
    tie[:, 1:] |= gap[:, :k - 1]  # tied with the previous
    moved = np.asarray(got_p) != np.asarray(ref_p)[:, :k]
    return {"bad_positions": int(np.sum(moved & ~tie)),
            "dist_rel_err": float(np.nan_to_num(rel, nan=np.inf).max())}


def breakpoints(cardinality: int) -> np.ndarray:
    """The ``cardinality - 1`` interior N(0, 1) quantiles, ascending."""
    nd = NormalDist()
    return np.array([nd.inv_cdf(i / cardinality)
                     for i in range(1, cardinality)], np.float32)


@functools.partial(jax.jit, static_argnames=("segments", "low"))
def _sax_step(chunk, bp, order_bp, *, segments: int, low: bool):
    x = _round(znorm(chunk), low)
    paa = jnp.mean(x.reshape(x.shape[0], segments, -1), axis=-1)
    sym = jnp.sum(paa[..., None] > bp, axis=-1).astype(jnp.uint8)
    near = jnp.min(jnp.abs(paa[..., None] - bp), axis=-1) < NEAR_BP
    near_order = jnp.any(
        jnp.min(jnp.abs(paa[..., None] - order_bp), axis=-1) < NEAR_BP,
        axis=-1)
    return sym, near, near_order


def leaf_key(sax: np.ndarray, refine_bits: int, cardinality: int):
    """uint64 key: bit plane 0 (the MSB of every segment) first, segment 0
    the most significant bit within a plane."""
    bits = (cardinality - 1).bit_length()
    w = sax.shape[1]
    s = sax.astype(np.uint64)
    weights = np.uint64(1) << np.arange(w - 1, -1, -1, dtype=np.uint64)
    key = np.zeros(len(sax), np.uint64)
    for plane in range(refine_bits):
        bit = (s >> np.uint64(bits - 1 - plane)) & np.uint64(1)
        key = (key << np.uint64(w)) | (bit * weights).sum(1, dtype=np.uint64)
    return key


def index_reference(raw: np.ndarray, segments: int, cardinality: int,
                    refine_bits: int, low: bool = False) -> dict:
    """SAX words (file order), segments near a breakpoint, and leaf order.

    ``near`` marks segment means within ``NEAR_BP`` of any breakpoint,
    where float32 rounding may put a symbol on either side; ``near_order``
    marks rows with a segment that close to a breakpoint where the key's
    top ``refine_bits`` bits change, whose place in the order may move.
    """
    bp = breakpoints(cardinality)
    step = cardinality >> refine_bits
    order_bp = bp[step - 1::step]
    sax, near, near_order = [], [], []
    for s in range(0, raw.shape[0], CHUNK_ROWS):
        out = _sax_step(jnp.asarray(raw[s:s + CHUNK_ROWS]), jnp.asarray(bp),
                        jnp.asarray(order_bp), segments=segments, low=low)
        for acc, a in zip((sax, near, near_order), out):
            acc.append(np.asarray(a))
    sax = np.concatenate(sax)
    order = np.argsort(leaf_key(sax, refine_bits, cardinality), kind="stable")
    return {"sax": sax, "near": np.concatenate(near),
            "near_order": np.concatenate(near_order), "order": order}


def compare_index(sax_sorted, pos, ref: dict) -> dict:
    """The built index against the reference.

    ``pos_missing``: file positions not held exactly once. ``sax_bad``:
    symbols that differ from the reference, except by one region where
    the segment mean is near a breakpoint.
    ``order_bad``: rows, among those not near an order breakpoint, whose
    place in the leaf order differs from the reference's.
    """
    sax_sorted, pos = np.asarray(sax_sorted), np.asarray(pos)
    n = len(ref["sax"])
    valid = (pos >= 0) & (pos < n)
    seen = np.bincount(pos[valid], minlength=n)
    once = seen == 1
    sax_file = np.zeros_like(ref["sax"])
    sax_file[pos[valid]] = sax_sorted[valid]
    step = np.abs(sax_file.astype(np.int16) - ref["sax"].astype(np.int16))
    wrong = (step > 1) | ((step == 1) & ~ref["near"])
    got = pos[valid]
    got = got[~ref["near_order"][got]]
    want = ref["order"][~ref["near_order"][ref["order"]]]
    m = min(len(got), len(want))
    order_bad = int(np.sum(got[:m] != want[:m])) + abs(len(got) - len(want))
    return {"pos_missing": int(np.sum(~once)),
            "sax_bad": int(np.sum(wrong[once])),
            "order_bad": order_bad}
