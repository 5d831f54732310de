"""Dispatch wrappers for the Pallas kernels.

Every op takes ``impl``:

  * ``"auto"``   — compiled Pallas on TPU, jnp reference elsewhere (CPU/GPU);
  * ``"pallas"`` — Pallas in interpret mode off-TPU (correctness validation);
  * ``"ref"``    — pure-jnp oracle (also the vectorized "SIMD analogue" used
                   by the CPU benchmarks);
  * ``"sisd"``   — scalar-loop formulation (Table-1 baseline; lower bound only).

Any other string raises ``ValueError``: a typo must not quietly run the
slow interpret path, nor hide which device did the work.

Wrappers own the ugly parts: padding to block multiples and un-padding
results, so kernels can assume exact tiling.

Block shapes resolve through the committed tuning table
(``repro.core.tuning`` / ``TUNING.json``): an explicit block kwarg always
wins, a ``None`` falls through to the tuned entry for (kernel, backend,
dtype, Q-bucket, N-bucket), and a table miss uses the registry default —
today's hand-picked value. Resolution happens at trace time (shapes are
concrete there) and never changes answers: block shapes only re-tile the
same per-element math.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import tuning
from repro.kernels import euclidean as _euclid
from repro.kernels import lower_bound as _lb
from repro.kernels import paa_isax as _pi
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_IMPLS = ("auto", "pallas", "ref")


def _use_ref(impl: str, extra: tuple = ()) -> bool:
    """Validate ``impl``; True when the jnp reference answers the call."""
    if impl not in _IMPLS + extra:
        raise ValueError(
            f"unknown impl {impl!r}; expected one of {_IMPLS + extra}")
    return impl == "ref" or (impl == "auto" and not _on_tpu())


def _pad_rows(x: jax.Array, mult: int, fill=0):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate(
            [x, jnp.full((pad, *x.shape[1:]), fill, dtype=x.dtype)], axis=0
        )
    return x, n


def lower_bound_sq(
    query_paa: jax.Array,
    sax: jax.Array,
    bp_padded: jax.Array,
    series_length: int,
    *,
    impl: str = "auto",
    block_n: Optional[int] = None,
    transposed: bool = False,
) -> jax.Array:
    """(w,) PAA x (N, w) sax -> (N,) squared lower bounds.

    ``block_n=None`` resolves through the tuning table (registry default
    1024 on a miss); an explicit value always wins.
    """
    if _use_ref(impl, ("sisd",)):
        return _ref.lower_bound_sq(query_paa, sax, bp_padded, series_length)
    if impl == "sisd":
        return _ref.lower_bound_sq_sisd(query_paa, sax, bp_padded, series_length)
    block_n = tuning.resolve_blocks(
        "lb_single", q=1, n=sax.shape[0], block_n=block_n)["block_n"]
    interpret = not _on_tpu()
    if transposed:
        pad = (-sax.shape[0]) % block_n
        saxT = sax.T
        if pad:
            saxT = jnp.pad(saxT, ((0, 0), (0, pad)))
        out = _lb.lower_bound_sq_pallas(
            query_paa, saxT, bp_padded, series_length,
            block_n=block_n, interpret=interpret, transposed=True,
        )
        return out[: sax.shape[0]]
    sax_p, n = _pad_rows(sax, block_n, 0)
    out = _lb.lower_bound_sq_pallas(
        query_paa, sax_p, bp_padded, series_length,
        block_n=block_n, interpret=interpret, transposed=False,
    )
    return out[:n]


def lower_bound_sq_batch(
    query_paa: jax.Array,
    sax: jax.Array,
    bp_padded: jax.Array,
    series_length: int,
    *,
    impl: str = "auto",
    block_q: Optional[int] = None,
    block_n: Optional[int] = None,
) -> jax.Array:
    """(Q, w) PAA batch x (N, w) sax -> (Q, N) squared lower bounds.

    The fused batch form of :func:`lower_bound_sq`: one grid pass streams the
    SAX array through VMEM once for the whole query batch. Padding of both Q
    (to the sublane block) and N (to the lane block) lives here.
    ``block_q``/``block_n`` left as ``None`` resolve through the tuning
    table (registry defaults 8/1024 on a miss); explicit values win.
    """
    if _use_ref(impl):
        return _ref.lower_bound_sq_batch(
            query_paa, sax, bp_padded, series_length
        )
    n_q, n = query_paa.shape[0], sax.shape[0]
    blocks = tuning.resolve_blocks(
        "lb_batch", q=n_q, n=n, block_q=block_q, block_n=block_n)
    block_q, block_n = blocks["block_q"], blocks["block_n"]
    q_p, _ = _pad_rows(query_paa, block_q, 0.0)
    sax_t = sax.T
    pad_n = (-n) % block_n
    if pad_n:
        sax_t = jnp.pad(sax_t, ((0, 0), (0, pad_n)))
    out = _lb.lower_bound_sq_batch_pallas(
        q_p, sax_t, bp_padded, series_length,
        block_q=block_q, block_n=block_n, interpret=not _on_tpu(),
    )
    return out[:n_q, :n]


def lower_bound_sq_multi(
    query_paa: jax.Array,
    sax: jax.Array,
    bp_padded: jax.Array,
    series_length: int,
    block_len: jax.Array,
    *,
    impl: str = "auto",
    block_q: Optional[int] = None,
    block_n: int = 128,
) -> jax.Array:
    """(Q, w) PAA x (N_pad, w) PACKED multi-component sax -> (Q, N_pad).

    The fused form of one lower-bound pass over a whole live store (base +
    runs + delta shards) instead of one engine call per component: the
    caller packs each component's leaf-sorted SAX rows padded to a
    ``block_n`` multiple (``core.search.pack_components`` — the block
    alignment lets an append extend the buffer without moving earlier
    components' rows) and ``block_len[j]`` counts the valid rows of block
    ``j``. Pad rows are +inf in the result, so downstream candidate
    selection can never pick one.

    ``block_n`` here is the *layout* the caller packed with (it must
    match the buffer; pack-time resolves it through the tuning table —
    see :func:`core.search.pack_components`); only ``block_q`` is a free
    call-time knob and resolves through the table when ``None``.
    """
    n = sax.shape[0]
    if n % block_n:
        raise ValueError(f"packed N={n} not a multiple of block_n={block_n}")
    if block_len.shape[0] != n // block_n:
        raise ValueError(
            f"block_len has {block_len.shape[0]} entries for "
            f"{n // block_n} blocks")
    if _use_ref(impl):
        valid = (
            jnp.arange(block_n, dtype=jnp.int32)[None, :]
            < jnp.asarray(block_len, jnp.int32)[:, None]
        ).reshape(-1)
        return _ref.lower_bound_sq_batch_multi(
            query_paa, sax, bp_padded, series_length, valid
        )
    n_q = query_paa.shape[0]
    block_q = tuning.resolve_blocks(
        "lb_multi", q=n_q, n=n, block_q=block_q)["block_q"]
    q_p, _ = _pad_rows(query_paa, block_q, 0.0)
    out = _lb.lower_bound_sq_multi_pallas(
        q_p, sax.T, bp_padded, series_length,
        jnp.asarray(block_len, jnp.int32),
        block_q=block_q, block_n=block_n, interpret=not _on_tpu(),
    )
    return out[:n_q]


def paa_isax(
    series: jax.Array,
    breakpoints: jax.Array,
    segments: int,
    *,
    impl: str = "auto",
    block_b: Optional[int] = None,
    normalize: bool = True,
) -> tuple:
    """(B, n) raw -> ((B, w) uint8 sax, (B, w) f32 paa).

    ``block_b=None`` resolves through the tuning table (default 256).
    """
    if _use_ref(impl):
        return _ref.paa_isax(series, segments, breakpoints, normalize)
    block_b = tuning.resolve_blocks(
        "paa_isax", q=1, n=series.shape[0], block_b=block_b)["block_b"]
    series_p, b = _pad_rows(series, block_b, 1.0)
    sax, paa = _pi.paa_isax_pallas(
        series_p, breakpoints, segments,
        block_b=block_b, interpret=not _on_tpu(), normalize=normalize,
    )
    return sax[:b], paa[:b]


def euclid_sq(
    query: jax.Array,
    data: jax.Array,
    *,
    impl: str = "auto",
    block_b: Optional[int] = None,
) -> jax.Array:
    """(n,) query x (B, n) data -> (B,) squared distances.

    ``block_b=None`` resolves through the tuning table (default 256).
    """
    if _use_ref(impl):
        return _ref.euclid_sq(query, data)
    block_b = tuning.resolve_blocks(
        "euclid", q=1, n=data.shape[0], block_b=block_b)["block_b"]
    data_p, b = _pad_rows(data, block_b, 0.0)
    out = _euclid.euclid_sq_pallas(
        query, data_p, block_b=block_b, interpret=not _on_tpu()
    )
    return out[:b]


def euclid_min(
    query: jax.Array,
    data: jax.Array,
    *,
    impl: str = "auto",
    block_b: Optional[int] = None,
) -> tuple:
    """(n,) x (B, n) -> (min squared distance, argmin index)."""
    if _use_ref(impl):
        d = _ref.euclid_sq(query, data)
        i = jnp.argmin(d)
        return d[i], i.astype(jnp.int32)
    block_b = tuning.resolve_blocks(
        "euclid", q=1, n=data.shape[0], block_b=block_b)["block_b"]
    data_p, b = _pad_rows(data, block_b, jnp.inf)
    dists, idxs = _euclid.euclid_min_pallas(
        query, data_p, block_b=block_b, interpret=not _on_tpu()
    )
    j = jnp.argmin(dists)
    return dists[j], idxs[j]
