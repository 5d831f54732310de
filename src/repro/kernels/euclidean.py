"""Pallas TPU kernel: batched squared Euclidean distance (RDC inner loop).

The paper's RDC workers each compute Dist(rawData, query) for one candidate at
a time (Alg. 11 line 6). The TPU-native version evaluates a whole candidate
tile per grid step: the (block_b, n) raw tile streams HBM->VMEM once and the
VPU reduces (x - q)^2 along the series axis. A fused running-min variant
(``euclid_min``) also keeps the per-tile (min distance, argmin) pair so the
BSF update never leaves the chip — the kernel-level analogue of the shared-BSF
atomic update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _euclid_kernel(q_ref, x_ref, o_ref):
    q = q_ref[...][0][None, :]  # (1, n)
    x = x_ref[...].astype(jnp.float32)
    d = x - q
    o_ref[...] = jnp.sum(d * d, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def euclid_sq_pallas(
    query: jax.Array,
    data: jax.Array,
    *,
    block_b: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """(n,) query x (B, n) data -> (B,) squared distances."""
    b, n = data.shape
    if b % block_b:
        raise ValueError(f"B={b} not a multiple of block_b={block_b}")
    out = pl.pallas_call(
        _euclid_kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        interpret=interpret,
    )(query.astype(jnp.float32)[None, :], data)
    return out.reshape(b)


def _euclid_min_kernel(q_ref, x_ref, dist_ref, idx_ref, *, block_b: int):
    q = q_ref[...]  # (1, n)
    x = x_ref[...].astype(jnp.float32)
    d = x - q
    sq = jnp.sum(d * d, axis=-1, keepdims=True)  # (bb, 1)
    m = jnp.min(sq)
    row = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 0)
    j = jnp.min(jnp.where(sq == m, row, block_b))  # first minimum, as argmin
    # One lane-dense (1, 1, 128) block per tile: a (1, 1) block of a 2-D
    # output is not (8, 128)-aligned, so the chip's compiler refuses it.
    dist_ref[...] = jnp.full(dist_ref.shape, m, jnp.float32)
    idx_ref[...] = jnp.full(idx_ref.shape, pl.program_id(0) * block_b + j,
                            jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def euclid_min_pallas(
    query: jax.Array,
    data: jax.Array,
    *,
    block_b: int = 256,
    interpret: bool = True,
) -> tuple:
    """Fused distance + per-tile min: -> ((B/bb,) dists, (B/bb,) indices).

    Caller finishes with a tiny argmin over the per-tile minima; the raw
    (B,) distance vector never materializes in HBM.
    """
    b, n = data.shape
    if b % block_b:
        raise ValueError(f"B={b} not a multiple of block_b={block_b}")
    tiles = b // block_b
    kernel = functools.partial(_euclid_min_kernel, block_b=block_b)
    dists, idxs = pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles, 1, 128), jnp.float32),
            jax.ShapeDtypeStruct((tiles, 1, 128), jnp.int32),
        ],
        interpret=interpret,
    )(query.astype(jnp.float32)[None, :], data)
    return dists[:, 0, 0], idxs[:, 0, 0]
