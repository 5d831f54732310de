"""Pallas TPU kernel: fused z-norm + PAA + iSAX symbolization (ConvertToSAX).

The paper's IndexBulkLoading workers call ConvertToSAX once per ingested
series (Alg. 2 line 2); on TPU this is the bulk-load inner loop, fused so a
raw-series tile is read from HBM into VMEM exactly once and both outputs
(uint8 symbols + f32 PAA) are produced in-register.

Symbolization is the branch-free compare-and-sum over the breakpoint table
(symbol = #breakpoints below the PAA value), one (block_b, w) compare per
breakpoint read from SMEM. For card=256 and block_b=256 series of length
256 the working set is 256*256*4B (raw) + small tables ~ 256KiB,
comfortably VMEM-resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paa_isax_kernel(ts_ref, bp_ref, sax_ref, paa_ref, *, segments: int,
                     normalize: bool):
    x = ts_ref[...].astype(jnp.float32)  # (bb, n)
    bb, n = x.shape
    if normalize:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) * (x - mu), axis=-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + 1e-16)
    # PAA as per-segment sums over lane slices: a (bb, n) -> (bb, w, n/w)
    # reshape is a shape cast the chip's compiler refuses.
    seg = n // segments
    lane = jax.lax.broadcasted_iota(jnp.int32, (bb, segments), 1)
    p = jnp.zeros((bb, segments), jnp.float32)
    for s in range(segments):
        col = jnp.sum(x[:, s * seg:(s + 1) * seg], axis=-1, keepdims=True)
        p = jnp.where(lane == s, col, p)
    p = p / seg
    # symbol = #breakpoints below the PAA value; breakpoints are SMEM scalars.
    sym = jax.lax.fori_loop(
        0, bp_ref.shape[0],
        lambda i, acc: acc + (p > bp_ref[i]).astype(jnp.int32),
        jnp.zeros((bb, segments), jnp.int32))
    sax_ref[...] = sym.astype(jnp.uint8)
    paa_ref[...] = p


@functools.partial(
    jax.jit, static_argnames=("segments", "block_b", "interpret", "normalize")
)
def paa_isax_pallas(
    series: jax.Array,
    breakpoints: jax.Array,
    segments: int,
    *,
    block_b: int = 256,
    interpret: bool = True,
    normalize: bool = True,
) -> tuple:
    """(B, n) f32 raw series -> ((B, w) uint8 sax, (B, w) f32 paa)."""
    b, n = series.shape
    if b % block_b:
        raise ValueError(f"B={b} not a multiple of block_b={block_b}")
    grid = (b // block_b,)
    kernel = functools.partial(
        _paa_isax_kernel, segments=segments, normalize=normalize
    )
    sax, paa = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_b, segments), lambda i: (i, 0)),
            pl.BlockSpec((block_b, segments), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, segments), jnp.uint8),
            jax.ShapeDtypeStruct((b, segments), jnp.float32),
        ],
        interpret=interpret,
    )(series, breakpoints.astype(jnp.float32))
    return sax, paa
