"""Seeded Gaussian random walks and query mixes, made on the device.

Steps are N(0, 1), summed along each series: the synthetic generator of the
ParIS+ paper. A collection is one jitted call from a JAX key, built chunk
by chunk so that only the output lives on the device in full; the same key
gives the same collection, so the reference can make it again after the
window.

Query kinds:
  near   a stored series (drawn uniformly) plus N(0, noise_sd^2) per point;
  fresh  a new random walk, not in the collection.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK_ROWS = 65536
KINDS = ("near", "fresh")


@functools.partial(jax.jit, static_argnames=("num", "length"))
def series(key, num: int, length: int) -> jax.Array:
    """(num, length) float32 random walks from ``key``."""
    rows = min(CHUNK_ROWS, num)
    chunks = -(-num // rows)

    def one(i):
        steps = jax.random.normal(jax.random.fold_in(key, i), (rows, length),
                                  jnp.float32)
        return jnp.cumsum(steps, axis=1)

    out = jax.lax.map(one, jnp.arange(chunks)).reshape(chunks * rows, length)
    return out if chunks * rows == num else out[:num]


@jax.jit
def _mix(key, raw, near, noise_sd):
    k_pick, k_noise, k_walk = jax.random.split(key, 3)
    count, length = near.shape[0], raw.shape[1]
    picks = jax.random.randint(k_pick, (count,), 0, raw.shape[0])
    noisy = raw[picks] + noise_sd[:, None] * jax.random.normal(
        k_noise, (count, length), jnp.float32)
    walks = jnp.cumsum(
        jax.random.normal(k_walk, (count, length), jnp.float32), axis=1)
    return jnp.where(near[:, None], noisy, walks)


def kind_counts(mix: list, count: int) -> list:
    """Whole counts per kind from the mix's shares; the last takes the rest.

    Every seed gets the same counts, so the seed changes which series are
    asked and in what order, never how much of each kind.
    """
    counts = [int(np.floor(part["share"] * count)) for part in mix[:-1]]
    return counts + [count - sum(counts)]


def queries(key, raw: jax.Array, mix: list, count: int,
            rng: np.random.Generator) -> np.ndarray:
    """(count, length) host queries of the mix, shuffled by ``rng``."""
    near = np.zeros(count, bool)
    noise = np.zeros(count, np.float32)
    at = 0
    for part, n in zip(mix, kind_counts(mix, count)):
        if part["kind"] not in KINDS:
            raise ValueError(f"unknown query kind {part['kind']!r}")
        near[at:at + n] = part["kind"] == "near"
        noise[at:at + n] = part.get("noise_sd", 0.0)
        at += n
    order = rng.permutation(count)
    out = _mix(key, raw, jnp.asarray(near[order]), jnp.asarray(noise[order]))
    return np.asarray(out)
