"""Every Pallas kernel of the search path, and the two-stage candidate
selection, compiles for a TPU v5e chip.

The chip is described, not attached: the TPU compiler installed beside JAX
compiles for a ``v5e:2x2`` topology from a CPU-only host, with
``interpret=False``, at real widths (N = 2^20 series of length 256, w = 16,
cardinality 256, a Q = 64 batch). Interpret-mode parity tests cannot see
what the chip's compiler refuses (unaligned blocks, unsupported gathers and
shape casts); these can. Nothing runs, so they say nothing about answers or
speed.

The topology is described only inside a fixture: the TPU library may be
loaded by one process at a time, so describing it at import would make
test collection differ between workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import tuning
from repro.core.search import select_candidates
from repro.kernels import euclidean, lower_bound, paa_isax

N, W, LENGTH, Q, CARD, ROUND = 1 << 20, 16, 256, 64, 256, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _blocks(kernel):
    """The block shapes ops resolves on a chip with no tuned table row."""
    return dict(tuning.KERNELS[kernel].defaults)


def _lower(name, spec):
    f32, u8, i32 = jnp.float32, jnp.uint8, jnp.int32
    bpp = spec((CARD + 1,), f32)
    if name == "lb_batch":
        return lower_bound.lower_bound_sq_batch_pallas.lower(
            spec((Q, W), f32), spec((W, N), u8), bpp, LENGTH,
            interpret=False, **_blocks("lb_batch"))
    if name == "lb_multi":
        blocks = _blocks("lb_multi")
        return lower_bound.lower_bound_sq_multi_pallas.lower(
            spec((Q, W), f32), spec((W, N), u8), bpp, LENGTH,
            spec((N // blocks["block_n"],), i32), interpret=False, **blocks)
    if name in ("lb_single_rows", "lb_single_cols"):
        cols = name.endswith("cols")
        return lower_bound.lower_bound_sq_pallas.lower(
            spec((W,), f32), spec((W, N) if cols else (N, W), u8), bpp,
            LENGTH, interpret=False, transposed=cols, **_blocks("lb_single"))
    if name == "euclid":
        return euclidean.euclid_sq_pallas.lower(
            spec((LENGTH,), f32), spec((N, LENGTH), f32), interpret=False,
            **_blocks("euclid"))
    if name == "euclid_engine_rounds":
        # The engine's RDC round: one vmapped call over (Q, round, n).
        one = lambda q, x: euclidean.euclid_sq_pallas(  # noqa: E731
            q, x, interpret=False, **_blocks("euclid"))
        return jax.jit(jax.vmap(one)).lower(
            spec((Q, LENGTH), f32), spec((Q, ROUND, LENGTH), f32))
    if name == "euclid_min":
        return euclidean.euclid_min_pallas.lower(
            spec((LENGTH,), f32), spec((N, LENGTH), f32), interpret=False,
            **_blocks("euclid"))
    if name == "paa_isax":
        return paa_isax.paa_isax_pallas.lower(
            spec((N, LENGTH), f32), spec((CARD - 1,), f32), W,
            interpret=False, **_blocks("paa_isax"))
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "lb_batch", "lb_multi", "lb_single_rows", "lb_single_cols", "euclid",
    "euclid_engine_rounds", "euclid_min", "paa_isax",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _lower(name, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_selection_compiles_for_v5e_within_top_k_memory(one_chip):
    """The two-stage candidate selection compiles for the chip at the
    engine's K = N/16, and its temporaries stay below the (bound, index)
    sort of the whole (Q, N) batch that the one ``top_k`` it replaces
    holds (its sorts run 8 queries at a time)."""
    lb = jax.ShapeDtypeStruct((Q, N), jnp.float32, sharding=one_chip)
    compiled = jax.jit(select_candidates, static_argnums=1).lower(
        lb, N // 16).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * Q * N * 4, temp
