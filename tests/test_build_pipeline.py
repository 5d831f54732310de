"""PipelineBuilder parity + robustness: byte-identical modes, the
mem_limit < chunk multi-epoch edge, the empty source, and caller-owned
workdir cleanup on failure."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    BuildStats, PipelineBuilder, SeriesSource, build_index,
)
from repro.core.build_pipeline import merge_runs
from repro.core.index import validate_index

N, LENGTH, CHUNK = 3000, 64, 512
RNG = np.random.default_rng(21)


@pytest.fixture(scope="module")
def raw():
    return RNG.standard_normal((N, LENGTH)).cumsum(axis=1).astype(np.float32)


@pytest.fixture(scope="module")
def want(raw):
    return build_index(jnp.asarray(raw))


def _assert_byte_identical(index, want):
    np.testing.assert_array_equal(np.asarray(index.sax), np.asarray(want.sax))
    np.testing.assert_array_equal(np.asarray(index.pos), np.asarray(want.pos))
    np.testing.assert_array_equal(
        np.asarray(index.bucket_offsets), np.asarray(want.bucket_offsets))
    np.testing.assert_array_equal(np.asarray(index.raw), np.asarray(want.raw))


@pytest.mark.parametrize("mode", ["paris+", "paris", "serial"])
def test_modes_byte_identical_to_build_index(raw, want, mode):
    src = SeriesSource.from_array(raw, chunk_series=CHUNK)
    index, stats = PipelineBuilder(mode=mode, n_workers=3).build(src)
    _assert_byte_identical(index, want)
    assert stats.epochs == 1 and stats.chunks == src.num_chunks
    assert all(validate_index(index).values())


@pytest.mark.parametrize("mode", ["paris+", "paris", "serial"])
def test_mem_limit_below_chunk_multi_epoch_parity(raw, want, mode):
    # mem_limit smaller than one chunk: EVERY chunk closes an epoch — the
    # maximal multi-epoch stress of the finalize merge.
    src = SeriesSource.from_array(raw, chunk_series=CHUNK)
    index, stats = PipelineBuilder(
        mode=mode, n_workers=3, mem_limit_series=CHUNK // 2).build(src)
    assert stats.epochs == src.num_chunks > 1
    _assert_byte_identical(index, want)


@pytest.mark.parametrize("mode", ["paris+", "paris", "serial"])
def test_empty_source_returns_empty_index(mode):
    src = SeriesSource.from_array(np.zeros((0, LENGTH), np.float32))
    index, stats = PipelineBuilder(mode=mode).build(src)
    assert index.num_series == 0
    assert index.series_length == LENGTH
    assert stats.epochs == 0 and stats.chunks == 0
    assert all(validate_index(index).values())


class _FailingSource(SeriesSource):
    """Raises on a configurable chunk read (mid-build I/O failure)."""

    fail_at = 3

    def read(self, i):
        if i >= self.fail_at:
            raise IOError("disk died")
        return super().read(i)


def test_failed_build_cleans_partial_epoch_dirs(raw, tmp_path):
    workdir = tmp_path / "build"
    workdir.mkdir()
    (workdir / "keep.txt").write_text("caller-owned")
    src = _FailingSource(raw, chunk_series=CHUNK)
    builder = PipelineBuilder(
        mode="paris+", n_workers=2, mem_limit_series=CHUNK // 2,
        workdir=str(workdir))
    with pytest.raises(IOError):
        builder.build(src)
    # epochs WERE flushed before the failure, and all were cleaned up
    assert not [d for d in os.listdir(workdir) if d.startswith("e")]
    assert (workdir / "keep.txt").exists()  # caller files untouched


def test_successful_build_keeps_caller_workdir_epochs(raw, tmp_path):
    workdir = tmp_path / "build"
    src = SeriesSource.from_array(raw, chunk_series=CHUNK)
    index, stats = PipelineBuilder(
        mode="paris+", mem_limit_series=CHUNK, workdir=str(workdir)).build(src)
    assert index.num_series == N
    dirs = sorted(d for d in os.listdir(workdir) if d.startswith("e"))
    assert len(dirs) == stats.epochs > 1


def test_overlap_efficiency_robust_to_zero_total_time():
    assert BuildStats().overlap_efficiency == 1.0  # no work, vacuously hidden
    mid = BuildStats(convert_time=1.0)  # queried mid-build: no total yet
    assert mid.overlap_efficiency == 0.0
    done = BuildStats(convert_time=1.0, total_time=1.2, read_time=1.1)
    assert 0.0 <= done.overlap_efficiency <= 1.0


def test_overlap_efficiency_leaves_out_assembly():
    # Assembling the index (raw upload, z-normalisation, device arrays)
    # is neither convert nor construct work: it is not exposed CPU time.
    base = BuildStats(convert_time=2.0, read_time=3.0, total_time=3.5)
    assembled = BuildStats(convert_time=2.0, read_time=3.0, total_time=5.5,
                           assemble_time=2.0)
    assert base.overlap_efficiency == assembled.overlap_efficiency == 0.75


def test_merge_runs_requires_runs():
    with pytest.raises(ValueError):
        merge_runs([])
