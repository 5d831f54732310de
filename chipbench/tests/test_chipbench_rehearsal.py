"""A run of the harness on the CPU, with the chip's device check replaced.

The collection is cut to 4,096 series and the router's batch to 16 rows
so that a run fits a test; everything else is the cell as committed. A
sound run must come out correct with exactly the result keys the driver
reads; runs whose timed path is broken underneath, and the control (the
bfloat16 reference in the program's place), must come out not correct.
"""

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, manifest, reference

ROOT = manifest.ROOT
OPEN = "rw256-n4m.open-mixed-k10"
BUILD = "rw256-n4m.build"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _fake_device(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


@pytest.fixture
def small(monkeypatch):
    """Cut the collection and the batch; keep the compile cache off."""
    import repro.compile_cache
    real = manifest.cell

    def cell(bench, name, *a, **k):
        c = real(bench, name, *a, **k)
        c.config["data"]["num_series"] = 4096
        c.config["router"].update(max_batch=16, min_bucket=16)
        if "rate_qps" in c.traffic:
            c.traffic["rate_qps"] = 40.0
        if "build" in c.traffic:
            c.traffic["build"]["chunk_series"] = 1024
        return c

    monkeypatch.setattr(manifest, "cell", cell)
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "")
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)


def _run(capsys, workload, seconds=2.0, seed=2**31 + 17):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0)
    assert harness.run(args, time.perf_counter(), check=_fake_device) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_open_loop_rehearsal_is_correct_with_the_contract_keys(small, capsys):
    res = _run(capsys, OPEN)
    assert list(res) == KEYS
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 80
    assert set(res["metrics"]) == {"setup_s", "latency_p50_ms",
                                   "latency_p95_ms"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"] in ("s", "ms")
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert res["checks"]["bad_positions"]["value"] == 0


def test_run_refuses_a_backend_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", OPEN, "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "not 'tpu'" in out.stderr
    assert "{" not in out.stdout


def _broken_engine(monkeypatch, fault):
    """Break the engine every router batcher builds, under the harness."""
    from repro.serving import search_batcher
    real = search_batcher.make_batch_engine

    def factory(index, **kw):
        engine = real(index, **kw)

        def broken(queries, tiers=None):
            if fault == "control":
                return reference.knn(index.raw, np.asarray(queries),
                                     kw["k"], low=True)
            d, p = (np.asarray(a) for a in engine(queries))
            half = len(d) // 2
            if fault == "altered":  # one answer changed where produced
                p = p.copy()
                p[0, -1] = (p[0, -1] + 1) % index.num_series
            elif fault == "half":  # half the batch left out
                d = np.concatenate([d[:len(d) - half], d[:half]])
                p = np.concatenate([p[:len(p) - half], p[:half]])
            elif fault == "unchanged":  # the search state never advanced
                d, p = np.full_like(d, np.inf), np.full_like(p, -1)
            return jnp.asarray(d), jnp.asarray(p)

        broken.bucket = engine.bucket
        return broken

    monkeypatch.setattr(search_batcher, "make_batch_engine", factory)


@pytest.mark.parametrize("fault", ["altered", "half", "unchanged",
                                   "control"])
def test_open_loop_breakage_is_not_correct(small, capsys, monkeypatch,
                                           fault):
    _broken_engine(monkeypatch, fault)
    res = _run(capsys, OPEN, seconds=1.0)
    assert res["correct"] is False, (fault, res["checks"])


def _broken_build(monkeypatch, fault):
    from repro.core import build_pipeline
    from repro.core.index import empty_index
    real = build_pipeline.PipelineBuilder.build

    def build(self, source):
        if fault == "half":  # half the series left out
            source = type(source)(source.data[:len(source.data) // 2],
                                  source.chunk_series)
        index, stats = real(self, source)
        if fault == "altered":  # one SAX word changed where produced
            index = type(index)(**{**index.__dict__,
                                   "sax": index.sax.at[0, 0].add(7)})
        elif fault == "unchanged":  # nothing built
            index = empty_index(source.length, self.segments,
                                self.cardinality)
        elif fault == "control":
            ref = reference.index_reference(
                np.asarray(source.data), self.segments, self.cardinality,
                self.refine_bits, low=True)
            index = type(index)(**{**index.__dict__,
                                   "sax": jnp.asarray(ref["sax"][ref["order"]]),
                                   "pos": jnp.asarray(ref["order"], jnp.int32)})
        return index, stats

    monkeypatch.setattr(build_pipeline.PipelineBuilder, "build", build)


def test_build_rehearsal_is_correct(small, capsys):
    res = _run(capsys, BUILD, seconds=1.0)
    assert list(res) == KEYS
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"setup_s", "build_series_per_s"}


@pytest.mark.parametrize("fault", ["altered", "half", "unchanged",
                                   "control"])
def test_build_breakage_is_not_correct(small, capsys, monkeypatch, fault):
    _broken_build(monkeypatch, fault)
    res = _run(capsys, BUILD, seconds=1.0)
    assert res["correct"] is False, (fault, res["checks"])
