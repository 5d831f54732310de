"""A run of the four-chip cell on four forced CPU devices, cut small.

The collection is cut to 4,096 series (1,024 a shard) and the router's
batch to 16 rows; everything else is the cell as committed. The runs go
in one subprocess that sets ``XLA_FLAGS`` before JAX is imported, with
the chip's device check replaced as in ``test_chipbench_rehearsal``. A
sound run must come out correct with the contract's keys; a shard whose
offset is shifted by one row, and the bfloat16 reference in each shard
engine's place, must come out not correct.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import manifest
from chipbench.tests.test_chipbench_rehearsal import KEYS

CELL = "rw256-n16m.mesh4"

CHILD = r"""
import argparse, contextlib, io, json, sys, time
import numpy as np
import repro.compile_cache
from chipbench import harness, manifest, reference
from chipbench.drivers import sharded_open_loop
from repro.core import ShardedIndex
from repro.serving import search_batcher

real_cell = manifest.cell

def small(bench, name, *a, **k):
    c = real_cell(bench, name, *a, **k)
    c.config["data"]["num_series"] = 4096
    c.config["router"].update(max_batch=16, min_bucket=16)
    c.traffic["rate_qps"] = 40.0
    return c

manifest.cell = small
repro.compile_cache.enable_compile_cache = lambda: ""
real_build = sharded_open_loop.build_sharded_index_on_devices
real_engine = search_batcher.make_batch_engine

def shifted(*a, **k):  # shard 2 answers as if it began one row later
    ix = real_build(*a, **k)
    off = list(ix.offsets)
    off[2] += 1
    return ShardedIndex(ix.shards, tuple(off))

def control(index, **kw):  # the bfloat16 brute force in each shard's place
    engine = real_engine(index, **kw)
    def low(queries, tiers=None):
        return reference.knn(index.raw, np.asarray(queries), kw["k"],
                             low=True)
    low.bucket = engine.bucket
    return low

def fake_device(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}

for fault in sys.argv[1:]:
    if fault == "shifted":
        sharded_open_loop.build_sharded_index_on_devices = shifted
    if fault == "control":
        search_batcher.make_batch_engine = control
    args = argparse.Namespace(workload=%(cell)r, seed=2**31 + 29,
                              seconds=1.5, trace=0)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert harness.run(args, time.perf_counter(), check=fake_device) == 0
    sharded_open_loop.build_sharded_index_on_devices = real_build
    search_batcher.make_batch_engine = real_engine
    print(json.dumps({"fault": fault,
                      "result": json.loads(buf.getvalue().splitlines()[-1])}))
""" % {"cell": CELL}

FAULTS = ["sound", "shifted", "control"]


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [manifest.ROOT, os.path.join(manifest.ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", CHILD, *FAULTS], env=env,
                         cwd=manifest.ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(s) for s in out.stdout.splitlines()
             if s.startswith('{"fault"')]
    return {line["fault"]: line["result"] for line in lines}


def test_sharded_rehearsal_is_correct_with_the_contract_keys(runs):
    res = runs["sound"]
    assert list(res) == KEYS
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 60
    assert set(res["metrics"]) == {"setup_s", "latency_p50_ms",
                                   "latency_p95_ms"}
    assert res["device"]["count"] == 4
    assert res["checks"]["bad_positions"]["value"] == 0


@pytest.mark.parametrize("fault", ["shifted", "control"])
def test_sharded_breakage_is_not_correct(runs, fault):
    assert runs[fault]["correct"] is False, runs[fault]["checks"]
