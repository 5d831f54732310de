"""An index built and served one shard per device.

``build_sharded_index_on_devices`` builds each file-order share on the
device that holds it; a ``ShardedSearchRouter`` over those shards runs
each shard's engine on that shard's device and merges on the host. The
checks run in one subprocess with four forced host devices (the main
pytest process keeps seeing one device) and print what they found as
one JSON line, which the tests read.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import glob, json, sys, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.profiler import ProfileData
from repro.core import (build_index, build_sharded_index,
                        build_sharded_index_on_devices, exact_knn_batch,
                        make_batch_engine)
from repro.core import isax
from repro.serving import ShardedSearchRouter

devs = jax.devices()
rng = np.random.default_rng(11)
N, LENGTH, ROUND = 4098, 64, 256  # shares of 1025, 1025, 1024, 1024 rows
walk = rng.standard_normal((N, LENGTH)).cumsum(axis=1).astype(np.float32)
whole = build_sharded_index(build_index(jnp.asarray(walk)), 4)
bounds = whole.offsets
shares = [jax.device_put(walk[lo:hi], d)
          for lo, hi, d in zip(bounds[:-1], bounds[1:], devs)]
sharded = build_sharded_index_on_devices(shares)
out = {"offsets": list(sharded.offsets) == list(bounds), "equal": [],
       "placed": [], "engine_placed": []}
for s, (got, want) in enumerate(zip(sharded.shards, whole.shards)):
    out["equal"].append(all(
        np.array_equal(np.asarray(getattr(got, a)), np.asarray(getattr(want, a)))
        for a in ("sax", "pos", "bucket_offsets", "raw")))
    out["placed"].append(all(
        getattr(got, a).devices() == {devs[s]}
        for a in ("sax", "pos", "bucket_offsets", "raw")))
    eng = make_batch_engine(got, k=4, round_size=ROUND, min_bucket=8)
    d, p = eng(walk[:3])
    out["engine_placed"].append(d.devices() == {devs[s]} == p.devices())

# Stored rows plus noise and fresh walks.
near = walk[rng.integers(0, N, 6)] + rng.normal(0, 0.3, (6, LENGTH))
fresh = rng.standard_normal((6, LENGTH)).cumsum(axis=1)
qs = np.concatenate([near, fresh]).astype(np.float32)
single = build_index(jnp.asarray(walk))
zq = np.asarray(isax.znorm(jnp.asarray(qs)))
zx = np.asarray(single.raw)
brute = ((zx[None] - zq[:, None]) ** 2).sum(-1)
order = np.argsort(brute, axis=1, kind="stable")
out["parity"], out["brute"] = {}, {}
for k in (1, 10):
    want_d, want_p = exact_knn_batch(single, jnp.asarray(qs), k=k,
                                     round_size=ROUND)
    router = ShardedSearchRouter(sharded, k=k, max_batch=8, min_bucket=8,
                                 round_size=ROUND)
    trace_dir = tempfile.mkdtemp()
    if k == 10:
        jax.profiler.start_trace(trace_dir)
    try:
        got_d, got_p = router.search_batch(qs)
    finally:
        if k == 10:
            jax.profiler.stop_trace()
    out["parity"][k] = (np.array_equal(got_d, np.asarray(want_d))
                        and np.array_equal(got_p, np.asarray(want_p)))
    # Away from ties (a gap of 1e-4 relative to the next distance).
    ref = np.take_along_axis(brute, order[:, :k + 1], 1)
    clear = np.abs(np.diff(ref, axis=1)) > 1e-4 * ref[:, 1:]
    clear[:, 1:] &= clear[:, :-1].copy()
    out["brute"][k] = bool(np.all((got_p == order[:, :k])[clear[:, :k]]))
    stats = router.stats()
    out["stats"] = {key: stats.get(key) for key in (
        "merges", "shard_wait_ms_sum", "shard_wait_ms_max", "merge_ms_sum")}
    router.stop()
path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
out["spans"] = [[ev.name, {a: v for a, v in ev.stats}]
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name.startswith("paris.route.")]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def found():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_per_device_build_equals_the_cut_of_one_index(found):
    assert found["offsets"]
    assert found["equal"] == [True] * 4


def test_each_shard_sits_on_its_own_device(found):
    assert found["placed"] == [True] * 4


def test_each_shard_engine_answers_on_its_device(found):
    assert found["engine_placed"] == [True] * 4


@pytest.mark.parametrize("k", [1, 10])
def test_router_over_device_shards_matches_one_index(found, k):
    assert found["parity"][str(k)]
    assert found["brute"][str(k)]


def test_router_counts_shard_wait_and_merge(found):
    st = found["stats"]
    assert st["merges"] == 12
    assert 0.0 <= st["shard_wait_ms_max"] <= st["shard_wait_ms_sum"]
    assert st["merge_ms_sum"] > 0.0


def test_router_spans_in_a_profile(found):
    merge = [a for n, a in found["spans"] if n == "paris.route.merge"]
    fanout = [a for n, a in found["spans"] if n == "paris.route.fanout"]
    assert len(merge) == len(fanout) == 12
    assert all(a["shards"] == 4 and a["wait_ms"] >= 0.0 for a in merge)
    assert all(a["shards"] == 4 for a in fanout)
