"""Multi-device tests (subprocess with forced host devices, so the main
pytest process keeps seeing exactly 1 device)."""

import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_search_exact_and_pruning():
    out = _run_subprocess(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import isax, index as idx_mod, datagen, distributed as dist

raw = datagen.random_walk(8192, 128, seed=5)
index = idx_mod.build_index(jnp.asarray(raw))
mesh = jax.make_mesh((8,), ("shard",))
dindex = dist.dist_index_from(index, dist.index_shardings(mesh, ("shard",)))
step = jax.jit(dist.make_distributed_search(mesh, ("shard",),
                                            series_length=128,
                                            round_size=256, leaf_cap=4))
stepnb = jax.jit(dist.make_distributed_search(mesh, ("shard",),
                                              series_length=128,
                                              round_size=256, leaf_cap=4,
                                              shared_bsf=False))
rng = np.random.default_rng(7)
ok = True
reads_s = reads_nb = 0
for t in range(4):
    base = np.asarray(raw[rng.integers(0, len(raw))])
    q = jnp.asarray(base + rng.standard_normal(128) * 1.5, jnp.float32)
    res = step(dindex, q); resnb = stepnb(dindex, q)
    d = np.asarray(isax.euclid_sq(isax.znorm(q), index.raw))
    ok &= abs(float(res.dist_sq) - d.min()) < 1e-3
    ok &= int(res.position) == int(d.argmin())
    ok &= abs(float(resnb.dist_sq) - d.min()) < 1e-3
    reads_s += int(res.raw_reads); reads_nb += int(resnb.raw_reads)
print("EXACT", ok, "READS", reads_s, reads_nb, reads_s <= reads_nb)
""")
    assert "EXACT True" in out
    assert out.strip().endswith("True")


def test_dist_index_from_places_each_shard_on_its_device():
    """raw_sorted is made one device-shard at a time: it holds raw[pos]
    plus +BIG filler rows (N padded to the shard count), each device
    holding only its rows."""
    out = _run_subprocess(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import index as idx_mod, datagen, distributed as dist
raw = datagen.random_walk(1001, 64, seed=3)
index = idx_mod.build_index(jnp.asarray(raw))
mesh = jax.make_mesh((4,), ("shard",))
sh = dist.index_shardings(mesh, ("shard",))
placed = dist.dist_index_from(index, sh)
pos = np.asarray(index.pos)
want_raw = np.concatenate([np.asarray(index.raw)[pos],
                           np.full((3, 64), 1e9, np.float32)])
want_pos = np.concatenate([pos, np.full(3, -1, pos.dtype)])
want_sax = np.concatenate([np.asarray(index.sax),
                           np.zeros((3, index.segments), np.uint8)])
same = (np.array_equal(np.asarray(placed.raw_sorted), want_raw)
        and np.array_equal(np.asarray(placed.pos), want_pos)
        and np.array_equal(np.asarray(placed.sax), want_sax))
rows = sorted(s.data.shape[0] for s in placed.raw_sorted.addressable_shards)
devs = {s.device for s in placed.raw_sorted.addressable_shards}
print("PLACED", same, rows == [251] * 4, len(devs) == 4,
      placed.raw_sorted.sharding == sh.raw_sorted,
      placed.sax.sharding == sh.sax, placed.pos.sharding == sh.pos)
""", devices=4)
    assert "PLACED True True True True True True" in out


def test_distributed_build_matches_local():
    out = _run_subprocess(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import isax, datagen, distributed as dist
raw = datagen.random_walk(4096, 128, seed=6)
mesh = jax.make_mesh((8,), ("shard",))
bstep = jax.jit(dist.make_distributed_build(mesh, ("shard",)))
sax, keys = bstep(jnp.asarray(raw))
exp_sax, _ = isax.convert_to_sax(jnp.asarray(raw))
exp_keys = isax.root_key(exp_sax)
print("MATCH", bool((sax == exp_sax).all()) and
      bool((keys == exp_keys).all()))
""")
    assert "MATCH True" in out


def test_sharded_train_step_runs_and_matches_single_device():
    out = _run_subprocess(r"""
import dataclasses, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import Model
from repro.training import data as dm, optimizer as om, sharding as sm
from repro.training import train_step as ts

cfg = dataclasses.replace(configs.get_smoke_config("internlm2-20b"),
                          dtype="float32")
mesh = jax.make_mesh((2, 2), ("data", "model"))
model = Model(cfg, remat=False)
params = model.init_params(jax.random.PRNGKey(0))
opt = om.init_opt_state(params)
batch = jax.tree.map(jnp.asarray, dm.synthetic_batch(0, 4, 16,
                                                     cfg.vocab_size))
tcfg = ts.TrainConfig(optimizer=om.OptimizerConfig(warmup_steps=0,
                                                   total_steps=10))
# single-device reference
p_ref, _, m_ref = jax.jit(ts.make_train_step(model, tcfg))(params, opt,
                                                           batch)
# sharded
sm.use_logical_rules(mesh, ("data",))
pshard = sm.param_shardings(params, mesh)
oshard = sm.opt_state_shardings(opt, pshard, mesh)
bshard = jax.tree.map(
    lambda a: NamedSharding(mesh, P(("data",), *([None]*(a.ndim-1)))),
    batch)
params_s = jax.tree.map(jax.device_put, params, pshard)
opt_s = jax.tree.map(jax.device_put, opt,
                     om.OptState(oshard.step, oshard.mu, oshard.nu))
batch_s = jax.tree.map(jax.device_put, batch, bshard)
step = jax.jit(ts.make_train_step(model, tcfg),
               in_shardings=(pshard, oshard, bshard))
with mesh:  # layers.logical uses PartitionSpec constraints
    p_sh, _, m_sh = step(params_s, opt_s, batch_s)
err = max(float(jnp.max(jnp.abs(a - b)))
          for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
print("LOSSDIFF", abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-4,
      "PARAMDIFF", err < 1e-4, err)
""")
    assert "LOSSDIFF True PARAMDIFF True" in out


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Save params sharded on a (4,) mesh, restore onto a (2,2) mesh —
    elastic rescale through the checkpoint format."""
    code = r"""
import sys, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.training import checkpoint as ck
d = sys.argv[1] if len(sys.argv) > 1 else None
d = %r
mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4])
w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
w4 = jax.device_put(w, NamedSharding(mesh4, P("data", None)))
ck.save(d, 1, {"w": w4})
mesh22 = jax.make_mesh((2, 2), ("data", "model"))
sh = {"w": NamedSharding(mesh22, P("data", "model"))}
out = ck.restore(d, 1, {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                 shardings=sh)
print("RESHARD", bool((np.asarray(out["w"]) ==
                       np.asarray(w)).all()),
      out["w"].sharding.spec)
"""
    out = _run_subprocess(code % str(tmp_path))
    assert "RESHARD True" in out


def test_moe_local_dispatch_matches_global():
    """moe_dispatch="local" (per-data-shard capacity, grouped-vmap
    dispatch) must equal the global path at dropless capacity."""
    out = _run_subprocess(r"""
import dataclasses, numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models import Model
from repro.training import sharding as sm

mesh = jax.make_mesh((4, 2), ("data", "model"))
base = dataclasses.replace(configs.get_smoke_config("olmoe-1b-7b"),
                           dtype="float32", capacity_factor=64.0)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                            base.vocab_size)
outs = {}
for disp in ("global", "local"):
    cfg = dataclasses.replace(base, moe_dispatch=disp)
    model = Model(cfg, remat=False)
    params = model.init_params(jax.random.PRNGKey(0))
    sm.use_logical_rules(mesh, ("data",))
    pshard = sm.param_shardings(params, mesh)
    params_s = jax.tree.map(jax.device_put, params, pshard)
    tok_s = jax.device_put(tokens, NamedSharding(mesh, P(("data",), None)))
    with mesh:
        logits, aux = jax.jit(model.forward_train)(params_s,
                                                   {"tokens": tok_s})
    outs[disp] = np.asarray(logits)
err = float(np.max(np.abs(outs["global"] - outs["local"])))
print("MOE_LOCAL_OK", err < 1e-3, err)
""")
    assert "MOE_LOCAL_OK True" in out
