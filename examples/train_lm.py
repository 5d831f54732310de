"""End-to-end LM training driver: train a ~100M-class granite-family model
for a few hundred steps on learnable synthetic data, with checkpointing and
resume. (Default size is CPU-scaled; --full-100m selects the 100M config.)

    PYTHONPATH=src python examples/train_lm.py --steps 300
"""

import argparse
import time

import jax

from repro.configs.base import ModelConfig
from repro.models import Model
from repro.training import data as data_mod
from repro.training import elastic as el
from repro.training import optimizer as opt_mod
from repro.training import train_step as ts_mod


def model_config(full: bool) -> ModelConfig:
    if full:  # ~100M params
        return ModelConfig(
            name="lm-100m", family="dense", num_layers=12, d_model=768,
            num_heads=12, num_kv_heads=4, head_dim=64, d_ff=2048,
            vocab_size=8192, mlp_type="swiglu")
    return ModelConfig(  # ~22M params: a few minutes of CPU
        name="lm-22m", family="dense", num_layers=6, d_model=384,
        num_heads=6, num_kv_heads=2, head_dim=64, d_ff=1024,
        vocab_size=4096, mlp_type="swiglu")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full-100m", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/paris_train_lm")
    args = ap.parse_args()

    cfg = model_config(args.full_100m)
    model = Model(cfg, remat=False)
    tcfg = ts_mod.TrainConfig(optimizer=opt_mod.OptimizerConfig(
        learning_rate=1e-3, warmup_steps=20, total_steps=args.steps))
    step_fn = jax.jit(ts_mod.make_train_step(model, tcfg),
                      donate_argnums=(0, 1))

    ecfg = el.ElasticConfig(ckpt_dir=args.ckpt_dir,
                            steps_between_checkpoints=100)
    policy = el.CheckpointPolicy(ecfg)

    def init_state():
        p = model.init_params(jax.random.PRNGKey(0))
        return (p, opt_mod.init_opt_state(p))

    (params, opt_state), start = el.resume_or_init(ecfg, init_state)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {n / 1e6:.1f}M params, resuming at step {start}")

    loader = data_mod.PrefetchingLoader(
        data_mod.bigram_batch, args.batch, args.seq, cfg.vocab_size,
        start_step=start)
    t0, toks = time.time(), 0
    first_loss = None
    try:
        for _ in range(start, args.steps):
            step_no, batch = loader.__next__()
            params, opt_state, m = step_fn(params, opt_state, batch)
            toks += args.batch * args.seq
            if first_loss is None:
                first_loss = float(m["loss"])
            if (step_no + 1) % 20 == 0:
                print(f"step {step_no + 1:4d} loss={float(m['loss']):.4f} "
                      f"tok/s={toks / (time.time() - t0):.0f}", flush=True)
            policy.maybe_save(step_no + 1, (params, opt_state))
    finally:
        loader.close()
    policy.finalize(args.steps, (params, opt_state))
    print(f"loss: {first_loss:.3f} -> {float(m['loss']):.3f} "
          f"({args.steps} steps, {time.time() - t0:.0f}s)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
