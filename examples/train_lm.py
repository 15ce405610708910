"""End-to-end training driver: data pipeline → model → optimizer →
checkpointing → elastic resume. Kill it mid-run and rerun: it resumes from
the latest checkpoint with bit-identical data order.

    PYTHONPATH=src python examples/train_lm.py --steps 300          # tiny CPU
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The 100m preset is a ~100M-param minicpm-family model (the WSD-schedule
arch); tiny fits a single-core CPU smoke budget.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_model, get_reduced_config
from repro.train.data import SyntheticDataConfig, SyntheticDataset
from repro.train.elastic import ElasticTrainer, Heartbeat
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def build_cfg(preset: str):
    base = get_reduced_config("minicpm-2b")
    if preset == "tiny":
        return base.replace(name="tiny-lm"), SyntheticDataConfig(8, 129)
    if preset == "100m":
        cfg = base.replace(
            name="lm-100m", num_layers=12, d_model=768, num_heads=12,
            kv_heads=12, d_ff=2048, vocab=32_000, residual_scale=0.4)
        return cfg, SyntheticDataConfig(8, 513)
    raise ValueError(preset)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--save-every", type=int, default=50)
    args = ap.parse_args()

    cfg, data_cfg = build_cfg(args.preset)
    model = get_model(cfg)
    opt_cfg = AdamWConfig(peak_lr=3e-3, warmup_steps=20,
                          stable_steps=args.steps - 60, decay_steps=40,
                          schedule="wsd", moment_dtype=jnp.float32)
    print(f"model={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"devices={jax.devices()}")

    trainer = ElasticTrainer(
        ckpt_dir=f"{args.ckpt_dir}_{args.preset}",
        save_every=args.save_every,
        heartbeat=Heartbeat(f"{args.ckpt_dir}_{args.preset}.heartbeat",
                            interval_s=5.0))

    def fresh():
        params, opt = init_train_state(model, cfg, opt_cfg,
                                       jax.random.key(0), dtype=jnp.float32)
        return {"params": params, "opt": opt}

    state, start = trainer.resume_or_init(fresh)
    if start:
        print(f"resumed from checkpoint at step {start}")
    ds = SyntheticDataset(cfg, data_cfg, start_step=start)
    step_fn = jax.jit(make_train_step(model, cfg, opt_cfg, microbatches=2))

    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(ds).items()}
        p, o, metrics = step_fn(state["params"], state["opt"], batch)
        state = {"params": p, "opt": o}
        trainer.maybe_save(step, state)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"{(time.time()-t0):6.1f}s", flush=True)
    trainer.maybe_save(args.steps - 1, state, force=True)
    print("done")


if __name__ == "__main__":
    main()
