"""Batched serving: prefill a prompt batch, then greedy-decode new tokens
through the KV cache (the decode_* dry-run cells exercise exactly this path
at 32k/500k context).

    PYTHONPATH=src python examples/serve_lm.py --arch gemma2-2b --tokens 32
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_model, get_reduced_config
from repro.train.serve_step import greedy_generate


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get_reduced_config(args.arch)
    model = get_model(cfg)
    params = model.init(jax.random.key(0), dtype=jnp.float32)
    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            jax.random.key(2), (args.batch, cfg.vision_tokens, cfg.vision_dim))
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.key(2), (args.batch, cfg.encoder_seq, cfg.d_model))

    max_len = args.prompt_len + args.tokens + 1
    gen = jax.jit(lambda p, b: greedy_generate(
        model, cfg, p, b, steps=args.tokens, max_len=max_len))
    t0 = time.perf_counter()
    out = gen(params, batch)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    total = args.batch * args.tokens
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"generated={args.tokens}/seq")
    print(f"output token ids (first sequence): {out[0].tolist()}")
    print(f"{total} tokens in {dt:.2f}s = {total/dt:.1f} tok/s "
          f"(CPU, includes compile)")


if __name__ == "__main__":
    main()
