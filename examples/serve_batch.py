"""Batched serving example: prefill a batch of multimodal prompts through a
small unified model (LoRA merged), then decode tokens with the KV cache —
the same serve_step the decode-shape dry-runs lower at 512 chips.

  PYTHONPATH=src python examples/serve_batch.py [--arch hymba-1.5b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import ccl as ccl_lib
from repro.launch.mesh import use_compile_cache
from repro.launch.serve import generate
from repro.models.model import build_model


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    help="assigned arch id (reduced variant is served)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    bundle = build_model(cfg)
    params = ccl_lib.init_unified(jax.random.key(0), bundle)
    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    extra = {}
    if cfg.frontend:
        extra["frontend_embeds"] = jax.random.normal(
            jax.random.key(2),
            (args.batch, cfg.frontend_tokens, cfg.frontend_dim),
            jnp.float32) * 0.3

    # cold start: first call pays jit compilation of prefill + decode step
    t0 = time.time()
    out = generate(bundle, params, prompts, max_new=args.new_tokens,
                   temperature=0.8, batch_extra=extra)
    jax.block_until_ready(out)
    cold_s = time.time() - t0

    # steady state: identical shapes, compiled path only — this is the
    # number that scales to production (compile amortizes over the fleet)
    t0 = time.time()
    out = generate(bundle, params, prompts, max_new=args.new_tokens,
                   temperature=0.8, key=jax.random.key(3),
                   batch_extra=extra)
    jax.block_until_ready(out)
    steady_s = time.time() - t0

    n_tok = args.batch * args.new_tokens
    print(f"arch={cfg.name} served batch={args.batch} "
          f"prompt={args.prompt_len} new={args.new_tokens}")
    print(f"  cold start (incl. compile): {cold_s:.2f}s")
    print(f"  steady state: {steady_s:.2f}s ({n_tok / steady_s:.1f} tok/s)")
    print("sample token ids:", out[0].tolist())


if __name__ == "__main__":
    main()
