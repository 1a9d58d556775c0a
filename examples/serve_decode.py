"""Serving example: batched greedy decoding with a ring-buffer KV cache,
using the same serve_step the decode dry-runs lower.

Demonstrates all three cache families: GQA KV cache (qwen3), compressed
MLA cache (deepseek-lite), and constant-size SSM state (mamba2).

  PYTHONPATH=src python examples/serve_decode.py [--arch qwen3-0.6b]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs.registry import get_config
from repro.launch.steps import make_serve_step
from repro.models.model import init_cache, init_model


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=64)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    B = args.batch
    cache = init_cache(cfg, B, args.cache_len)
    serve = jax.jit(make_serve_step(cfg))

    key = jax.random.PRNGKey(7)
    prompt = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab)
    extra = {}
    if cfg.encoder is not None:
        extra["frames"] = jax.random.normal(
            key, (B, cfg.encoder.n_frames, cfg.modality.feat_dim))

    # prefill by stepping through prompt tokens (serve_step is one-token)
    tok = prompt[:, 0]
    t0 = time.time()
    for t in range(args.prompt_len):
        batch = {"token": prompt[:, t], "t": jnp.full((B,), t, jnp.int32),
                 **extra}
        tok, cache = serve(params, cache, batch)
    generated = [tok]
    for t in range(args.prompt_len, args.prompt_len + args.gen_len - 1):
        batch = {"token": tok, "t": jnp.full((B,), t, jnp.int32), **extra}
        tok, cache = serve(params, cache, batch)
        generated.append(tok)
    gen = jnp.stack(generated, axis=1)
    dt = time.time() - t0
    n_tok = B * (args.prompt_len + args.gen_len - 1)
    print(f"arch={args.arch} (reduced)  batch={B}")
    print(f"generated {gen.shape[1]} tokens/request in {dt:.2f}s "
          f"({n_tok/dt:.0f} tok/s on CPU)")
    for b in range(min(B, 2)):
        print(f"  request {b}: {list(map(int, gen[b, :16]))} ...")
    cache_kinds = {"ssm": "constant SSM state", "hybrid": "RG-LRU + ring KV",
                   "moe": "compressed MLA c_kv"}
    print(f"cache family: "
          f"{cache_kinds.get(cfg.family, 'ring-buffer KV')}")


if __name__ == "__main__":
    main()
