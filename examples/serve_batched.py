"""Batched serving example: prefill + KV-cache decode with ring-buffered
sliding windows, for any assigned architecture.

  PYTHONPATH=src python examples/serve_batched.py --arch gemma2-9b --smoke
  PYTHONPATH=src python examples/serve_batched.py --arch xlstm-350m --smoke

Uses the reduced smoke config by default (full configs need the TPU pod —
see launch/dryrun.py for the production lowering of serve_step).
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_config
from repro.launch import steps
from repro.models import model as M
from repro.sharding import spec as S


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=48)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    params = S.materialize(M.model_schema(cfg), jax.random.PRNGKey(0))
    B, P, G = args.batch, args.prompt_len, args.gen
    cache_len = P + G

    key = jax.random.PRNGKey(1)
    if cfg.n_codebooks > 1:
        prompts = jax.random.randint(key, (B, cfg.n_codebooks, P), 0,
                                     cfg.vocab_size)
    else:
        prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)

    serve = jax.jit(steps.make_serve_step(cfg, cache_len))
    cache = M.init_cache(cfg, B, cache_len, jnp.bfloat16)

    # prefill by stepping the decode path (production uses the fused prefill
    # kernel path; this keeps the example simple and exercises the cache)
    t0 = time.time()
    logits = None
    for t in range(P):
        logits, cache = serve(params, cache, prompts[..., t:t + 1],
                              jnp.int32(t))
    t_prefill = time.time() - t0

    # batched sampling loop
    tokens = []
    cur = prompts[..., -1:]
    t0 = time.time()
    for t in range(P, P + G):
        logits, cache = serve(params, cache, cur, jnp.int32(t))
        key, sub = jax.random.split(key)
        flat = logits.astype(jnp.float32) / args.temperature
        nxt = jax.random.categorical(sub, flat, axis=-1)   # (B,1) / (B,1,K)
        if cfg.n_codebooks > 1:
            cur = nxt.swapaxes(1, 2)                        # (B,K,1)
        else:
            cur = nxt
        tokens.append(cur)
    t_gen = time.time() - t0
    out = jnp.concatenate(tokens, axis=-1)
    print(f"arch={cfg.name} batch={B} prompt={P} gen={G}")
    print(f"prefill: {t_prefill:.2f}s   decode: {t_gen:.2f}s "
          f"({B * G / t_gen:.1f} tok/s on {jax.default_backend()})")
    print("sampled token matrix shape:", out.shape)
    print("first sequence:", out[0].ravel()[:24].tolist())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
