"""End-to-end driver (deliverable b): federated pre-training of a ~100M-param
qwen3-family model with the paper's HFL mechanism between 2 clients.

  PYTHONPATH=src python examples/federated_pretrain.py --steps 300

Each client trains on its OWN corpus (different seeds => different data
distributions).  Every R steps, if a client's validation loss has plateaued
(switching mechanism), the blend step runs: each client scores every
published shared subtree on its recent batch (Eq. 7) and alpha-blends the
winner (Eq. 8).  Only the shared subtree (attention + embeddings) moves —
routed experts / recurrence / projectors would stay local (DESIGN.md §4).

On real hardware this runs under the multi-pod mesh with clients on the
`pod` axis (see launch/dryrun.py); on CPU it runs the same code on 1 device.
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import AttnConfig, LayerSpec, ModelConfig, Segment
from repro.core.hfl_llm import make_blend_step, shared_fraction
from repro.checkpoint.checkpoint import CheckpointManager
from repro.data.lm_pipeline import LMPipelineConfig, TokenPipeline
from repro.launch import steps
from repro.sharding import spec as S


def model_100m() -> ModelConfig:
    """~100M-param qwen3-family config (12L x 768, vocab 32k)."""
    return ModelConfig(
        name="qwen3-100m", family="dense",
        vocab_size=32_000, d_model=768, d_ff=2304,
        segments=(Segment((LayerSpec("attn", "mlp"),), 12),),
        attn=AttnConfig(n_heads=12, n_kv_heads=4, head_dim=64,
                        rope_theta=1_000_000.0, qk_norm=True),
        act="silu", tie_embeddings=True,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--R", type=int, default=25, help="federated period")
    ap.add_argument("--alpha", type=float, default=0.2)
    ap.add_argument("--patience", type=int, default=3)
    ap.add_argument("--lr", type=float, default=6e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_federated_ckpt")
    ap.add_argument("--tiny", action="store_true",
                    help="4L/256d model for CI-speed runs")
    args = ap.parse_args()

    cfg = model_100m()
    if args.tiny:
        cfg = dataclasses.replace(
            cfg, d_model=256, d_ff=768, vocab_size=2048,
            segments=(Segment((LayerSpec("attn", "mlp"),), 4),),
            attn=dataclasses.replace(cfg.attn, n_heads=4, n_kv_heads=2,
                                     head_dim=64))
    from repro.models.model import model_schema
    n_params = S.count_params(model_schema(cfg))
    print(f"model: {cfg.name} ({n_params/1e6:.1f}M params), "
          f"shared fraction {shared_fraction(cfg):.2f}")

    C = 2
    opt = steps.default_optimizer(args.lr)
    state = steps.init_state(cfg, opt, jax.random.PRNGKey(0), n_clients=C)
    pipes = [TokenPipeline(LMPipelineConfig(batch=args.batch, seq_len=args.seq,
                                            vocab_size=cfg.vocab_size,
                                            seed=100 + c), cfg)
             for c in range(C)]
    val_batches = [
        {k: jnp.asarray(v) for k, v in pipes[c].batch_at(10_000).items()}
        for c in range(C)]

    train_step = jax.jit(steps.make_hfl_train_step(cfg, opt))
    blend_step = jax.jit(make_blend_step(cfg, alpha=args.alpha))

    from repro.models.model import lm_loss

    @jax.jit
    def val_loss_fn(params_stacked):
        def one(p, b):
            return lm_loss(p, cfg, b)[0]
        return jnp.stack([one(jax.tree_util.tree_map(lambda x: x[c],
                                                     params_stacked),
                              val_batches[c]) for c in range(C)])

    mgr = CheckpointManager(args.ckpt, keep=2)
    val_hist = [[] for _ in range(C)]
    best = [float("inf")] * C
    n_blends = 0
    t0 = time.time()
    recent = None
    for step in range(args.steps):
        batch = {
            k: jnp.stack([jnp.asarray(pipes[c].batch_at(step)[k])
                          for c in range(C)])
            for k in pipes[0].batch_at(step)}
        state, metrics = train_step(state, batch)
        recent = batch
        if (step + 1) % args.R == 0:
            vl = val_loss_fn(state["params"])
            plateaued = []
            for c in range(C):
                val_hist[c].append(float(vl[c]))
                h = val_hist[c]
                p = args.patience
                plat = (len(h) > p and
                        all(v >= min(h[:-p]) for v in h[-p:]))
                plateaued.append(plat)
                best[c] = min(best[c], float(vl[c]))
            if any(plateaued):     # switching mechanism
                state = dict(state)
                state["params"], losses = blend_step(state["params"], recent)
                n_blends += 1
                print(f"  [blend @ {step+1}] losses=\n{losses}")
            losses_s = " ".join(f"c{c}={float(vl[c]):.3f}" for c in range(C))
            print(f"step {step+1:4d}  train={[round(float(x),3) for x in metrics['loss']]} "
                  f"val: {losses_s}  ({(time.time()-t0)/(step+1):.2f}s/step)",
                  flush=True)
            mgr.save_best(float(jnp.mean(vl)), state["params"])
    mgr.save_step(args.steps, state)
    print(f"done: {args.steps} steps, {n_blends} federated blends, "
          f"best val {best}, wall {time.time()-t0:.0f}s, "
          f"ckpt -> {args.ckpt}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
