"""Kernel micro-benchmarks (interpret-mode wall time is NOT TPU-meaningful —
the derived column reports the workload's arithmetic so the roofline can be
checked; per-kernel correctness lives in tests/test_kernels.py)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters: int = 3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def bench_flash_attention():
    from repro.kernels.flash_attention.ref import attention_ref

    B, S, H, KV, D = 1, 1024, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    ref = jax.jit(attention_ref)
    us = _time(ref, q, k, v)
    flops = 4 * B * H * S * S * D / 2
    return ("flash_attention_ref_1k", us, f"{flops:.3e}flops")


def bench_linear_scan():
    from repro.kernels.rg_lru.ref import linear_scan_ref

    B, S, d = 2, 2048, 256
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.nn.sigmoid(jax.random.normal(k1, (B, S, d)))
    b = jax.random.normal(k2, (B, S, d))
    us = _time(jax.jit(linear_scan_ref), a, b)
    return ("rg_lru_scan_ref_2k", us, f"{B * S * d * 3:.3e}flops")


def bench_pool_scoring():
    """The paper's selection hot loop: vmap scoring vs the fused kernel
    (interpret mode; on TPU the kernel is one launch instead of ns chains)."""
    from repro.core.networks import head_schema
    from repro.core.hfl import pool_errors
    from repro.sharding import spec as S

    ns, R, w = 64, 50, 3
    pool = [S.materialize(head_schema(w), jax.random.PRNGKey(i))
            for i in range(ns)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pool)
    xd = jax.random.normal(jax.random.PRNGKey(9), (R, w))
    y = jax.random.normal(jax.random.PRNGKey(8), (R,))
    us = _time(pool_errors, stacked, xd, y)
    n_mlp = ns * R
    return ("pool_scoring_vmap_ns64", us, f"{n_mlp}mlp_fwd")


def bench_hfl_round():
    """One full federated round (selection + blend) at paper scale."""
    from repro.core.networks import head_schema
    from repro.core.hfl import blend, pool_errors
    from repro.sharding import spec as S

    ns, nf, R, w = 10, 5, 50, 3
    pool = [S.materialize(head_schema(w), jax.random.PRNGKey(i))
            for i in range(ns)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pool)
    heads = jax.tree_util.tree_map(lambda p: p[:nf], stacked)
    xd = jax.random.normal(jax.random.PRNGKey(9), (R, nf, w))
    y = jax.random.normal(jax.random.PRNGKey(8), (R,))

    def round_fn(heads, stacked, xd, y):
        sels = []
        for i in range(nf):
            errs = pool_errors(stacked, xd[:, i], y)
            j = jnp.argmin(errs)
            sels.append(jax.tree_util.tree_map(lambda p: p[j], stacked))
        sel = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *sels)
        return blend(heads, sel, 0.2)

    us = _time(jax.jit(round_fn), heads, stacked, xd, y)
    return ("hfl_federated_round", us, f"ns{ns}_nf{nf}")


def run():
    rows = [bench_flash_attention(), bench_linear_scan(),
            bench_pool_scoring(), bench_hfl_round()]
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
