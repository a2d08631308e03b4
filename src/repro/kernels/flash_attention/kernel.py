"""Flash attention Pallas-TPU kernel.

Online-softmax tiling: grid = (B, H, S/BQ); each cell streams KV in BKV-sized
VMEM tiles with running (max, sum, acc) carried in registers/VMEM.  BlockSpecs
keep one (BQ, D) query tile + the full (S, D) K/V stripe of the matching KV
head in VMEM; D and BQ/BKV are multiples of the 128-lane MXU tiling for the
real-hardware path (validated here with interpret=True on CPU).

GQA is handled in the BlockSpec index_map (query head h reads KV head h//G),
sliding windows / causality by masking each tile, gemma-style softcap applied
pre-mask.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1.0e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bkv: int, seq: int,
                 window: Optional[int], softcap: float, scale: float):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (BQ, D)
    q_start = qi * bq

    n_kv = seq // bkv

    def body(j, carry):
        acc, m_run, l_run = carry
        k = k_ref[0, 0, pl.ds(j * bkv, bkv), :].astype(jnp.float32)  # (BKV,D)
        v = v_ref[0, 0, pl.ds(j * bkv, bkv), :].astype(jnp.float32)
        s = q @ k.T                                       # (BQ, BKV)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_run - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l_new

    D = q.shape[-1]
    acc0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    # skip tiles that are entirely masked: causal upper bound
    hi = jnp.minimum((q_start + bq + bkv - 1) // bkv, n_kv)
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (q_start - window) // bkv)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, window: Optional[int] = None,
                         logit_softcap: float = 0.0, bq: int = 256,
                         bkv: int = 256, interpret=None):
    """q: (B, H, S, D); k/v: (B, KV, S, D).  Returns (B, H, S, D)."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    G = H // KV
    bq = min(bq, S)
    bkv = min(bkv, S)
    assert S % bq == 0 and S % bkv == 0, (S, bq, bkv)
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_attn_kernel, bq=bq, bkv=bkv, seq=S,
                               window=window, softcap=logit_softcap,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i, _G=G: (b, h // _G, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i, _G=G: (b, h // _G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
