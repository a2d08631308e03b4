"""Jitted wrapper: model layout (B, S, H, dh) -> kernel layout (B*H, S, dh)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mlstm.kernel import mlstm_chunkwise_bh


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise(q, k, v, i_pre, f_pre, *, chunk: int = 128,
                    interpret=None):
    """q,k,v: (B, S, H, dh); i_pre,f_pre: (B, S, H).  Returns (B, S, H, dh)."""
    B, S, H, dh = q.shape

    def bh(x):
        return x.swapaxes(1, 2).reshape(B * H, S, -1)

    def bh1(x):
        return x.swapaxes(1, 2).reshape(B * H, S)

    out = mlstm_chunkwise_bh(bh(q), bh(k), bh(v), bh1(i_pre), bh1(f_pre),
                             chunk=chunk, interpret=interpret)
    return out.reshape(B, H, S, dh).swapaxes(1, 2)
