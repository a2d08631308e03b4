"""Jitted public wrapper: model layout (B, S, H, D) -> kernel layout."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("window", "logit_softcap",
                                             "interpret"))
def flash_attention(q, k, v, *, window: Optional[int] = None,
                    logit_softcap: float = 0.0, interpret=None):
    """q: (B, S, H, D), k/v: (B, S, KV, D) — the model-side layout."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = flash_attention_bhsd(qt, kt, vt, window=window,
                               logit_softcap=logit_softcap,
                               interpret=interpret)
    return out.swapaxes(1, 2)
