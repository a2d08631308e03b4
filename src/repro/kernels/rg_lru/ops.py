"""Jitted wrapper for the RG-LRU chunked-scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rg_lru.kernel import rglru_scan_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_scan(a, b, *, chunk: int = 256, interpret=None):
    """Linear recurrence h_t = a_t h_{t-1} + b_t over (B, S, d) tensors."""
    return rglru_scan_pallas(a, b, chunk=chunk, interpret=interpret)
