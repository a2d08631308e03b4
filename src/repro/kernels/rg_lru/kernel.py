"""RG-LRU linear-recurrence Pallas-TPU kernel (chunked scan).

TPU adaptation of the Griffin recurrence: the grid walks (batch, time-chunk)
with the time axis SEQUENTIAL per core; the carried hidden state lives in a
VMEM scratch buffer that persists across grid steps (standard TPU Pallas
carry idiom).  Within a chunk the recurrence h_t = a_t h_{t-1} + b_t is
solved by an associative scan over the VMEM-resident (CHUNK, d) tile —
log-depth on the VPU instead of a CUDA warp-scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _rglru_kernel(a_ref, b_ref, o_ref, h_scratch):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    a = a_ref[0].astype(jnp.float32)           # (CHUNK, d)
    b = b_ref[0].astype(jnp.float32)
    h0 = h_scratch[0]                          # (d,)
    # fold carry into the first step: b'_0 = a_0 h0 + b_0
    b = b.at[0].set(a[0] * h0 + b[0])

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=0)
    o_ref[0] = h.astype(o_ref.dtype)
    h_scratch[0] = h[-1]


def rglru_scan_pallas(a, b, *, chunk: int = 256, interpret=None):
    """a, b: (B, S, d).  Returns h: (B, S, d) with h_t = a_t h_{t-1} + b_t."""
    B, S, d = a.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    return pl.pallas_call(
        _rglru_kernel,
        grid=(B, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda bi, ci: (bi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a, b)
