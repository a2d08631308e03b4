"""Chunkwise-parallel mLSTM Pallas-TPU kernel [arXiv:2405.04517].

Grid = (B*H, S/CHUNK); the chunk axis is sequential per core, carrying the
stabilized (C, n, m) inter-chunk state in VMEM scratch.  Within a chunk the
recurrence is evaluated in closed form: an intra-chunk gated attention matrix
(CHUNK x CHUNK, MXU matmuls) plus a rank-`dh` contribution from the carried
matrix memory — the TPU-native replacement for a CUDA scan over time.

Math (matches the sequential oracle exactly):
    lf = logsigmoid(f~),  b_t = cumsum(lf)  (inclusive, within chunk)
    m_t   = max(m_prev + b_t, max_{s<=t}(b_t - b_s + li_s))
    w_ts  = exp(b_t - b_s + li_s - m_t)          (s <= t, else 0)
    coef_t = exp(m_prev + b_t - m_t)
    num_t = coef_t (q_t C_prev) + sum_s w_ts (q_t.k_s) v_s
    den_t = max(|coef_t (q_t.n_prev) + sum_s w_ts (q_t.k_s)|, exp(-m_t))
    h_t   = num_t / den_t
    chunk-end state update with the same weights at t = L.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG = -1.0e30


def _mlstm_kernel(q_ref, k_ref, v_ref, ig_ref, fg_ref, o_ref,
                  C_scr, n_scr, m_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        C_scr[...] = jnp.zeros_like(C_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG)

    q = q_ref[0].astype(jnp.float32)            # (L, dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    li = ig_ref[0].astype(jnp.float32)          # (L,)
    lf = jax.nn.log_sigmoid(fg_ref[0].astype(jnp.float32))
    L = chunk

    b = jnp.cumsum(lf)                           # (L,) inclusive
    m_prev = m_scr[0, 0]
    C_prev = C_scr[...]                          # (dh, dh)
    n_prev = n_scr[0]                            # (dh,)

    # intra-chunk log-weights D[t, s] = b_t - b_s + li_s   (s <= t)
    Dmat = b[:, None] - b[None, :] + li[None, :]
    tpos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    spos = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    Dmat = jnp.where(spos <= tpos, Dmat, NEG)

    m_intra = jnp.max(Dmat, axis=1)              # (L,)
    m_t = jnp.maximum(m_prev + b, m_intra)
    w = jnp.exp(Dmat - m_t[:, None])             # (L, L)
    coef = jnp.exp(m_prev + b - m_t)             # (L,)

    s_qk = q @ k.T                               # (L, L)
    inter_num = coef[:, None] * (q @ C_prev)     # (L, dh)
    num = inter_num + (w * s_qk) @ v
    den = coef * (q @ n_prev) + jnp.sum(w * s_qk, axis=1)
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
    o_ref[0] = (num / den[:, None]).astype(o_ref.dtype)

    # ---- inter-chunk state update (evaluate the same closed form at t=L) --
    bL = b[-1]
    m_next = jnp.maximum(m_prev + bL, jnp.max(bL - b + li))
    wL = jnp.exp(bL - b + li - m_next)           # (L,)
    decay = jnp.exp(m_prev + bL - m_next)
    C_scr[...] = decay * C_prev + (k * wL[:, None]).T @ v
    n_scr[0] = decay * n_prev + jnp.sum(k * wL[:, None], axis=0)
    m_scr[0, 0] = m_next


def mlstm_chunkwise_bh(q, k, v, i_pre, f_pre, *, chunk: int = 128,
                       interpret=None):
    """q,k,v: (BH, S, dh); gates: (BH, S).  Returns h: (BH, S, dh)."""
    BH, S, dh = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dh), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v, i_pre, f_pre)
