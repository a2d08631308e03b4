"""Fused heterogeneous-domain-selection kernel (paper Eq. 7).

The paper flags model selection as the expensive part of HFL ("requires
additional computation (for model selection)") — it evaluates EVERY pool head
(ns = NS x nf models) on the client's last R dense vectors: ns x R tiny MLP
forwards.  A GPU implementation launches ns tiny GEMM chains; on TPU that is
dominated by launch/HBM latency.  This kernel fuses the whole sweep: one grid
cell scores a BP-sized block of pool heads against ALL nf probe batches,
keeping the block's five Table-4 layers (16-256-64-16-1) and the stacked
(nf*R, w) probe rows resident in VMEM.  Inside a cell a loop walks the BP
heads; each head is a chain of plain 2-D (nf*R, d) matmuls.

Layout (chosen so every block is legal for the TPU's (8, 128) tiling):

* the pool axis is always the LEADING dim of a 3-D block, so any BP works;
* biases come in as (ns, 1, d) and the last layer's (ns, 16, 1) weight as
  (ns, 1, 16) — the last layer is a lane reduction, not an N=1 matmul;
* the error tensor is (ns, 1, nf): one (1, nf) row per head, its last two
  block dims equal to the array's; the entry point returns it as (nf, ns).

The per-feature mean masks each feature's R rows with ``where`` before the
sublane sum, so a NaN probe in feature f poisons only column f.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.networks import LRELU_SLOPE
from repro.kernels import resolve_interpret


def _pool_kernel(x_ref, y_ref, w0, b0, w1, b1, w2, b2, w3, b3, w4, b4,
                 o_ref, *, R: int):
    x = x_ref[...].astype(jnp.float32)             # (nf*R, w)
    y = y_ref[...].astype(jnp.float32)             # (nf*R, 1)
    rows, nf = x.shape[0], o_ref.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, nf), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, nf), 1)
    in_feature = (row >= col * R) & (row < (col + 1) * R)

    def dense(h, w_ref, b_ref, p):
        # default precision, like the vmap path's XLA dots, so that the
        # two scorers round alike on the TPU (PERF.md, Findings)
        return (jnp.dot(h, w_ref[p].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
                + b_ref[p].astype(jnp.float32))

    def lrelu(h):
        return jnp.where(h >= 0, h, LRELU_SLOPE * h)

    def head(p, carry):
        h = jax.nn.sigmoid(dense(x, w0, b0, p))
        h = jax.nn.sigmoid(dense(h, w1, b1, p))
        h = lrelu(dense(h, w2, b2, p))
        h = lrelu(dense(h, w3, b3, p))
        out = (jnp.sum(h * w4[p].astype(jnp.float32), axis=1, keepdims=True)
               + b4[p].astype(jnp.float32))            # (nf*R, 1)
        sq = jnp.where(in_feature, (y - out) ** 2, 0.0)  # (nf*R, nf)
        o_ref[p] = (jnp.sum(sq, axis=0, keepdims=True)
                    * (1.0 / R)).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0], head, 0)


def pool_mlp_features_pallas(xd_feats, y, weights, *, block_pool: int = 8,
                             interpret=None):
    """Score the pool against every target feature in one fused sweep.

    xd_feats: (nf, R, w); y: (R,); weights: tuple (w0,b0,...,w4,b4) each with
    leading pool dim ns.  Returns (nf, ns) errors.  ns must be a multiple of
    block_pool — the jitted wrapper in ``ops.py`` owns the padding; this raw
    entry point refuses ragged pools rather than silently mis-tiling."""
    ns = weights[0].shape[0]
    BP = min(block_pool, ns)
    if ns % BP:
        raise ValueError(
            f"pool size ns={ns} is not a multiple of block_pool={BP}; pad "
            f"the pool to a block multiple first (ops.pool_mlp_errors / "
            f"ops.pool_mlp_errors_features do this for you)")
    nf, R, w = xd_feats.shape
    x_rows = xd_feats.reshape(nf * R, w)
    y_rows = jnp.tile(y, nf)[:, None]
    # (ns, d) biases -> (ns, 1, d); (ns, 16, 1) last weight -> (ns, 1, 16)
    weights = tuple(t.reshape(ns, 1, -1) if t.ndim == 2 or k == 8 else t
                    for k, t in enumerate(weights))

    def block(t):
        return pl.BlockSpec((BP,) + t.shape[1:], lambda p: (p, 0, 0))

    full = lambda p: (0, 0)
    out = pl.pallas_call(
        functools.partial(_pool_kernel, R=R),
        grid=(ns // BP,),
        in_specs=[pl.BlockSpec(x_rows.shape, full),
                  pl.BlockSpec(y_rows.shape, full)]
        + [block(t) for t in weights],
        out_specs=pl.BlockSpec((BP, 1, nf), lambda p: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ns, 1, nf), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x_rows, y_rows, *weights)
    return out[:, 0, :].T


def pool_mlp_pallas(xd, y, weights, *, block_pool: int = 8,
                    interpret=None):
    """Single-feature sweep: xd: (R, w); y: (R,); weights as above (ns a
    multiple of block_pool).  Returns (ns,) errors — the nf=1 slice of the
    feature-batched sweep."""
    return pool_mlp_features_pallas(xd[None], y, weights,
                                    block_pool=block_pool,
                                    interpret=interpret)[0]
