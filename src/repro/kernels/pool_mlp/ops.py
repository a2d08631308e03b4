"""Jitted wrapper mapping the HeadPool's stacked param dict onto the fused
pool-scoring kernel.  Pool padding to the block size lives HERE, and only
here — the raw kernel entry points refuse ragged pools."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pool_mlp.kernel import (pool_mlp_features_pallas,
                                           pool_mlp_pallas)

_KEYS = ("w0", "b0", "w1", "b1", "w2", "b2", "w3", "b3", "w4", "b4")


def _padded_weights(pool_stacked, BP: int):
    """The stacked Table-4 param dict as the kernel's weight tuple, zero-
    padded so the pool dim is a multiple of the block size (the single home
    of the padding logic)."""
    ns = pool_stacked["w0"].shape[0]
    pad = (-ns) % BP
    weights = []
    for k in _KEYS:
        t = pool_stacked[k]
        if pad:
            t = jnp.concatenate(
                [t, jnp.zeros((pad,) + t.shape[1:], t.dtype)], axis=0)
        weights.append(t)
    return tuple(weights)


@functools.partial(jax.jit, static_argnames=("block_pool", "interpret"))
def pool_mlp_errors(pool_stacked, xd, y, *, block_pool: int = 8,
                    interpret=None):
    """pool_stacked: dict of stacked Table-4 head params (ns leading dim);
    xd: (R, w); y: (R,).  Returns (ns,) mean squared errors (Eq. 7)."""
    ns = pool_stacked["w0"].shape[0]
    BP = min(block_pool, ns)
    errs = pool_mlp_pallas(xd, y, _padded_weights(pool_stacked, BP),
                           block_pool=BP, interpret=interpret)
    # Non-finite scores (NaN probes or poisoned pool rows) pin to +inf so
    # argmin never selects them — identical to the vmap path's pinning,
    # and an exact pass-through for finite errors.
    errs = jnp.where(jnp.isfinite(errs), errs, jnp.inf)
    return errs[:ns]


@functools.partial(jax.jit, static_argnames=("block_pool", "interpret"))
def pool_mlp_errors_features_masked(pool_stacked, xd_feats, y, valid, *,
                                    block_pool: int = 8, interpret=None):
    """The cohort engine's padded union-pool sweep: score a pool whose rows
    include zero-padded INVALID entries (features beyond a client's native
    nf, padded to ``max_nf``) and return their errors as ``+inf``.

    The kernel itself sweeps the dense padded rectangle — padded rows cost
    one extra block at most and keep the grid regular, which is the whole
    point of padding — and the ``valid`` mask (ns,) is applied inside this
    jitted wrapper so invalid rows can never win a selection, even if a
    backend lowers the zero-weight forward to something non-finite.
    xd_feats: (nf, R, w); y: (R,); valid: (ns,) bool.  Returns (nf, ns)."""
    errs = pool_mlp_errors_features(pool_stacked, xd_feats, y,
                                    block_pool=block_pool,
                                    interpret=interpret)
    return jnp.where(valid[None, :], errs, jnp.inf)


def pool_mlp_errors_shard(pool_chunk, xd_feats, y, valid=None, *,
                          block_pool: int = 8, interpret=None):
    """Score one device's contiguous CHUNK of the flattened pool — the
    client-sharded engine's per-device Eq.-7 sweep (each device scores
    ``ns / D`` rows; `federation.merge_sharded_argmin` reduces the
    per-chunk minima).

    The Eq.-7 error of a pool row depends on nothing but that row's params
    and the probe batch, so sweeping a chunk is BITWISE equal to slicing
    the corresponding columns out of the full sweep — the property the
    sharded/replicated parity tests pin.  The chunk is padded to the block
    size independently of the full pool (``_padded_weights`` keys on the
    chunk's own leading dim), which costs at most one extra block.

    pool_chunk: stacked param dict with a ``chunk``-sized leading dim;
    xd_feats: (nf, R, w); y: (R,); valid: optional (chunk,) bool mask of
    real (non-padded-feature) rows — invalid rows come back ``+inf``.
    Returns (nf, chunk)."""
    if valid is None:
        return pool_mlp_errors_features(pool_chunk, xd_feats, y,
                                        block_pool=block_pool,
                                        interpret=interpret)
    return pool_mlp_errors_features_masked(pool_chunk, xd_feats, y, valid,
                                           block_pool=block_pool,
                                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_pool", "interpret"))
def pool_mlp_errors_features(pool_stacked, xd_feats, y, *,
                             block_pool: int = 8, interpret=None):
    """Score the whole pool against EVERY target feature's probe batch.

    xd_feats: (nf, R, w) — one (R, w) dense-vector batch per target feature;
    y: (R,).  Returns (nf, ns).  ONE pallas_call whose grid walks pool
    blocks, each cell scoring its heads against all nf probe batches — nf
    sweeps in a single kernel launch, not a trace-time Python loop of nf
    launches."""
    ns = pool_stacked["w0"].shape[0]
    BP = min(block_pool, ns)
    errs = pool_mlp_features_pallas(xd_feats, y,
                                    _padded_weights(pool_stacked, BP),
                                    block_pool=BP, interpret=interpret)
    # NaN-probe hardening: pin non-finite scores to +inf (NaN propagates
    # through argmin unpredictably across backends; +inf loses to every
    # finite candidate on all of them).  Finite errors pass through exactly.
    errs = jnp.where(jnp.isfinite(errs), errs, jnp.inf)
    return errs[:, :ns]
