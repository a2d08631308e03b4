"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

Why this exists (EXPERIMENTS.md §Perf, iter A3): the pjit/gather formulation
in moe.py builds capacity buffers by GLOBAL token index; with tokens sharded
over `data` and experts over `data x model`, GSPMD can only satisfy the
gather by all-gathering the full (T, d) token matrix to every device
(~30 GB/layer fwd at DeepSeek scale, x3 with remat+bwd).  The communication-
minimal schedule — each token travels to the (at most k) devices owning its
experts and back — is an all-to-all, which GSPMD cannot infer from a gather.
This module expresses it explicitly with shard_map:

  1. slice the model-replicated activations by `model` index (free): each of
     the D x M devices now owns T_loc = T/(D*M) unique tokens;
  2. route locally; sort token assignments by OWNER DEVICE; fill per-
     destination capacity buckets (N_ep, C, d);
  3. all_to_all over the joint ("data","model") expert-parallel axis
     (~T_loc * k * d bytes per device per direction, the information-
     theoretic minimum for capacity-based MoE);
  4. locally sub-dispatch to the E/(D*M) resident experts, run the gated
     FFN, all_to_all the outputs back, combine with router weights;
  5. reassemble the sequence with an S-axis all-gather over `model`.

Experts whose count does not divide the joint axis fall back to EP over
`model` only (olmoe: 64 experts / 16 model shards); if that fails too the
caller uses the gather path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models.layers.common import activation
from repro.models.layers.moe import _router


def ep_axes_for(cfg: MoEConfig, mesh) -> Optional[Tuple[str, ...]]:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    joint = sizes.get("data", 1) * sizes.get("model", 1)
    if cfg.n_experts % joint == 0:
        return ("data", "model")
    if cfg.n_experts % sizes.get("model", 1) == 0:
        return ("model",)
    return None


def _fill_buckets(ids, payload_tok, n_buckets, cap):
    """Sort-based bucketing: ids (N,) in [0, n_buckets); returns
    (bucket_tok (n_buckets, cap) int32 indices-with-sentinel, keep mask)."""
    N = ids.shape[0]
    order = jnp.argsort(ids)
    s_ids = ids[order]
    s_tok = payload_tok[order]
    sizes = jnp.bincount(ids, length=n_buckets)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(sizes)[:-1].astype(jnp.int32)])
    pos = jnp.arange(N, dtype=jnp.int32) - offs[s_ids]
    keep = pos < cap
    pos = jnp.where(keep, pos, cap)
    buf = jnp.full((n_buckets, cap), -1, jnp.int32).at[s_ids, pos].set(
        jnp.where(keep, s_tok, -1), mode="drop")
    return buf


def moe_apply_a2a(params, x, cfg: MoEConfig, act: str, mesh,
                  ep_axes: Tuple[str, ...]):
    """x: (B, S, d) sharded P('data', None, None), model-replicated.
    Returns (out with the same sharding, aux scalar)."""
    B, S, d = x.shape
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    D, Mx = sizes.get("data", 1), sizes.get("model", 1)
    E, K = cfg.n_experts, cfg.top_k
    n_ep = 1
    for a in ep_axes:
        n_ep *= sizes.get(a, 1)
    e_per_dev = E // n_ep
    f = activation(act)

    # per-device unique token count after the model-axis sequence slice
    S_loc = S // Mx
    T_loc = (B // D) * S_loc
    # per-destination capacity (paper-standard capacity-factor semantics)
    cap = max(cfg.min_capacity,
              int(cfg.capacity_factor * T_loc * K / n_ep))

    def body(x_loc, router_w, wg, wu, wd):
        # x_loc: (B/D, S, d) — model-replicated; take this shard's S-slice
        m_idx = jax.lax.axis_index("model")
        xs = jax.lax.dynamic_slice_in_dim(x_loc, m_idx * S_loc, S_loc, axis=1)
        xt = xs.reshape(T_loc, d)

        scores, weights, ids = _router({"router": router_w}, xt, cfg)
        # load-balance statistics: average the per-expert vectors globally
        # BEFORE the product so the aux loss equals the global formulation
        probs_mean = jnp.mean(scores, axis=0)
        counts = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32),
                         axis=(0, 1))
        frac = counts / jnp.maximum(1.0, T_loc * K)
        probs_mean = jax.lax.pmean(jax.lax.pmean(probs_mean, "data"), "model")
        frac = jax.lax.pmean(jax.lax.pmean(frac, "data"), "model")
        aux = cfg.aux_loss_weight * E * jnp.sum(frac * probs_mean)

        flat_ids = ids.reshape(-1)                       # (T_loc*K,)
        flat_tok = jnp.repeat(jnp.arange(T_loc, dtype=jnp.int32), K)
        dst = flat_ids // e_per_dev                      # owner device
        buf_tok = _fill_buckets(dst, flat_tok, n_ep, cap)   # (n_ep, cap)
        # local expert id of each slot (for the resident sub-dispatch)
        buf_assign = jnp.full((n_ep, cap), -1, jnp.int32)
        order = jnp.argsort(dst)
        s_dst, s_eid = dst[order], flat_ids[order]
        sizes_b = jnp.bincount(dst, length=n_ep)
        offs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                jnp.cumsum(sizes_b)[:-1].astype(jnp.int32)])
        pos = jnp.arange(dst.shape[0], dtype=jnp.int32) - offs[s_dst]
        keep = pos < cap
        pos = jnp.where(keep, pos, cap)
        buf_assign = buf_assign.at[s_dst, pos].set(
            jnp.where(keep, s_eid % e_per_dev, -1), mode="drop")

        xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
        send = xpad[jnp.where(buf_tok >= 0, buf_tok, T_loc)]  # (n_ep, cap, d)

        def a2a(v):
            # all_to_all over the (possibly joint) expert-parallel axis;
            # tiled: split dim 0 (size n_ep) across the group, re-concat
            return jax.lax.all_to_all(v, ep_axes, split_axis=0,
                                      concat_axis=0, tiled=True)

        recv = a2a(send)                                   # (n_ep, cap, d)
        recv_assign = a2a(buf_assign)                      # (n_ep, cap)

        # resident sub-dispatch: group received rows by local expert
        flat_recv = recv.reshape(n_ep * cap, d)
        flat_assign = recv_assign.reshape(n_ep * cap)
        valid = flat_assign >= 0
        lid = jnp.where(valid, flat_assign, 0)
        onehot = (jax.nn.one_hot(lid, e_per_dev, dtype=flat_recv.dtype)
                  * valid[:, None].astype(flat_recv.dtype))
        grouped = jnp.einsum("nd,ne->end", flat_recv, onehot)  # (e, N, d)?
        # NOTE: for e_per_dev small this dense grouping is cheap and local
        g = f(jnp.einsum("end,edf->enf", grouped, wg.astype(x.dtype)))
        u = jnp.einsum("end,edf->enf", grouped, wu.astype(x.dtype))
        eo = jnp.einsum("enf,efd->end", g * u, wd.astype(x.dtype))
        out_rows = jnp.einsum("end,ne->nd", eo, onehot)    # back to rows
        out_send = out_rows.reshape(n_ep, cap, d)
        out_recv = a2a(out_send)                           # back at source
        out_recv = out_recv.reshape(n_ep, cap, d)

        # combine at source with router weights
        flat_w = weights.reshape(-1).astype(x.dtype)
        w_buf = jnp.zeros((n_ep, cap), x.dtype).at[s_dst, pos].set(
            jnp.where(keep, flat_w[order], 0.0), mode="drop")
        yt = jnp.zeros((T_loc + 1, d), x.dtype).at[
            jnp.where(buf_tok >= 0, buf_tok, T_loc)].add(
            out_recv * w_buf[..., None])
        ys = yt[:T_loc].reshape(B // D, S_loc, d)
        # reassemble the full sequence across the model axis
        y_full = jax.lax.all_gather(ys, "model", axis=1, tiled=True)
        return y_full, aux

    in_specs = (P("data", None, None), P(), P(ep_axes, None, None),
                P(ep_axes, None, None), P(ep_axes, None, None))
    out_specs = (P("data", None, None), P())
    body_mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)
    out, aux = body_mapped(x, params["router"], params["wg"], params["wu"],
                           params["wd"])
    if cfg.n_shared_experts:
        from repro.models.layers.mlp import mlp_apply
        out = out + mlp_apply(params["shared"], x, act)
    return out, aux
