"""Heterogeneous cohort engine: fast-path federation for mixed populations.

The paper's central claim is *heterogeneous* federated transfer — clients
with different feature sets sharing network parts asynchronously — but the
batched fast path stacks the whole population on one leading axis, which
requires every client to have the same feature count ``nf`` and identical
split shapes.  This module closes that gap: an arbitrary mixed population
(varying nf, ragged train/valid/test lengths) is partitioned into
**homogeneous cohorts** — maximal groups of clients that stack — and the
whole mixed epoch still runs as ONE compiled dispatch:

* **Per-cohort training.**  Each cohort's clients are stacked ``(C_k, ...)``
  and take the same vmapped Adam step the homogeneous engine uses
  (``hfl._train_step``), at the cohort's native geometry — no feature
  padding ever enters the training math, so values stay bit-identical to
  the sequential oracle.  Cohorts with fewer sub-rounds than the epoch's
  maximum run masked no-op steps on zero-padded round slices (the computed
  update is discarded with a ``where``, an exact copy of the old state) —
  that is how ragged lengths ride a single uniform scan.

* **Global padded pool exchange.**  Knowledge crosses cohorts through the
  union head pool, stacked ``(C, max_nf, ...)`` with every client's head
  rows zero-padded to ``max_nf`` and a static ``(C, max_nf)`` feature-
  validity mask.  Each sub-round replays the exact homogeneous policy round
  (``federation._policy_round_body`` with ``feat_valid``) over the padded
  union: the Eq.-7 scoring sweep runs over all ``C * max_nf`` rows (padded
  rows masked to ``inf``, so the ``pool_mlp`` kernel sweeps a dense
  rectangle), selection walks clients in their ORIGINAL list order
  (interleaved across cohorts, exactly the oracle), and Eq.-8 blending is
  projected back to each cohort's native nf by slicing the padded result.
  :func:`hetero_selection_lut` maps padded flat indices back to the
  oracle's sorted-foreign-pool positions so logged selections are
  identical.

* **Cohort-aware mesh sharding.**  With a multi-device ``clients`` mesh,
  each cohort's stack is partitioned over the same client axis (every
  cohort size must divide the device count) and the padded union pool is
  assembled from per-cohort all-gathers — the same replicated-deterministic
  exchange pattern as ``mesh_federation``, now per cohort.

``Federation(engine="batched")`` routes here automatically whenever the
population is heterogeneous (see ``federation._is_homogeneous``); cohorting
is an internal planning step surfaced in ``Federation.dispatch_stats``
(``cohorts``, ``per_cohort``).  Selections and validation histories are
bit-identical to the sequential oracle (pinned by ``tests/test_cohorts.py``
on the single-device and multi-device mesh paths).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mesh_federation as MF
from repro.core import telemetry as TEL
from repro.core import trust as TR
from repro.core.federation import (_count_restack, _exchange_round_bytes,
                                   _hold_client_copies_on_host, _is_host,
                                   _policy_round_body, _stack_data,
                                   _stack_trees, _to_host, _tree_bytes,
                                   _tree_row, _wants_per_round)
from repro.core.hfl import FederatedClient, _eval_mse, _train_step
from repro.core.policies import FederationPolicies
from repro.optim import adam


# ---------------------------------------------------------------------------
# Cohort planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CohortSpec:
    """One homogeneous cohort: clients with the same nf and identical
    train/valid/test shapes, stackable on a leading axis.  ``members`` are
    global client indices in their original Federation order (the policy
    round's client order is GLOBAL — cohorts only partition the training
    geometry, never the exchange order)."""
    nf: int
    members: Tuple[int, ...]
    n_train: int
    n_sub: int           # full R-sized sub-rounds per epoch for this cohort

    @property
    def size(self) -> int:
        return len(self.members)


@dataclasses.dataclass(frozen=True)
class CohortPlan:
    """The cohort engine's static execution plan — hashable, so it keys the
    compile cache of the fused heterogeneous epoch."""
    cohorts: Tuple[CohortSpec, ...]
    C: int
    max_nf: int
    R: int
    n_sub_max: int
    nfs: Tuple[int, ...]       # per global client
    n_subs: Tuple[int, ...]    # per global client

    def feat_valid(self) -> np.ndarray:
        """(C, max_nf) bool: which rows of each client's padded head/probe
        stacks are real features."""
        fv = np.zeros((self.C, self.max_nf), bool)
        for i, nf in enumerate(self.nfs):
            fv[i, :nf] = True
        return fv


def plan_cohorts(clients: Sequence[FederatedClient], R: int) -> CohortPlan:
    """Partition a population into homogeneous cohorts.

    The cohort key is (nf, train/valid/test shapes): two clients share a
    cohort iff their stacked state is one geometry.  Fully ragged
    populations degrade to singleton cohorts — still correct, just less
    vmap leverage.  Head geometry (the probe window w) must be uniform
    across the WHOLE population: the union pool stacks every client's head
    params into one tree, exactly like the sequential oracle's
    ``HeadPool.stacked_for`` (which would fail on mixed w too)."""
    w0 = {c.cfg.w for c in clients}
    if len(w0) != 1:
        raise ValueError(
            f"heterogeneous head widths w={sorted(w0)}: the shared head "
            f"pool requires one probe-window width across the population "
            f"(heads all map (w,) -> scalar); split the federation per w")
    groups = {}
    order = []
    for i, c in enumerate(clients):
        key = (c.nf,
               tuple(np.shape(a) for a in c.train),
               tuple(np.shape(a) for a in c.valid),
               tuple(np.shape(a) for a in c.test))
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(i)
    cohorts = []
    for key in order:
        nf = key[0]
        members = tuple(groups[key])
        n_train = key[1][2][0] if len(key[1]) == 3 else 0
        n_sub = max(0, (n_train - R) // R + 1) if n_train >= R else 0
        cohorts.append(CohortSpec(nf=nf, members=members, n_train=n_train,
                                  n_sub=n_sub))
    nfs = tuple(c.nf for c in clients)
    n_subs = [0] * len(clients)
    for co in cohorts:
        for i in co.members:
            n_subs[i] = co.n_sub
    return CohortPlan(cohorts=tuple(cohorts), C=len(clients),
                      max_nf=max(nfs), R=R,
                      n_sub_max=max((co.n_sub for co in cohorts), default=0),
                      nfs=nfs, n_subs=tuple(n_subs))


def nf_strata(nfs: Sequence[int]) -> "OrderedDict[int, np.ndarray]":
    """Group population indices by feature count, in ascending-nf order —
    the stratification key the participation sampler uses.

    nf is a cheap METADATA proxy for the full cohort key (which also folds
    in split shapes that only exist once clients are materialized): every
    cohort of a sampled wave lies inside one nf stratum, so per-stratum
    sample counts sized to a mesh multiple keep every wave cohort
    mesh-divisible, and fixed per-stratum counts keep the per-wave
    ``CohortPlan`` geometry static across waves (compile-cache hits
    instead of a recompile per wave)."""
    from collections import OrderedDict
    nfs = np.asarray(nfs)
    return OrderedDict((int(nf), np.flatnonzero(nfs == nf))
                       for nf in np.unique(nfs))


# ---------------------------------------------------------------------------
# Padded union pool
# ---------------------------------------------------------------------------

def pad_features(tree, max_nf: int):
    """Zero-pad the leading (feature) axis of every leaf of an ``(nf, ...)``
    head tree to ``max_nf`` — the padded rows are dead weight the validity
    masks hide from every selection."""
    def pad(p):
        xp = np if _is_host(p) else jnp
        p = xp.asarray(p)
        if p.shape[0] == max_nf:
            return p
        return xp.concatenate(
            [p, xp.zeros((max_nf - p.shape[0],) + p.shape[1:], p.dtype)], 0)
    return jax.tree_util.tree_map(pad, tree)


def _pad_axis1(tree, max_nf: int):
    """Zero-pad axis 1 (the feature axis of a client-stacked tree)."""
    def pad(p):
        if p.shape[1] == max_nf:
            return p
        widths = [(0, 0)] * p.ndim
        widths[1] = (0, max_nf - p.shape[1])
        return jnp.pad(p, widths)
    return jax.tree_util.tree_map(pad, tree)


def stack_hetero_pool(pool, names: Sequence[str], nfs: Sequence[int],
                      max_nf: int):
    """A HeadPool's entries as the cohort engine's padded ``(C, max_nf, ...)``
    stacked tree: every client's nf head entries, zero-padded to max_nf —
    the heterogeneous twin of ``federation.stack_pool``."""
    rows = []
    for n, nf in zip(names, nfs):
        stacked = _stack_trees([pool.entries[(n, f)] for f in range(nf)])
        rows.append(pad_features(stacked, max_nf))
    return _stack_trees(rows)


def hetero_selection_lut(names: Sequence[str], nfs: Sequence[int],
                         max_nf: int) -> np.ndarray:
    """Map the padded union pool's row-major (client, padded-feature) flat
    index to the sequential oracle's sorted-by-(name, feature) foreign-pool
    index for each selecting client — the mixed-nf generalization of
    ``federation._selection_lut`` (whose pools are rectangular).  Entries
    for the selector's own rows and for padded feature rows are -1."""
    C = len(names)
    lut = np.full((C, C * max_nf), -1, np.int64)
    for i in range(C):
        others = sorted((names[j], j) for j in range(C) if j != i)
        off = 0
        for _, j in others:
            for g in range(nfs[j]):
                lut[i, j * max_nf + g] = off + g
            off += nfs[j]
    return lut


# ---------------------------------------------------------------------------
# The fused heterogeneous epoch
# ---------------------------------------------------------------------------

def _tree_select(cond, new, old):
    """Elementwise keep-or-discard of a whole pytree update (exact copies —
    the ragged-round mask cannot perturb kept values)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(cond, a, b), new, old)


def _hetero_epoch_body(lr: float, plan: CohortPlan,
                       policies: FederationPolicies, use_kernel: bool,
                       do_federate: bool, do_eval: bool, *,
                       exchange_every: int = 1, gather=None,
                       local_rows=None, shard=None, admission=None,
                       trust=None, telemetry=None):
    """The fused whole-epoch computation for a cohorted population, shared by
    the single-device and mesh backends: one ``lax.scan`` over the epoch's
    global sub-rounds.  Each step trains every cohort at its native
    geometry (masked where the cohort's rounds have run out), then — when
    federating — assembles the padded union view (heads + probe batches
    scattered into global client order), replays the exact homogeneous
    policy round over it with feature-validity masks, and projects each
    cohort's blended heads back to native nf.  Per-epoch eval + save-best
    run per cohort at the end.

    ``gather(tree)`` / ``local_rows(tree, k)`` are the mesh hooks: identity
    on the single-device path; the mesh backend injects a client-axis
    all-gather (per-cohort full view for the replicated policy round) and a
    dynamic-slice taking cohort k's device-local block back out.  ``shard``
    is forwarded to :func:`~repro.core.federation._policy_round_body`
    (client-sharded Eq.-7 scoring over the padded union pool's ``C *
    max_nf`` rows).  ``exchange_every`` = k > 1 segments the scan exactly
    like ``federation._epoch_body``: groups of k sub-rounds run k-1
    train-only steps plus one train+exchange step on the group's last
    round, leftover ``n_sub % k`` rounds never exchange — static cadence,
    so the mesh path traces the identical collective schedule on every
    device; k=1 is the historical flat scan, bit-identical.

    ``admission`` forwards the pool admission guard's norm bound to
    :func:`~repro.core.federation._policy_round_body`; when set, the epoch
    returns one extra trailing ``(exchange_rounds, C)`` bool rejection
    mask (None traces exactly the fault-free body).

    ``trust`` threads the trust layer at the PADDED geometry, exactly as
    ``federation._epoch_body`` does at the homogeneous one: the epoch
    function takes one extra trailing ``trust_arrays`` argument (padded
    signature stack / ``(net_masks, correction)`` scan leg / DP dummy)
    and returns one extra trailing ``((rounds, C) clip, (rounds, C)
    wm_failed)`` pair after the admission mask.  Secure aggregation
    replaces the padded-union selection with ``trust.secure_round`` over
    the padded stacks (``feat_valid`` silences padded rows in every sum).
    ``trust=None`` traces the byte-identical pre-trust graph.

    ``telemetry`` (a TelemetryPlan with the in-graph series enabled)
    appends one more trailing scan output — the per-round metrics 4-tuple
    ``(foreign_picks (C,) int32, score_min (C,) f32, score_mean (C,) f32,
    pool_age (C,) int32)`` at the padded geometry (padded features select
    -1, so they never count as picks) — appended LAST and therefore popped
    FIRST at every unpack site, before trust, before admission.
    ``telemetry=None`` traces the byte-identical pre-telemetry graph.

    The named scopes are those of ``federation._epoch_body``:
    ``train_step`` (every cohort's step), ``policy_round`` (``eq7_score``
    inside) and ``eval_best``, plus ``union_view``: the scatter of every
    cohort's heads and probe batches into the padded union (with the
    mesh's per-cohort all-gathers) and the projection of the blended
    heads back to each cohort's native nf.  The function is named
    ``hetero_epoch``, so its programs, single-device and mesh, compile as
    the module ``jit_hetero_epoch``, apart from the homogeneous
    ``jit_epoch``."""
    opt = adam(lr)
    step = jax.vmap(functools.partial(_train_step, opt))
    evaluate = jax.vmap(_eval_mse)
    K = len(plan.cohorts)
    C, max_nf, R = plan.C, plan.max_nf, plan.R
    feat_valid = plan.feat_valid()
    members = [np.asarray(co.members, np.int32) for co in plan.cohorts]
    bounded = policies.pool.bounded
    k_ex = int(exchange_every)
    secure = trust is not None and trust.secure_agg is not None
    secure_in_scan = secure and do_federate
    sel_trust = None if secure else trust
    if gather is None:
        gather = lambda t: t
    if local_rows is None:
        local_rows = lambda t, k: t

    def hetero_epoch(params_t, opt_t, pool_heads, pool_age, key, best_val_t,
                     best_params_t, xs_t, xd_t, y_t, part, tick, live,
                     val_xs_t, val_xd_t, val_y_t, trust_arrays=None):

        def train(params_t, opt_t, bx, bd, by, live_r):
            """Every cohort's masked native-geometry step for one
            sub-round (shared by exchange and train-only rounds)."""
            params_t, opt_t = list(params_t), list(opt_t)
            for k, co in enumerate(plan.cohorts):
                with jax.named_scope("train_step"):
                    p2, o2, _ = step(params_t[k], opt_t[k], bx[k], bd[k],
                                     by[k])
                    if co.n_sub == plan.n_sub_max:
                        params_t[k], opt_t[k] = p2, o2  # never a padded round
                    else:
                        params_t[k] = _tree_select(live_r[k], p2,
                                                   params_t[k])
                        opt_t[k] = _tree_select(live_r[k], o2, opt_t[k])
            return params_t, opt_t

        def body(carry, inp):
            params_t, opt_t, pool_heads, pool_age, key = carry
            if secure_in_scan:
                inp, (mask_e, corr_e) = inp
            (bx, bd, by), part_r, tick_r, live_r = inp
            params_t, opt_t = train(params_t, opt_t, bx, bd, by, live_r)
            if do_federate:
                if bounded:
                    pool_age = pool_age + tick_r
                key, sub = jax.random.split(key)
                # padded union view in GLOBAL client order: scatter each
                # cohort's (gathered) heads and probe batches into
                # (C, max_nf, ...) / (C, R, max_nf, w) zero-initialized
                # stacks — exact copies, so oracle bit-parity survives
                with jax.named_scope("union_view"):
                    heads_g = jax.tree_util.tree_map(jnp.zeros_like,
                                                     pool_heads)
                    w = bd[0].shape[-1]
                    xd_g = jnp.zeros((C, R, max_nf, w), bd[0].dtype)
                    y_g = jnp.zeros((C, R), by[0].dtype)
                    for k in range(K):
                        idx = members[k]
                        hk = _pad_axis1(gather(params_t[k]["heads"]), max_nf)
                        heads_g = jax.tree_util.tree_map(
                            lambda g, h: g.at[idx].set(h), heads_g, hk)
                        if not secure:  # secure needs no probe scatters
                            dk = gather(bd[k])         # (C_k, R, nf_k, w)
                            pad = max_nf - dk.shape[2]
                            if pad:
                                dk = jnp.pad(dk, ((0, 0), (0, 0), (0, pad),
                                                  (0, 0)))
                            xd_g = xd_g.at[idx].set(dk)
                            y_g = y_g.at[idx].set(gather(by[k]))
                if secure:
                    with jax.named_scope("policy_round"):
                        (new_heads, pool_heads, pool_age, chosen, rej,
                         clip) = TR.secure_round(
                            heads_g, pool_heads, pool_age, part_r, mask_e,
                            corr_e, sub, feat_valid=feat_valid,
                            sa=trust.secure_agg, dp=trust.dp, nf=max_nf,
                            admission=admission)
                    tstats = (clip, jnp.zeros((C,), bool))
                else:
                    with jax.named_scope("policy_round"):
                        out = _policy_round_body(
                            heads_g, pool_heads, pool_age, xd_g, y_g,
                            part_r, sub, nf=max_nf, policies=policies,
                            use_kernel=use_kernel, feat_valid=feat_valid,
                            shard=shard, admission=admission,
                            trust=sel_trust,
                            trust_sig=(trust_arrays
                                       if sel_trust is not None
                                       and sel_trust.watermark is not None
                                       else None), telemetry=telemetry)
                    if telemetry is not None:
                        scores = out[-1]
                        out = out[:-1]
                    if trust is not None:
                        tstats = out[-1]
                        out = out[:-1]
                    if admission is not None:
                        new_heads, pool_heads, pool_age, chosen, rej = out
                    else:
                        new_heads, pool_heads, pool_age, chosen = out
                with jax.named_scope("union_view"):
                    for k, co in enumerate(plan.cohorts):
                        rows = jax.tree_util.tree_map(
                            lambda g: g[members[k], :co.nf], new_heads)
                        params_t[k] = {**params_t[k],
                                       "heads": local_rows(rows, k)}
            else:
                chosen = jnp.full((C, max_nf), -1, jnp.int32)
                if admission is not None:
                    rej = jnp.zeros((C,), bool)
                if trust is not None:
                    tstats = (jnp.zeros((C,), bool), jnp.zeros((C,), bool))
            if telemetry is not None:
                if not do_federate or secure:
                    # non-exchanging / masked-secure rounds score nothing:
                    # the series carry the inf/0 sentinels
                    scores = (jnp.full((C,), jnp.inf, jnp.float32),
                              jnp.zeros((C,), jnp.float32))
                tele_r = (jnp.sum(chosen >= 0, axis=-1).astype(jnp.int32),
                          scores[0], scores[1], pool_age)
            ys = (chosen,)
            if admission is not None:
                ys = ys + (rej,)
            if trust is not None:
                ys = ys + (tstats,)
            if telemetry is not None:
                ys = ys + (tele_r,)
            if len(ys) == 1:
                ys = ys[0]
            return ((tuple(params_t), tuple(opt_t), pool_heads, pool_age,
                     key), ys)

        def train_only(carry, inp):
            params_t, opt_t, pool_heads, pool_age, key = carry
            (bx, bd, by), part_r, tick_r, live_r = inp
            params_t, opt_t = train(params_t, opt_t, bx, bd, by, live_r)
            return ((tuple(params_t), tuple(opt_t), pool_heads, pool_age,
                     key), None)

        xs_all = ((xs_t, xd_t, y_t), part, tick, live)
        carry = (params_t, opt_t, pool_heads, pool_age, key)
        if not do_federate or k_ex == 1:
            # the historical flat scan; exchange_every=1 stays bit-identical
            xs = (xs_all, trust_arrays) if secure_in_scan else xs_all
            carry, ys = jax.lax.scan(body, carry, xs)
        else:
            n_sub = part.shape[0]
            n_grp, rem = divmod(n_sub, k_ex)
            grouped = jax.tree_util.tree_map(
                lambda t: t[:n_grp * k_ex].reshape(
                    (n_grp, k_ex) + t.shape[1:]), xs_all)

            def group(carry, inp_k):
                # k-1 train-only rounds, then train + exchange on the
                # group's LAST round (probes = that round's own R-batches)
                if secure_in_scan:
                    inp_k, masks_e = inp_k
                carry, _ = jax.lax.scan(
                    train_only, carry,
                    jax.tree_util.tree_map(lambda t: t[:k_ex - 1], inp_k))
                last = jax.tree_util.tree_map(lambda t: t[k_ex - 1], inp_k)
                if secure_in_scan:
                    last = (last, masks_e)
                return body(carry, last)

            xs = (grouped, trust_arrays) if secure_in_scan else grouped
            carry, ys = jax.lax.scan(group, carry, xs)
            if rem:                       # leftover rounds never exchange
                carry, _ = jax.lax.scan(
                    train_only, carry,
                    jax.tree_util.tree_map(lambda t: t[n_grp * k_ex:],
                                           xs_all))
        if telemetry is not None:
            tele = ys[-1]
            ys = ys[:-1]
            if len(ys) == 1:
                ys = ys[0]
        else:
            tele = None
        if admission is not None and trust is not None:
            chosen, rejected, tstats = ys
        elif admission is not None:
            chosen, rejected = ys
            tstats = None
        elif trust is not None:
            chosen, tstats = ys
            rejected = None
        else:
            chosen, rejected, tstats = ys, None, None
        (params_t, opt_t, pool_heads, pool_age, key) = carry
        if do_eval:
            vs, new_bv, new_bp = [], [], []
            for k in range(K):
                with jax.named_scope("eval_best"):
                    v = evaluate(params_t[k], val_xs_t[k], val_xd_t[k],
                                 val_y_t[k])              # (local clients,)
                    improved = v < best_val_t[k]
                    new_bv.append(jnp.where(improved, v, best_val_t[k]))
                    n_loc = v.shape[0]
                    new_bp.append(jax.tree_util.tree_map(
                        lambda b, p: jnp.where(
                            improved.reshape((n_loc,) + (1,) * (p.ndim - 1)),
                            p, b),
                        best_params_t[k], params_t[k]))
                vs.append(v)
            best_val_t, best_params_t = tuple(new_bv), tuple(new_bp)
            v_t = tuple(vs)
        else:
            v_t = None
        out = (params_t, opt_t, pool_heads, pool_age, key, best_val_t,
               best_params_t, v_t, chosen)
        if admission is not None:
            out = out + (rejected,)
        if trust is not None:
            out = out + (tstats,)
        if telemetry is not None:
            out = out + (tele,)
        return out

    return hetero_epoch


@functools.lru_cache(maxsize=None)
def _make_hetero_epoch_fn(lr: float, plan: CohortPlan,
                          policies: FederationPolicies, use_kernel: bool,
                          do_federate: bool, do_eval: bool,
                          exchange_every: int = 1, admission=None,
                          trust=None, telemetry=None):
    """Compile-cached fused heterogeneous epoch (single-device): one
    dispatch scans every global sub-round of a mixed-cohort epoch, with the
    whole carried state donated — the cohort twin of
    ``federation._make_epoch_fn``.  The cache key adds the (hashable)
    :class:`CohortPlan`, so every distinct population LAYOUT compiles once
    and every cohort inside it shares that single program."""
    epoch = _hetero_epoch_body(lr, plan, policies, use_kernel, do_federate,
                               do_eval, exchange_every=exchange_every,
                               admission=admission, trust=trust,
                               telemetry=telemetry)
    return jax.jit(epoch, donate_argnums=(0, 1, 2, 3, 4, 5, 6))


@functools.lru_cache(maxsize=None)
def _make_mesh_hetero_epoch_fn(lr: float, plan: CohortPlan, w: int,
                               policies: FederationPolicies,
                               use_kernel: bool, do_federate: bool,
                               do_eval: bool, mesh,
                               exchange_every: int = 1, admission=None,
                               trust=None, telemetry=None):
    """The client-sharded twin of :func:`_make_hetero_epoch_fn`: the same
    epoch body under ``shard_map``, with every cohort's stack partitioned
    over the mesh's ``clients`` axis (each cohort size must divide the
    device count — :func:`validate_cohort_mesh`), the padded union pool
    assembled from per-cohort all-gathers, and the Eq.-7 sweep over the
    padded union sharded per device (``shard=(axis, D)`` — each device
    scores its contiguous ``C * max_nf / D`` chunk, argminima merged
    through a tiny (D, max_nf) gather), everything downstream
    replicated-deterministic exactly like
    ``mesh_federation._make_mesh_epoch_fn``."""
    from jax.sharding import PartitionSpec as P

    axis = MF.client_axis(mesh)
    D = MF.mesh_devices(mesh)
    cl, rep, data = P(axis), P(), P(None, axis)
    K = len(plan.cohorts)
    pspecs_t = tuple(MF.param_pspecs(co.nf, w, co.size, mesh)
                     for co in plan.cohorts)
    c_locs = [co.size // D for co in plan.cohorts]

    def gather(tree):
        return jax.lax.all_gather(tree, axis, tiled=True)

    def local_rows(tree, k):
        i0 = jax.lax.axis_index(axis) * c_locs[k]
        return jax.tree_util.tree_map(
            lambda g: jax.lax.dynamic_slice_in_dim(g, i0, c_locs[k], 0),
            tree)

    epoch = _hetero_epoch_body(lr, plan, policies, use_kernel, do_federate,
                               do_eval, exchange_every=exchange_every,
                               gather=gather, local_rows=local_rows,
                               shard=(axis, D), admission=admission,
                               trust=trust, telemetry=telemetry)
    tup = lambda spec: tuple(spec for _ in range(K))
    out_specs = (pspecs_t, tup(cl), rep, rep, rep, tup(cl), pspecs_t,
                 tup(cl) if do_eval else None, rep)
    if admission is not None:
        out_specs = out_specs + (rep,)   # rejection mask is replicated
    in_specs = (pspecs_t, tup(cl), rep, rep, rep, tup(cl), pspecs_t,
                tup(data), tup(data), tup(data), rep, rep, rep,
                tup(cl), tup(cl), tup(cl))
    if trust is not None:
        # trust inputs (padded signature stack / mask pair / dummy) and
        # the per-round trust stats are replicated like the pool carry
        in_specs = in_specs + (rep,)
        out_specs = out_specs + (rep,)
    if telemetry is not None:
        # the per-round metrics 4-tuple comes back replicated (derived
        # from the replicated pool carry / collectively-reduced scores);
        # a single ``rep`` prefixes the whole tuple, as for trust above
        out_specs = out_specs + (rep,)
    sharded = jax.shard_map(
        epoch, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1, 2, 3, 4, 5, 6))


def validate_cohort_mesh(mesh, plan: CohortPlan) -> None:
    """Client-sharded cohort execution needs every cohort's stack to split
    evenly over the mesh: each device owns a contiguous equal block of each
    cohort.  Raise with the offending cohort sizes otherwise."""
    D = MF.mesh_devices(mesh)
    bad = [co.size for co in plan.cohorts if co.size % D]
    if bad:
        raise ValueError(
            f"cohort sizes {bad} cannot shard evenly over {D} devices "
            f"(every cohort size must be a multiple of the device count); "
            f"pad the population per cohort, regroup it, or run without "
            f"a mesh")


def shard_hetero_fit_state(mesh, plan: CohortPlan, w: int, *, params_t,
                           opt_t, pool_heads, pool_age, key, best_val_t,
                           best_params_t, rounds_t, val_t):
    """Place the cohort engine's fit state on the mesh (the heterogeneous
    twin of ``mesh_federation.shard_fit_state``): per-cohort trees get the
    schema-derived client partitioning, the padded union pool / ages / PRNG
    key are replicated, per-cohort round data partitions its client (2nd)
    axis."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    validate_cohort_mesh(mesh, plan)
    axis = MF.client_axis(mesh)
    named = lambda ps: NamedSharding(mesh, ps)
    clients_sh, rep = named(P(axis)), named(P())

    def put_params(trees):
        return tuple(
            jax.device_put(t, jax.tree_util.tree_map(
                named, MF.param_pspecs(co.nf, w, co.size, mesh)))
            for t, co in zip(trees, plan.cohorts))

    params_t = put_params(params_t)
    best_params_t = put_params(best_params_t)
    opt_t = tuple(jax.device_put(t, clients_sh) for t in opt_t)
    best_val_t = tuple(jax.device_put(t, clients_sh) for t in best_val_t)
    pool_heads = jax.device_put(pool_heads, rep)
    pool_age = jax.device_put(pool_age, rep)
    key = jax.device_put(key, rep)
    rounds_t = tuple(
        tuple(jax.device_put(a, named(P(None, axis))) for a in rd)
        for rd in rounds_t)
    val_t = tuple(tuple(jax.device_put(a, clients_sh) for a in vd)
                  for vd in val_t)
    return (params_t, opt_t, pool_heads, pool_age, key, best_val_t,
            best_params_t, rounds_t, val_t)


# ---------------------------------------------------------------------------
# The cohorted fit loop
# ---------------------------------------------------------------------------

def _fit_cohorted(fed, n_epochs: int, cbs) -> None:
    """The batched executor's heterogeneous path: plan cohorts, stack each
    at its native geometry, scan whole mixed epochs inside one compiled
    dispatch (chunked per sub-round when a callback needs per-round
    delivery), exchange heads through the padded union pool, and write
    results back through the same sync contract as the homogeneous
    executor.  Selection- and value-identical to the sequential oracle."""
    clients = fed.clients
    C = len(clients)
    names = [c.name for c in clients]
    cfg, pol = fed.cfg, fed.policies
    R = fed.schedule.R
    plan = plan_cohorts(clients, R)
    K = len(plan.cohorts)
    n_sub_max = plan.n_sub_max
    n_subs = np.asarray(plan.n_subs)

    def rounds_axis(t, n_sub):
        """(C_k, n, ...) -> (n_sub_max, C_k, R, ...): the cohort's R-slices
        on a leading scan axis, zero-padded to the global round count (the
        padded rounds are masked no-ops)."""
        Ck = t.shape[0]
        m = n_sub * R
        r = np.moveaxis(t[:, :m].reshape((Ck, n_sub, R) + t.shape[2:]),
                        1, 0)
        if n_sub < n_sub_max:
            r = np.concatenate(
                [r, np.zeros((n_sub_max - n_sub,) + r.shape[1:],
                             r.dtype)], 0)
        return r

    # telemetry layer: `tele` = the enabled plan iff the in-graph series is
    # on (static jit arg; None traces the uninstrumented graph), `rec` =
    # the host-side flight recorder
    tele = fed._tele_rounds()
    rec = fed._recorder
    mesh = fed._exec_mesh()
    key = fed._key
    with TEL.span(rec, "restack"):
        rounds_t, val_t = [], []
        params_l, opt_l, bv_l, bp_l = [], [], [], []
        for co in plan.cohorts:
            cs = [clients[i] for i in co.members]
            rounds_t.append(tuple(
                rounds_axis(_stack_data([c.train[j] for c in cs]), co.n_sub)
                for j in range(3)))
            val_t.append(tuple(_stack_data([c.valid[j] for c in cs])
                               for j in range(3)))
            params_l.append(_stack_trees([c.params for c in cs]))
            opt_l.append(_stack_trees([c.opt_state for c in cs]))
            bv_l.append(np.asarray([c.best_val for c in cs], np.float32))
            bp_l.append(_stack_trees([c.best_params for c in cs]))
        rounds_t, val_t = tuple(rounds_t), tuple(val_t)
        params_t, opt_t = tuple(params_l), tuple(opt_l)
        best_val_t, best_params_t = tuple(bv_l), tuple(bp_l)
        del params_l, opt_l, bv_l, bp_l
        pool_heads = stack_hetero_pool(fed.pool, names, plan.nfs,
                                       plan.max_nf)
        pool_age = np.asarray([fed.pool.age_of(n_) for n_ in names],
                              np.int32)
        _count_restack(rec, params_t, opt_t, best_params_t, pool_heads)
        # one placement per fit, straight from the host on a mesh
        if mesh is not None:
            (params_t, opt_t, pool_heads, pool_age, key, best_val_t,
             best_params_t, rounds_t, val_t) = shard_hetero_fit_state(
                mesh, plan, cfg.w, params_t=params_t, opt_t=opt_t,
                pool_heads=pool_heads, pool_age=pool_age, key=key,
                best_val_t=best_val_t, best_params_t=best_params_t,
                rounds_t=rounds_t, val_t=val_t)
            _hold_client_copies_on_host(fed)
        else:
            (params_t, opt_t, pool_heads, pool_age, best_val_t,
             best_params_t, rounds_t, val_t) = jax.device_put(
                (params_t, opt_t, pool_heads, pool_age, best_val_t,
                 best_params_t, rounds_t, val_t))
    use_kernel = cfg.use_pool_kernel
    lut = hetero_selection_lut(names, plan.nfs, plan.max_nf)
    admission = fed._admission()
    smask = fed._straggler_mask
    trust = fed._trust
    secure = trust is not None and trust.secure_agg is not None
    # host templates/derivations the trust layer needs, at the PADDED
    # geometry (masks and signatures ride the (C, max_nf, ...) union)
    head_tmpl = TR.pad_rows(jax.tree_util.tree_map(
        np.asarray, clients[0].params["heads"]), plan.max_nf) \
        if secure else None
    sig_stack = None
    if trust is not None and trust.watermark is not None:
        sig_stack = jax.tree_util.tree_map(
            jnp.asarray,
            TR.stack_trees_np([TR.pad_rows(fed._wm_sig(c), plan.max_nf)
                               for c in clients]))
    clip_total = 0
    wm_fail = np.zeros(C, np.int64)
    dp_pubs = np.zeros(C, np.int64)
    heads_rejected = 0
    live_np = np.asarray([[k < co.n_sub for co in plan.cohorts]
                          for k in range(n_sub_max)], bool)

    k_ex = fed.schedule.exchange_every
    exch = fed.schedule.exchange_mask(n_sub_max)
    n_exch_epoch = fed.schedule.exchanges(n_sub_max)
    exchange_rounds = 0
    pool_bytes = 0
    # per-device bytes one mesh exchange round moves (0 on one device):
    # padded-union pool heads + per-cohort probe gathers at native nf,
    # reduce sized by the padded union (ns = C * max_nf)
    heads_bytes = _tree_bytes(pool_heads)
    probe_bytes = sum(co.size * R * (co.nf * cfg.w + 1) * 4
                      for co in plan.cohorts)
    exch_bytes = _exchange_round_bytes(
        MF.mesh_devices(fed._exec_mesh()), heads_bytes, probe_bytes,
        C, plan.max_nf, C * plan.max_nf,
        pol.selection) if fed._exec_mesh() is not None else 0

    histories = [list(c.val_history) for c in clients]
    # device-resident learnable state across all cohorts (the participation
    # orchestrator's gather/scatter unit and bounded-working-set meter)
    state_bytes = sum(_tree_bytes((p, o, bp)) for p, o, bp in
                      zip(params_t, opt_t, best_params_t))
    n_rounds = np.zeros(C, np.int64)
    base_rounds = dict(fed.n_rounds)

    def make_epoch_fn(do_federate: bool, do_eval: bool,
                      exchange_every: int = 1):
        if mesh is not None:
            return _make_mesh_hetero_epoch_fn(cfg.lr, plan, cfg.w, pol,
                                              use_kernel, do_federate,
                                              do_eval, mesh, exchange_every,
                                              admission, trust, tele)
        return _make_hetero_epoch_fn(cfg.lr, plan, pol, use_kernel,
                                     do_federate, do_eval, exchange_every,
                                     admission, trust, tele)

    def trust_args(act_rows, e_off: int = 0):
        """The epoch function's trailing ``trust_arrays`` argument for one
        dispatch.  ``act_rows`` is the (n_exch, C) per-exchange-round
        participation — on the cohort engine the publisher set varies per
        sub-round (clients drop out as their sub-rounds run dry), so the
        secure dropout correction is reconstructed per round from the
        round's own survivor set."""
        if trust is None:
            return ()
        if secure:
            n_exch = len(act_rows)
            wave = fed._trust_wave_base + fed.epoch
            masks = TR.net_masks(trust.secure_agg, wave, n_exch,
                                 fed._trust_ids, head_tmpl,
                                 round_offset=e_off)
            corrs = [TR.mask_correction(
                jax.tree_util.tree_map(lambda m: m[r:r + 1], masks),
                act_rows[r]) for r in range(n_exch)]
            if corrs:
                corr = jax.tree_util.tree_map(
                    lambda *cs: np.concatenate(cs), *corrs)
            else:
                corr = jax.tree_util.tree_map(
                    lambda m: np.zeros((0,) + m.shape[2:], m.dtype), masks)
            ta = jax.tree_util.tree_map(jnp.asarray, (masks, corr))
        elif sig_stack is not None:
            ta = sig_stack
        else:
            ta = jnp.zeros((), jnp.float32)
        if mesh is not None:
            ta = MF.replicate(mesh, ta)
        return (ta,)

    def account_trust(tstats, rej, opps):
        """Fold one dispatch's trust outputs into the fit's counters.
        ``opps``: (C,) per-client exchange publication opportunities this
        dispatch (zero everywhere on a non-federating dispatch)."""
        nonlocal clip_total
        if trust is None:
            return
        clip_r, wmf_r = (np.asarray(t) for t in tstats)
        clip_total += int(clip_r.sum())
        wmf_pc = wmf_r.reshape(-1, C).sum(axis=0).astype(np.int64)
        wm_fail[:] += wmf_pc
        if trust.dp is not None:
            rej_pc = (np.asarray(rej).reshape(-1, C).sum(axis=0)
                      if rej is not None else np.zeros(C, np.int64))
            dp_pubs[:] += np.asarray(opps, np.int64) - wmf_pc - rej_pc

    fused = not any(_wants_per_round(cb) for cb in cbs)
    n_dispatch = 0

    def sync():
        """Write the per-cohort loop state back into the clients / pool /
        rng — after the loop, and on demand for mid-fit checkpoints."""
        with TEL.span(rec, "writeback"):
            params_h, opt_h, best_h, bv_h, heads_h, ages = _to_host(
                (params_t, opt_t, best_params_t, best_val_t, pool_heads,
                 pool_age))
            for k, co in enumerate(plan.cohorts):
                for r, i in enumerate(co.members):
                    c = clients[i]
                    c.params = _tree_row(params_h[k], r)
                    c.opt_state = _tree_row(opt_h[k], r)
                    c.val_history = histories[i]
                    c.best_val = float(bv_h[k][r])
                    c.best_params = _tree_row(best_h[k], r)
            for i, c in enumerate(clients):
                row = jax.tree_util.tree_map(
                    lambda p: p[i, :plan.nfs[i]], heads_h)
                fed.pool.publish(c.name, row, plan.nfs[i], age=int(ages[i]))
                fed.n_rounds[c.name] = (base_rounds[c.name]
                                        + int(n_rounds[i]))
            fed._key = key

    fed._sync = sync
    for _ in range(n_epochs):
        epoch = fed.epoch
        active = np.asarray(pol.switch.active_mask(histories,
                                                   fed._switch_rng))
        if smask is not None:   # stragglers train but miss every exchange
            active = active & ~np.asarray(smask, bool)
        do_federate = bool(active.any()) and C >= 2
        # participation: epoch-active AND the client still has sub-rounds
        # left (the oracle's live set); the staleness clock ticks in every
        # sub-round where federation COULD run among still-live clients —
        # note >= (a client exhausted in exactly this round still counts,
        # matching the oracle's live-at-start-of-iteration semantics)
        part_np = active[None, :] & \
            (np.arange(n_sub_max)[:, None] < n_subs[None, :])
        if pol.pool.bounded and do_federate:
            tick_np = np.asarray(
                [(active & (n_subs >= k)).any() for k in range(n_sub_max)],
                np.int32)
        else:
            tick_np = np.zeros(n_sub_max, np.int32)
        part = jnp.asarray(part_np)
        tick = jnp.asarray(tick_np)
        live = jnp.asarray(live_np)
        if mesh is not None:
            part = MF.replicate(mesh, part)
            tick = MF.replicate(mesh, tick)
            live = MF.replicate(mesh, live)
        state = (params_t, opt_t, pool_heads, pool_age, key, best_val_t,
                 best_params_t)
        fed._mid_epoch = True
        if fused:
            epoch_fn = make_epoch_fn(do_federate, True, k_ex)
            act_rows = part_np[exch] if do_federate else part_np[:0]
            args = (*state, tuple(r[0] for r in rounds_t),
                    tuple(r[1] for r in rounds_t),
                    tuple(r[2] for r in rounds_t), part, tick, live,
                    tuple(v[0] for v in val_t), tuple(v[1] for v in val_t),
                    tuple(v[2] for v in val_t), *trust_args(act_rows))
            if rec is not None:
                rec.note_program(epoch_fn, *args)
            with TEL.span(rec, "dispatch", epoch=epoch, path="fused"):
                out = epoch_fn(*args)
            if tele is not None:   # telemetry rides LAST: pop it first
                tele_out, out = out[-1], out[:-1]
            if trust is not None:
                tstats, out = out[-1], out[:-1]
            if admission is not None:
                (*state, v_t, chosen, rej) = out
                heads_rejected += int(np.asarray(rej).sum())
            else:
                (*state, v_t, chosen) = out
                rej = None
            account_trust(tstats, rej, act_rows.sum(axis=0)) \
                if trust is not None else None
            n_dispatch += 1
        else:
            chunks = []
            tele_chunks = []
            e_done = 0          # exchange rounds executed so far this epoch
                                # (the trust layer's within-epoch mask index)
            for rnd in range(n_sub_max):
                # cadence on the chunked path: a non-exchange sub-round is
                # exactly a do_federate=False dispatch (train-only)
                fed_r = do_federate and bool(exch[rnd])
                epoch_fn = make_epoch_fn(fed_r, rnd == n_sub_max - 1)
                sl = slice(rnd, rnd + 1)
                act_rows = part_np[sl] if fed_r else part_np[:0]
                with TEL.span(rec, "dispatch", epoch=epoch, round=rnd,
                              path="chunked"):
                    out = epoch_fn(
                        *state,
                        tuple(r[0][sl] for r in rounds_t),
                        tuple(r[1][sl] for r in rounds_t),
                        tuple(r[2][sl] for r in rounds_t),
                        part[sl], tick[sl], live[sl],
                        tuple(v[0] for v in val_t),
                        tuple(v[1] for v in val_t),
                        tuple(v[2] for v in val_t),
                        *trust_args(act_rows, e_done))
                if tele is not None:
                    tele_chunks.append(out[-1])
                    out = out[:-1]
                if trust is not None:
                    tstats, out = out[-1], out[:-1]
                if admission is not None:
                    (*state, v_t, ch, rej) = out
                    heads_rejected += int(np.asarray(rej).sum())
                else:
                    (*state, v_t, ch) = out
                    rej = None
                account_trust(tstats, rej, act_rows.sum(axis=0)) \
                    if trust is not None else None
                if fed_r:
                    e_done += 1
                chunks.append(ch)
                n_dispatch += 1
                (params_t, opt_t, pool_heads, pool_age, key, best_val_t,
                 best_params_t) = state
                if exch[rnd]:
                    n_rounds += part_np[rnd]
                for cb in cbs:
                    cb.on_round(fed, epoch, rnd)
            if n_sub_max == 0:   # no trainable sub-round: eval-only dispatch
                epoch_fn = make_epoch_fn(do_federate, True)
                with TEL.span(rec, "dispatch", epoch=epoch,
                              path="eval-only"):
                    out = epoch_fn(
                        *state,
                        tuple(r[0] for r in rounds_t),
                        tuple(r[1] for r in rounds_t),
                        tuple(r[2] for r in rounds_t),
                        part, tick, live,
                        tuple(v[0] for v in val_t),
                        tuple(v[1] for v in val_t),
                        tuple(v[2] for v in val_t),
                        *trust_args(part_np[:0]))
                if tele is not None:
                    out = out[:-1]
                if trust is not None:
                    out = out[:-1]
                if admission is not None:
                    (*state, v_t, ch, _rej) = out
                else:
                    (*state, v_t, ch) = out
                chunks.append(ch)
                n_dispatch += 1
            chosen = jnp.concatenate(chunks) if chunks else None
            tele_out = tuple(
                np.concatenate([np.asarray(t[k]) for t in tele_chunks])
                for k in range(4)) if tele is not None and tele_chunks \
                else None
        (params_t, opt_t, pool_heads, pool_age, key, best_val_t,
         best_params_t) = state
        with TEL.span(rec, "readback", epoch=epoch):
            # ONE device->host materialization of the epoch's results
            v_all = np.empty(C, np.float64)
            for k, co in enumerate(plan.cohorts):
                v_all[np.asarray(co.members)] = np.asarray(v_t[k],
                                                           np.float64)
            ch_np = (np.asarray(chosen)         # (rounds, C, max_nf)
                     if do_federate and chosen is not None else None)
        with TEL.span(rec, "record", epoch=epoch):
            if ch_np is not None:
                for ch in ch_np:
                    for i in range(C):
                        if ch[i][0] >= 0:
                            nf_i = plan.nfs[i]
                            fed.selections[names[i]].append(
                                lut[i, ch[i][:nf_i]].tolist())
            if tele is not None and tele_out is not None:
                rec.record_epoch_rounds(epoch, tele_out, active)
            if fused:
                n_rounds += part_np[exch].sum(axis=0)
            if rec is not None:
                done = int(part_np[exch].sum())
                if done:
                    rec.count("client_rounds", done)
            # refresh the live counters each epoch (idempotent with sync())
            for i, nm in enumerate(names):
                fed.n_rounds[nm] = base_rounds[nm] + int(n_rounds[i])
            if do_federate:
                exchange_rounds += n_exch_epoch
                pool_bytes += n_exch_epoch * exch_bytes
                if rec is not None and n_exch_epoch:
                    # the scored pool's rows and the real ones among them
                    rec.count("union_rows", C * plan.max_nf * n_exch_epoch)
                    rec.count("union_real_rows", sum(plan.nfs) * n_exch_epoch)
            for i in range(C):
                histories[i].append(float(v_all[i]))
        fed.epoch += 1
        fed._mid_epoch = False
        for cb in cbs:
            cb.on_epoch_end(fed, epoch,
                            {names[i]: float(v_all[i]) for i in range(C)},
                            {names[i]: bool(active[i]) for i in range(C)})

    if trust is not None:
        fed._clip_events += clip_total
        for i, nm in enumerate(names):
            if wm_fail[i]:
                fed._wm_failures[nm] = (fed._wm_failures.get(nm, 0)
                                        + int(wm_fail[i]))
            if dp_pubs[i]:
                fed._dp_counts[nm] = (fed._dp_counts.get(nm, 0)
                                      + int(dp_pubs[i]))
    if rec is not None:
        # fold this fit's in-graph counters into the flight recorder (the
        # participation orchestrator may overwrite dispatch_stats later)
        if heads_rejected:
            rec.count("heads_rejected", int(heads_rejected))
        if trust is not None:
            if clip_total:
                rec.count("clip_events", int(clip_total))
            if wm_fail.sum():
                rec.count("watermark_failures", int(wm_fail.sum()))
    fed.dispatch_stats = {
        "engine": "batched",
        "path": "fused" if fused else "chunked",
        "devices": MF.mesh_devices(mesh),
        "cohorts": K,
        "per_cohort": [{"nf": co.nf, "clients": co.size,
                        "sub_rounds": co.n_sub, "dispatches": n_dispatch}
                       for co in plan.cohorts],
        "epochs": n_epochs, "dispatches": n_dispatch,
        "dispatches_per_epoch": n_dispatch / n_epochs,
        "exchange_every": k_ex,
        "exchange_rounds": exchange_rounds,
        "pool_bytes_gathered": pool_bytes,
        "state_bytes": state_bytes,
        **fed._fault_stats(heads_rejected),
        **fed._trust_stats()}
    sync()
    fed._sync = None
