"""Composable federation API: one policy description, two executors.

:class:`Federation` owns a set of :class:`~repro.core.hfl.FederatedClient`
objects, a :class:`~repro.core.policies.FederationPolicies` bundle (switch /
selection / transfer / pool — see `core/policies.py`), a shared
:class:`RoundSchedule`, and a :class:`Callback` list.  Both executors —
the ``sequential`` reference oracle and the ``batched`` fused engine —
consume the SAME policy description, so a new scenario (partial
participation, staleness bounds, softer selection, per-feature blending)
is one policy object, not two engine edits.

The batched executor fuses the ENTIRE federated epoch into one jitted
``lax.scan`` over sub-rounds (:func:`_make_epoch_fn`): each scan step runs
the vmapped Adam step on that round's R-slice and then the fused policy
round, with the per-epoch eval + save-best merge folded into the same
compiled function and the whole carried state donated, so an epoch is ONE
dispatch and zero host round-trips.  The policy bundle is a *static* jit
argument: every policy is a frozen (hashable) dataclass whose ``*_batched``
methods are traced straight into the scan, which is what preserves the
selection-identical guarantee between the two engines (pinned by
``tests/test_hfl_batched.py`` and ``tests/test_fused_epoch.py``).
Callbacks that need per-round delivery (see :class:`Callback`) fall back to
a chunked scan — the same compiled body dispatched per sub-round.

State — per-client params / optimizer state / validation history / best
snapshot, the head pool with per-entry ages, the host and device RNG
streams, and the epoch/round counters — lives on the Federation and its
clients, so :meth:`Federation.fit` is *resumable*: ``fit(epochs=k)`` runs k
more epochs, and :meth:`Federation.save` / :meth:`Federation.restore`
round-trip everything through ``repro.checkpoint`` for bit-identical
mid-training resumption.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.core import faults as FT
from repro.core import mesh_federation as MF
from repro.core import telemetry as TEL
from repro.core import trust as TR
from repro.core.hfl import (FederatedClient, HeadPool, HFLConfig,
                            _eval_mse, _train_step, pool_errors)
from repro.core.policies import FederationPolicies, policy_from_spec
from repro.kernels.pool_mlp import ops as pool_ops
from repro.optim import adam


# ---------------------------------------------------------------------------
# Round schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RoundSchedule:
    """The paper's training protocol skeleton, shared by every executor and
    by the non-federated benchmark loop: `epochs` epochs, one gradient step
    per R consecutive periods.

    ``exchange_every`` relaxes the pool-exchange cadence (bounded-staleness
    federation): a federated opportunity runs only on every k-th executed
    sub-round — sub-round ``r`` (0-based, counted within the epoch)
    exchanges iff ``(r + 1) % k == 0``, always on the sub-round's OWN probe
    batch.  The default k=1 is the paper's per-sub-round exchange,
    bit-identical to the historical behaviour.  The cadence resets at epoch
    boundaries, so an epoch with fewer than k sub-rounds never exchanges
    (the schedule tells you: ``exchanges(n_sub) == 0``).  Everything
    counted "per federated opportunity" follows the cadence: staleness ages
    (:class:`~repro.core.policies.MaxStaleness` ``max_age`` bounds exchange
    opportunities, not train sub-rounds), ``Federation.n_rounds``, and the
    selection log.  Semantics contract: docs/SCALING.md."""
    epochs: int
    R: int
    exchange_every: int = 1

    def __post_init__(self):
        if self.exchange_every < 1:
            raise ValueError(
                f"exchange_every must be >= 1 (1 = exchange every "
                f"sub-round, the paper's cadence), got {self.exchange_every}")

    def exchange_mask(self, n_sub: int) -> np.ndarray:
        """(n_sub,) bool: which within-epoch sub-rounds run a federated
        opportunity — ``(r + 1) % exchange_every == 0``."""
        return (np.arange(1, n_sub + 1) % self.exchange_every) == 0

    def exchanges(self, n_sub: int) -> int:
        """Federated opportunities per epoch of ``n_sub`` sub-rounds."""
        return n_sub // self.exchange_every

    def slices(self, n: int):
        """Sub-round batch slices over an n-sample train split.

        Only FULL R-batches are yielded: when n is not a multiple of R, the
        trailing partial batch of ``leftover(n)`` events is dropped — those
        events are never trained on, in any epoch.  :meth:`Federation.fit`
        announces this with a UserWarning so population sweeps over ragged
        lengths don't silently lose data (truncate to a multiple of R, or
        pick a divisor R, to silence it)."""
        for start in range(0, n - self.R + 1, self.R):
            yield slice(start, start + self.R)

    def sub_rounds(self, n: int) -> int:
        return max(0, (n - self.R) // self.R + 1)

    def leftover(self, n: int) -> int:
        """Trailing events per epoch that :meth:`slices` drops (0 when n is
        a multiple of R; n itself when n < R)."""
        return n - self.sub_rounds(n) * self.R


# ---------------------------------------------------------------------------
# Callbacks
# ---------------------------------------------------------------------------

class Callback:
    """Training hooks.  `fed` is the running Federation (None when invoked
    from the non-federated :func:`fit_local` loop).

    ``needs_per_round`` declares whether the callback must observe every
    ``on_round``.  The batched executor fuses a WHOLE epoch into one
    compiled dispatch when no callback needs per-round delivery; a callback
    that does forces the chunked path (one dispatch per sub-round, every
    ``on_round`` fired).  The default ``None`` auto-detects: overriding
    :meth:`on_round` opts in, leaving it untouched keeps the fused fast
    path.  Set it to ``False`` explicitly to keep the fused path even with
    an ``on_round`` override (the override then never fires on the batched
    engine), or ``True`` to force per-round delivery."""

    needs_per_round: Optional[bool] = None

    def on_fit_start(self, fed) -> None:
        """Once per :meth:`Federation.fit` call, before any training (and
        before the ragged-length UserWarning check)."""

    def on_round(self, fed, epoch: int, round_idx: int) -> None:
        """After each federated sub-round.  ``round_idx`` counts executed
        sub-rounds from 0 within the epoch.  On the batched engine this
        fires only on the chunked path (see ``needs_per_round``).  To read
        mid-epoch state there, go through :meth:`Federation.results` —
        it syncs the stacked loop state into the clients first; a direct
        ``fed.clients[i].params`` read is stale until then (current only
        on the sequential engine).  :meth:`Federation.save` is not valid
        here (mid-epoch saves raise)."""

    def on_epoch_end(self, fed, epoch: int, val: Dict[str, float],
                     active: Dict[str, bool]) -> None:
        """After each epoch: ``val`` maps client name -> this epoch's
        validation MSE, ``active`` maps client name -> whether its switch
        was active (it federated) this epoch.  Safe point for
        :meth:`Federation.save`."""

    def on_fit_end(self, fed, results) -> None:
        """Once per fit, after training: ``results`` is the
        :meth:`Federation.results` history dict."""


def _wants_per_round(cb: Callback) -> bool:
    """Resolve a callback's effective per-round need: the explicit
    ``needs_per_round`` flag if set, else whether it overrides
    :meth:`Callback.on_round`."""
    flag = getattr(cb, "needs_per_round", None)
    if flag is None:
        return type(cb).on_round is not Callback.on_round
    return bool(flag)


class VerboseLogger(Callback):
    """The engines' legacy per-epoch console line (a `*` marks clients whose
    switch was active this epoch), plus a wall-clock / throughput line:
    per-epoch wall time, client-rounds/s over the epoch (exchange
    opportunities actually run, the benchmarks' throughput unit), and —
    when the federation carries an enabled TelemetryPlan with the in-graph
    round series on — the latest pool staleness-age mean/max from the
    flight recorder."""

    def __init__(self):
        self._t0 = None
        self._rounds0 = None

    def on_fit_start(self, fed):
        self._t0 = time.perf_counter()
        self._rounds0 = (sum(fed.n_rounds.values())
                         if fed is not None else 0)

    def on_epoch_end(self, fed, epoch, val, active):
        engine = getattr(fed, "engine", None)
        tag = "hfl/batched" if engine == "batched" else "hfl"
        msg = " ".join(f"{n}={val[n]:.4f}{'*' if active.get(n) else ''}"
                       for n in val)
        print(f"[{tag}] epoch {epoch:3d} val: {msg}", flush=True)
        now = time.perf_counter()
        dt = now - self._t0 if self._t0 is not None else 0.0
        self._t0 = now
        if fed is None:
            print(f"[{tag}] epoch {epoch:3d} wall: {dt:.3f}s", flush=True)
            return
        total = sum(fed.n_rounds.values())
        done = total - (self._rounds0 or 0)
        self._rounds0 = total
        crs = done / dt if dt > 0 else 0.0
        line = (f"[{tag}] epoch {epoch:3d} wall: {dt:.3f}s "
                f"client-rounds/s: {crs:.1f}")
        rec = getattr(fed, "_recorder", None)
        ev = rec.last_round_event() if rec is not None else None
        if ev is not None and ev.get("age_mean") is not None:
            line += (f" staleness: {ev['age_mean']:.1f}"
                     f"/{ev['age_max']}")
        print(line, flush=True)


class MetricsCapture(Callback):
    """Records the per-epoch validation MSEs and switch activity."""

    def __init__(self):
        self.epochs: List[dict] = []

    def on_epoch_end(self, fed, epoch, val, active):
        self.epochs.append({"epoch": epoch, "val": dict(val),
                            "active": dict(active)})


class SaveBestCallback(Callback):
    """Persist the whole federation (Federation.save) whenever the
    population-mean validation MSE improves — disk-backed save-best."""

    def __init__(self, directory):
        self.directory = directory
        self.best = np.inf
        self.n_saves = 0

    def on_fit_start(self, fed):
        """Seed `best` from an existing checkpoint at `directory`, so a
        resumed run never clobbers a better historical best (the last
        checkpointed epoch is, by construction, the epoch that saved)."""
        m = Path(self.directory) / "manifest.json"
        if self.best == np.inf and m.exists():
            hist = json.loads(m.read_text())["val_histories"].values()
            if hist and all(h for h in hist):
                self.best = float(np.mean([h[-1] for h in hist]))

    def on_epoch_end(self, fed, epoch, val, active):
        if fed is None or not val:
            return
        m = float(np.mean(list(val.values())))
        if m < self.best:
            self.best = m
            fed.save(self.directory)
            self.n_saves += 1


# ---------------------------------------------------------------------------
# Sequential executor: one policy round for one client
# ---------------------------------------------------------------------------

def policy_round(client: FederatedClient, pool: HeadPool,
                 rng: np.random.Generator, policies: FederationPolicies,
                 *, use_kernel: bool = False) -> Optional[List[int]]:
    """One heterogeneous-transfer round for `client` (paper Fig. 6) under an
    explicit policy bundle.  Returns the selected pool indices per feature
    (positions in the sorted foreign pool), or None when there was nothing
    valid to select from."""
    if client._recent is None:
        return None
    stacked, keys = pool.stacked_for(client.name)
    if stacked is None:
        return None
    valid = pool.fresh_mask(client.name, policies.pool.max_age, keys=keys)
    if not valid.any():
        return None
    xd_R, y_R = client._recent
    sel = policies.selection
    chosen, sel_entries = [], []
    for i in range(client.nf):
        if sel.needs_errors:
            score_fn = (pool_ops.pool_mlp_errors if use_kernel
                        else pool_errors)
            errs = np.asarray(score_fn(stacked, jnp.asarray(xd_R[:, i]),
                                       jnp.asarray(y_R)))
            errs = np.where(valid, errs, np.inf)
        else:
            errs = None
        j = sel.select_host(errs, valid, rng)
        chosen.append(j)
        sel_entries.append(jax.tree_util.tree_map(lambda p: p[j], stacked))
    selected = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *sel_entries)
    client.params = dict(client.params)
    client.params["heads"] = policies.transfer.apply(client.params["heads"],
                                                     selected)
    return chosen


def _fit_sequential(fed: "Federation", n_epochs: int, cbs) -> None:
    """The reference oracle: a host-driven Python loop — per-client jitted
    train steps interleaved with per-client :func:`policy_round` calls in
    list order — that defines the semantics the batched engine must
    reproduce.  Handles heterogeneous nf and ragged data lengths."""
    pol = fed.policies
    C = len(fed.clients)
    use_kernel = fed.cfg.use_pool_kernel
    k_ex = fed.schedule.exchange_every
    admission = fed._admission()
    smask = fed._straggler_mask
    trust = fed._trust
    wm = trust.watermark if trust is not None else None
    dpn = trust.dp if trust is not None else None
    sa = trust.secure_agg if trust is not None else None
    gids = {c.name: fed._trust_ids[i] for i, c in enumerate(fed.clients)} \
        if trust is not None else {}
    rec = fed._recorder
    heads_rejected = 0
    n_exchange = 0            # executed sub-rounds that ran an exchange
    n_dispatch = 0            # jitted calls: train steps + Eq.-7 scorings +
                              # per-epoch evals (eager tree ops not counted)

    def publish(c, e_idx: int):
        """One publication opportunity for an active client: watermark
        verify + top-up, DP release, admission guard, pool write — the
        oracle twin of the fused body's publication tail (watermark and
        DP ride the SAME jnp functions the engines trace; only the DP
        noise stream is host-side — noise is engine-specific, like
        stochastic selection)."""
        nonlocal heads_rejected
        cand = c.params["heads"]
        if wm is not None:
            new_h, ok, _ = TR.wm_apply(cand, fed._wm_sig(c),
                                       strength=wm.strength,
                                       threshold=wm.threshold)
            c.params = dict(c.params)
            c.params["heads"] = new_h   # the client keeps its topped-up head
            if not bool(ok):            # tampered: block + count, stale row
                fed._wm_failures[c.name] += 1   # persists as evidence
                return
            cand = new_h
        if dpn is not None:
            cand, clipped = TR.dp_privatize_host(
                cand, dpn, fed._trust_wave_base + fed.epoch, e_idx,
                gids[c.name])
            if clipped:
                fed._clip_events += 1
        if admission is None or FT.heads_admissible(cand, admission):
            fed.pool.publish(c.name, cand, c.nf)
            if dpn is not None:
                fed._dp_counts[c.name] = fed._dp_counts.get(c.name, 0) + 1
        else:           # admission guard: the stale row persists
            heads_rejected += 1

    def secure_exchange(clients, active, e_idx: int):
        """The oracle's masked mean-transfer round: train results for the
        round's clients are stacked (zero-padded to max_nf for mixed
        populations) and handed to the SAME jitted ``trust.secure_round``
        the fused engines trace, so the masked blend matches the batched
        engine to float tolerance by construction; the host then publishes
        the masked payloads y = priv + mask, never a raw head."""
        nonlocal heads_rejected
        max_nf = max(c.nf for c in clients)
        wave = fed._trust_wave_base + fed.epoch
        tmpl = jax.tree_util.tree_map(
            np.asarray, TR.pad_rows(clients[0].params["heads"], max_nf))
        masks = TR.net_masks(sa, wave, 1,
                             [gids[c.name] for c in clients], tmpl,
                             round_offset=e_idx)
        act = np.array([active[c.name] for c in clients])
        corr = TR.mask_correction(masks, act)
        mask0 = jax.tree_util.tree_map(lambda m: jnp.asarray(m[0]), masks)
        corr0 = jax.tree_util.tree_map(lambda m: jnp.asarray(m[0]), corr)
        heads = TR.stack_trees_np(
            [TR.pad_rows(jax.tree_util.tree_map(np.asarray,
                                                c.params["heads"]), max_nf)
             for c in clients])
        heads = jax.tree_util.tree_map(jnp.asarray, heads)
        fv = np.zeros((len(clients), max_nf), bool)
        for i, c in enumerate(clients):
            fv[i, :c.nf] = True
        priv = None
        if dpn is not None:
            rel = [TR.dp_privatize_host(_tree_row(heads, i), dpn, wave,
                                        e_idx, gids[c.name])
                   for i, c in enumerate(clients)]
            fed._clip_events += sum(int(cl and act[i])
                                    for i, (_, cl) in enumerate(rel))
            priv = _stack_trees([r for r, _ in rel])
        dummy_age = jnp.zeros((len(clients),), jnp.int32)
        new_heads, _, _, _, rejected, _ = TR.secure_round_jit(
            heads, heads, dummy_age, jnp.asarray(act), mask0, corr0,
            jax.random.PRNGKey(0), priv=priv, feat_valid=jnp.asarray(fv),
            sa=sa, dp=None, nf=max_nf, admission=admission)
        rej = (np.zeros(len(clients), bool) if rejected is None
               else np.asarray(rejected))
        src = heads if priv is None else priv
        for i, c in enumerate(clients):
            if not act[i]:
                continue
            fed.n_rounds[c.name] += 1
            c.params = dict(c.params)
            c.params["heads"] = jax.tree_util.tree_map(
                lambda l: l[i, :c.nf], new_heads)
            if rej[i]:
                heads_rejected += 1
                continue
            y = jax.tree_util.tree_map(
                lambda p, m: np.asarray(p[i, :c.nf])
                + np.asarray(m[i, :c.nf]).astype(
                    np.asarray(p[i, :c.nf]).dtype),
                src, mask0)
            fed.pool.publish(c.name, y, c.nf)
            if dpn is not None:
                fed._dp_counts[c.name] = fed._dp_counts.get(c.name, 0) + 1

    for _ in range(n_epochs):
        epoch = fed.epoch
        mask = pol.switch.active_mask(
            [c.val_history for c in fed.clients], fed._switch_rng)
        if smask is not None:   # stragglers train but miss every exchange
            mask = np.asarray(mask, bool) & ~np.asarray(smask, bool)
        active = {c.name: bool(mask[i]) for i, c in enumerate(fed.clients)}
        iters = {c.name: c.train_epoch(R=fed.schedule.R)
                 for c in fed.clients}
        live = set(iters)
        rounds_start = sum(fed.n_rounds.values())
        fed._mid_epoch = True
        rnd = 0
        e_idx = 0               # exchange index within the epoch (the
                                # trust layer's mask/noise round key)
        while live:
            # bounded-staleness cadence: only every k-th executed sub-round
            # (within the epoch) is a federated opportunity — on the other
            # rounds clients just train, and the staleness clock stands
            # still (ages count exchange opportunities, not sub-rounds)
            exchange = (rnd + 1) % k_ex == 0
            # staleness clock: tick once per exchange round in which
            # federation can run (mirrors the batched engine's age array)
            ticked = not exchange or not (pol.pool.bounded and C >= 2
                                          and any(active[n] for n in live))
            progressed = False
            stepped = []
            for c in fed.clients:
                if c.name not in live:
                    continue
                try:
                    next(iters[c.name])
                except StopIteration:
                    live.discard(c.name)
                    continue
                progressed = True
                stepped.append(c)
                n_dispatch += 1
                if sa is not None or not exchange:
                    continue    # secure mode exchanges once, after training
                if not ticked:
                    fed.pool.tick()
                    ticked = True
                if active[c.name]:
                    sel = policy_round(c, fed.pool, fed._sel_rng, pol,
                                       use_kernel=use_kernel)
                    if sel is not None:
                        fed.selections[c.name].append(sel)
                        if pol.selection.needs_errors:
                            n_dispatch += c.nf
                    if trust is None:
                        fed.n_rounds[c.name] += 1
                        if admission is None or FT.heads_admissible(
                                c.params["heads"], admission):
                            fed.pool.publish(c.name, c.params["heads"], c.nf)
                        else:   # admission guard: the stale row persists
                            heads_rejected += 1
                    else:
                        fed.n_rounds[c.name] += 1
                        publish(c, e_idx)
            if sa is not None and exchange and progressed:
                # masked secure aggregation: one collective round over the
                # clients that trained this sub-round (mirrors the fused
                # engine's all-clients round)
                if not ticked:
                    fed.pool.tick()
                    ticked = True
                if any(active[c.name] for c in stepped) and C >= 2:
                    secure_exchange(fed.clients,
                                    {c.name: active[c.name]
                                     and c in stepped for c in fed.clients},
                                    e_idx)
                    n_dispatch += 1
            if progressed:
                if exchange and any(active.values()):
                    n_exchange += 1
                    e_idx += 1
                for cb in cbs:
                    cb.on_round(fed, epoch, rnd)
                rnd += 1
        for c in fed.clients:
            c.end_epoch()
        n_dispatch += C
        fed.epoch += 1
        fed._mid_epoch = False
        if rec is not None:
            done = sum(fed.n_rounds.values()) - rounds_start
            if done:
                rec.count("client_rounds", done)
        val = {c.name: c.val_history[-1] for c in fed.clients}
        for cb in cbs:
            cb.on_epoch_end(fed, epoch, val, active)
    if rec is not None and heads_rejected:
        rec.count("heads_rejected", int(heads_rejected))
    fed.dispatch_stats = {"engine": "sequential", "path": "per-round",
                          "devices": 1,
                          "epochs": n_epochs, "dispatches": n_dispatch,
                          "dispatches_per_epoch": n_dispatch / n_epochs,
                          "exchange_every": k_ex,
                          "exchange_rounds": n_exchange,
                          "pool_bytes_gathered": 0,
                          "state_bytes": sum(
                              _tree_bytes((c.params, c.opt_state,
                                           c.best_params))
                              for c in fed.clients),
                          **fed._fault_stats(heads_rejected),
                          **fed._trust_stats()}


# ---------------------------------------------------------------------------
# Batched executor: fused multi-client selection + transfer
# ---------------------------------------------------------------------------

def shard_argmin(errs_loc, offset):
    """One device's contribution to a sharded Eq.-7 argmin: per-feature
    ``(min error, GLOBAL flat index)`` over its contiguous pool chunk.
    ``jnp.argmin`` returns the first occurrence, so within the chunk ties
    already resolve to the lowest local index; adding the chunk ``offset``
    keeps global indices monotone in device order.  errs_loc: (nf, chunk);
    returns ((nf,) float values, (nf,) int32 global indices)."""
    li = jnp.argmin(errs_loc, axis=1)                              # (nf,)
    lv = jnp.take_along_axis(errs_loc, li[:, None], axis=1)[:, 0]
    return lv, (offset + li).astype(jnp.int32)


def merge_sharded_argmin(vals, gidx, ns: int):
    """Merge per-device :func:`shard_argmin` pairs into the GLOBAL argmin,
    reproducing ``jnp.argmin(errs, axis=1)`` on the full (nf, ns) matrix
    exactly — including its tie-break.

    The pinned tie-break rule (tests/test_sharded_policy.py): among tied
    minima the LOWEST flat pool index wins — ``argmin``'s first-occurrence
    semantics.  Chunks are contiguous and offsets monotone in device order,
    so taking the minimum global index among the devices achieving the
    minimum value reproduces it; a fully-stale pool (every error ``inf``,
    which ``inf == inf`` keeps comparable) resolves to index 0 on both
    paths.  vals/gidx: (D, nf); returns (nf,) int32."""
    m = jnp.min(vals, axis=0)                                      # (nf,)
    achieves = vals == m[None, :]
    return jnp.min(jnp.where(achieves, gidx, ns), axis=0).astype(jnp.int32)


def _policy_round_body(heads, pool_heads, pool_age, xd_R, y_R, active, key,
                       *, nf: int, policies: FederationPolicies,
                       use_kernel: bool, feat_valid=None, shard=None,
                       admission=None, trust=None, trust_sig=None,
                       telemetry=None):
    """One federated opportunity for ALL clients as a traceable scan over
    clients — the body both :func:`fused_policy_round` (standalone jit) and
    the fused-epoch scan (:func:`_make_epoch_fn`) trace.  The policy
    bundle's jittable ``select_batched`` / ``apply`` kernels are traced
    straight into the scan body, so a policy swap is a recompile, never an
    engine edit.

    The scan walks clients in their processing order, carrying the pool (and
    its per-publisher age vector) so that client i scores the heads already
    republished by clients < i in the same sub-round — exactly the
    sequential oracle's interleaving.

    heads, pool_heads: head params stacked to (C, nf, ...); pool_age: (C,)
    int32 opportunities-since-publication per pool row; xd_R: (C, R, nf, w);
    y_R: (C, R); active: (C,) bool; key: PRNG key.  Returns (new_heads,
    new_pool, new_age, chosen) where chosen is (C, nf) int32 flat indices
    into the row-major (client, feature) pool (-1 where the client was
    inactive or nothing valid was available).

    ``feat_valid`` opts into the heterogeneous (cohort-engine) form: a
    static (C, nf) bool array — here nf is ``max_nf``, the padded feature
    count — marking which rows of each client's padded head/probe stacks
    are real features.  Invalid rows are excluded from every selection,
    their blend results are discarded (padded head rows stay zero), and
    their ``chosen`` entries are -1.  ``None`` (the homogeneous engines)
    traces exactly the original body.

    ``shard`` opts into client-sharded Eq.-7 scoring (the mesh engines):
    an ``(axis_name, n_devices)`` pair naming the mesh axis this body runs
    under (via ``shard_map``).  Each device then scores only its contiguous
    ``ns / D`` chunk of the flattened pool per scan step — the pool itself
    stays replicated and is updated in lockstep, so the oracle's
    fresh-head visibility (client i sees clients < i's republications) is
    preserved exactly.  Selection policies with ``local_argmin`` reduce via
    per-device minima + :func:`merge_sharded_argmin` (two (D, nf)
    all-gathers per client); other error-based policies all-gather the
    full (nf, ns) error matrix and select replicated.  ``None`` (the
    single-device engines) traces exactly the unsharded body.

    ``admission`` opts into the in-graph pool admission guard (the fault-
    tolerance layer, ``core/faults.py``): a float L2 norm bound on any head
    tree a client tries to publish.  Before the pool write-back each
    candidate head is checked finite-and-within-bound; a rejected
    publication leaves the previous pool row AND its age untouched (the
    stale entry keeps aging under the staleness clock), and rows at the
    :data:`~repro.core.faults.QUARANTINE_AGE` sentinel are excluded from
    selection even under last-write-wins pools.  The body then returns a
    FIFTH output: the (C,) bool per-client rejection mask for this
    opportunity.  ``None`` (the default) traces exactly the original
    4-output body — the no-faults bit-identity pin.

    ``trust`` (a :class:`~repro.core.trust.TrustPlan` without secure_agg —
    the masked round bypasses this body entirely, see
    ``trust.secure_round``) opts into the trust layer's publication tail:
    with ``trust.watermark``, each active client's post-blend head is
    signature-verified and topped up (``trust.wm_apply`` on its row of
    the replicated ``trust_sig`` stack); a failed verification blocks the
    publication (the stale clean row persists) and is counted.  With
    ``trust.dp``, the publication candidate is clip+noise privatized
    in-graph (noise key = ``fold_in(key_i, 0x7D)`` — a stream the
    selection RNG never sees, which is what keeps ``trust=None``
    byte-identical).  The admission guard then checks the PRIVATIZED
    candidate (the actual release).  When ``trust`` is set the body
    returns one extra trailing output: a ``((C,) clip, (C,) wm_failed)``
    bool pair.  ``None`` traces exactly the pre-trust graph.

    ``telemetry`` (a :class:`~repro.core.telemetry.TelemetryPlan` with
    ``rounds`` on, or None) opts into the in-graph metrics carry: the body
    additionally returns, as its LAST output, a ``((C,) score_min, (C,)
    score_mean)`` float32 pair — the Eq.-7 score distribution each client
    saw over its valid candidates this opportunity (``inf`` / 0 when the
    selection policy scores nothing).  On the sharded ``local_argmin``
    path the aggregates reduce with ``pmin`` / ``psum`` so they come back
    replicated.  ``None`` traces exactly the pre-telemetry graph (the
    bit-identity pin, mirroring ``faults=None`` / ``trust=None``)."""
    if trust is not None and trust.secure_agg is not None:
        raise ValueError(
            "masked secure aggregation replaces the selection round "
            "entirely (trust.secure_round) — it never reaches "
            "_policy_round_body")
    C = y_R.shape[0]
    ns = C * nf
    sel, transfer, poolp = policies.selection, policies.transfer, policies.pool
    bounded = poolp.bounded
    if feat_valid is not None:
        fv = jnp.asarray(np.asarray(feat_valid, bool))          # (C, nf)
        valid_flat = fv.reshape(ns)

    def flat(pool):
        return jax.tree_util.tree_map(
            lambda p: p.reshape((ns,) + p.shape[2:]), pool)

    def body(carry, inp):
        heads, pool, age = carry
        i, key_i = inp
        fp = flat(pool)
        own = (jnp.arange(ns) // nf) == i
        if feat_valid is not None:
            own = own | ~valid_flat          # padded rows are never sources
        if bounded:
            # quarantined rows sit at age QUARANTINE_AGE > any max_age, so
            # the staleness exclusion already hides them
            excluded = own | jnp.repeat(age > poolp.max_age, nf)
            any_valid = jnp.any(~excluded)
        elif admission is not None or trust is not None:
            # last-write-wins pool under the admission guard or the trust
            # layer: quarantined seed rows (zeroed, age = QUARANTINE_AGE —
            # inadmissible or watermark-failed at seeding) must still be
            # hidden, exactly as the oracle's fresh_mask hides them
            excluded = own | jnp.repeat(age >= FT.QUARANTINE_AGE, nf)
            any_valid = jnp.any(~excluded)
        else:
            excluded = own
            # C >= 2 enforced by the caller; with a padded pool every
            # foreign client still contributes >= 1 valid feature row
            any_valid = jnp.bool_(True)
        def score(pool_rows, valid_rows):
            """Eq.-7 errors of ``pool_rows`` (full pool or a device chunk)
            against client i's probe batch — row-independent, so a chunk
            sweep equals the corresponding slice of the full sweep."""
            with jax.named_scope("eq7_score"):
                xd_i = jnp.moveaxis(xd_R[i], 1, 0)          # (nf, R, w)
                if use_kernel:
                    if valid_rows is not None:
                        return pool_ops.pool_mlp_errors_shard(
                            pool_rows, xd_i, y_R[i], valid_rows)
                    return pool_ops.pool_mlp_errors_features(
                        pool_rows, xd_i, y_R[i])
                return jax.vmap(
                    lambda xf: pool_errors(pool_rows, xf, y_R[i]))(xd_i)

        valid_arg = valid_flat if feat_valid is not None else None
        if sel.needs_errors:
            if shard is None:
                errs = jnp.where(excluded[None, :], jnp.inf,
                                 score(fp, valid_arg))          # (nf, ns)
            else:
                # client-sharded scoring: this device's contiguous chunk of
                # the flattened pool (C % D == 0 so ns % D == 0)
                axis, D = shard
                chunk = ns // D
                off = jax.lax.axis_index(axis) * chunk
                take = lambda v: jax.lax.dynamic_slice_in_dim(v, off,
                                                              chunk, 0)
                fp_loc = jax.tree_util.tree_map(take, fp)
                errs_loc = score(
                    fp_loc, take(valid_arg) if valid_arg is not None
                    else None)
                errs_loc = jnp.where(take(excluded)[None, :], jnp.inf,
                                     errs_loc)                  # (nf, chunk)
                if sel.local_argmin:
                    # small reduce: per-device (min, global index) pairs
                    lv, gi = shard_argmin(errs_loc, off)
                    j = merge_sharded_argmin(jax.lax.all_gather(lv, axis),
                                             jax.lax.all_gather(gi, axis),
                                             ns)
                    errs = None
                else:
                    # the policy needs the full error distribution: gather
                    # the chunks back to (nf, ns) and select replicated
                    errs = jax.lax.all_gather(errs_loc, axis, axis=1,
                                              tiled=True)
        else:
            errs = None
        # padded pools always pass bounded=True: the exclusion mask is
        # non-trivial even under last-write-wins, so selection policies must
        # take their masked path (see SelectionPolicy.select_batched)
        if shard is None or not (sel.needs_errors and sel.local_argmin):
            j = sel.select_batched(errs, excluded, key_i, nf=nf, ns=ns, i=i,
                                   bounded=bounded or feat_valid is not None
                                   or admission is not None)
        selected = jax.tree_util.tree_map(lambda p: p[j], fp)      # (nf, ...)
        mine = jax.tree_util.tree_map(lambda h: h[i], heads)
        blended = transfer.apply(mine, selected)
        act = active[i] & any_valid
        if feat_valid is not None:
            mask_i = act & fv[i]                               # (nf,)
            new_mine = jax.tree_util.tree_map(
                lambda b, m: jnp.where(
                    mask_i.reshape((nf,) + (1,) * (m.ndim - 1)), b, m),
                blended, mine)
        else:
            new_mine = jax.tree_util.tree_map(
                lambda b, m: jnp.where(act, b, m), blended, mine)
        # publication: active clients overwrite their pool row (age resets),
        # inactive clients' stale entries persist (the pool policy decides
        # how long they stay *visible*)
        pub = active[i]
        if trust is not None and trust.watermark is not None:
            # signature verify + top-up on the client's OWN head: the
            # topped head persists in its params (so the honest watermark
            # never decays through Eq.-8 blending); a failed verification
            # (a sign-flipped head projects at -strength) blocks the
            # publication and leaves the head untouched as evidence
            sig_i = jax.tree_util.tree_map(lambda s: s[i], trust_sig)
            topped, wm_ok, _ = TR.wm_apply(
                new_mine, sig_i, strength=trust.watermark.strength,
                threshold=trust.watermark.threshold)
            new_mine = jax.tree_util.tree_map(
                lambda t, m: jnp.where(pub, t, m), topped, new_mine)
            wmf_i = pub & ~wm_ok
            pub = pub & wm_ok
        else:
            wmf_i = jnp.zeros((), bool)
        heads = jax.tree_util.tree_map(
            lambda h, m: h.at[i].set(m), heads, new_mine)
        cand = new_mine
        if trust is not None and trust.dp is not None:
            # the DP release: what actually reaches the pool is the
            # clipped+noised candidate; the client's own params keep the
            # raw head.  The noise key forks off the selection key on a
            # dedicated stream, so the selection RNG sequence (and with
            # it the trust=None graph) is untouched.
            cand, clipped = TR.dp_privatize(
                cand, jax.random.fold_in(key_i, 0x7D),
                clip=trust.dp.clip, sigma=trust.dp.sigma)
            if feat_valid is not None:
                # padded rows stay zero in the pool (noise on a row the
                # client does not own is never a release)
                cand = jax.tree_util.tree_map(
                    lambda l: jnp.where(
                        fv[i].reshape((nf,) + (1,) * (l.ndim - 1)), l, 0),
                    cand)
            clip_i = pub & clipped
        else:
            clip_i = jnp.zeros((), bool)
        if admission is not None:
            # pool admission guard: a candidate head must be finite and
            # within the L2 norm bound, or the publication is rejected —
            # the previous (clean) row and its age survive untouched
            sq = sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
                     for leaf in jax.tree_util.tree_leaves(cand))
            ok = jnp.isfinite(sq) & (sq <= jnp.float32(admission) ** 2)
            rejected_i = pub & ~ok
            pub = pub & ok
        pool = jax.tree_util.tree_map(
            lambda pl, m: pl.at[i].set(jnp.where(pub, m, pl[i])),
            pool, cand)
        age = age.at[i].set(jnp.where(pub, 0, age[i]))
        if feat_valid is not None:
            chosen = jnp.where(act & fv[i], j, -1).astype(jnp.int32)
        else:
            chosen = jnp.where(act, j, -1).astype(jnp.int32)
        if admission is not None and trust is not None:
            ys = (chosen, rejected_i, (clip_i, wmf_i))
        elif admission is not None:
            ys = (chosen, rejected_i)
        elif trust is not None:
            ys = (chosen, (clip_i, wmf_i))
        else:
            ys = chosen
        if telemetry is not None:
            # the metrics carry: client i's Eq.-7 score aggregates over its
            # masked candidate pool.  Excluded entries score inf, so the
            # min is the winning score and the mean runs over the finite
            # (valid) candidates; policies that never score (and secure
            # rounds, which bypass this body) report the inf/0 sentinels.
            if sel.needs_errors and errs is not None:
                fin = jnp.isfinite(errs)
                smin_i = jnp.min(errs)
                smean_i = jnp.sum(jnp.where(fin, errs, 0.0)) \
                    / jnp.maximum(jnp.sum(fin), 1)
            elif sel.needs_errors:
                # sharded local_argmin path: the error matrix stayed
                # device-local — reduce the aggregates collectively so
                # they come back replicated
                axis, _D = shard
                fin = jnp.isfinite(errs_loc)
                smin_i = jax.lax.pmin(jnp.min(errs_loc), axis)
                smean_i = jax.lax.psum(
                    jnp.sum(jnp.where(fin, errs_loc, 0.0)), axis) \
                    / jnp.maximum(jax.lax.psum(jnp.sum(fin), axis), 1)
            else:
                smin_i = jnp.asarray(jnp.inf)
                smean_i = jnp.asarray(0.0)
            tele_i = (smin_i.astype(jnp.float32),
                      smean_i.astype(jnp.float32))
            ys = (ys if isinstance(ys, tuple) else (ys,)) + (tele_i,)
        return (heads, pool, age), ys

    keys = jax.random.split(key, C)
    (heads, pool_heads, pool_age), ys = jax.lax.scan(
        body, (heads, pool_heads, pool_age), (jnp.arange(C), keys))
    if telemetry is not None:
        tele = ys[-1]
        ys = ys[:-1]
        if len(ys) == 1:
            ys = ys[0]
    if admission is not None and trust is not None:
        chosen, rejected, tstats = ys
        out = (heads, pool_heads, pool_age, chosen, rejected, tstats)
    elif admission is not None:
        chosen, rejected = ys
        out = (heads, pool_heads, pool_age, chosen, rejected)
    elif trust is not None:
        chosen, tstats = ys
        out = (heads, pool_heads, pool_age, chosen, tstats)
    else:
        out = (heads, pool_heads, pool_age, ys)
    if telemetry is not None:
        out = out + (tele,)
    return out


@functools.partial(jax.jit, static_argnames=("nf", "policies", "use_kernel"))
def fused_policy_round(heads, pool_heads, pool_age, xd_R, y_R, active, key,
                       *, nf: int, policies: FederationPolicies,
                       use_kernel: bool):
    """Standalone jitted :func:`_policy_round_body` — ONE federated
    opportunity per dispatch.  The fused-epoch engine no longer dispatches
    this per round (it traces the body into its epoch scan); it remains the
    single-round entry point for diagnostics and benchmarks."""
    return _policy_round_body(heads, pool_heads, pool_age, xd_R, y_R,
                              active, key, nf=nf, policies=policies,
                              use_kernel=use_kernel)


def _is_host(x) -> bool:
    return isinstance(x, (np.ndarray, np.generic))


def _stack_leaf(*xs):
    """np.stack when every row is a host array (a byte copy, placed on the
    device later in one transfer per fit), else jnp.stack: stacking host
    rows with jnp.stack would send each row to the device on its own."""
    return np.stack(xs) if all(map(_is_host, xs)) else jnp.stack(xs)


def _stack_trees(trees):
    """Stack a list of same-structure pytrees leaf-wise on a new leading
    axis — the batched engine's (C, ...) client stacking, per leaf on the
    host or on the device as :func:`_stack_leaf` picks."""
    return jax.tree_util.tree_map(_stack_leaf, *trees)


def _stack_data(arrays) -> np.ndarray:
    """Stack the clients' data arrays of one split on the host."""
    return np.stack([np.asarray(a) for a in arrays])


def _count_restack(rec, *trees) -> None:
    """Count the stacked state's leaves by where they were stacked: their
    ratio is the share of restacks that took the host path."""
    if rec is None:
        return
    leaves = jax.tree_util.tree_leaves(trees)
    n_host = sum(map(_is_host, leaves))
    for name, n in (("restack_host_leaves", n_host),
                    ("restack_device_leaves", len(leaves) - n_host)):
        if n:
            rec.count(name, n)


def _to_host(tree):
    """One device->host copy of a stacked tree, its leaves made read-only:
    the client rows handed out as views of it stay as immutable as the
    device arrays they replace."""
    host = jax.device_get(tree)
    for leaf in jax.tree_util.tree_leaves(host):
        leaf.setflags(write=False)
    return host


def _tree_bytes(tree) -> int:
    """Total payload bytes of a pytree's leaves (comms accounting)."""
    return int(sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree_util.tree_leaves(tree)))


def _exchange_round_bytes(D: int, heads_bytes: int, probe_bytes: int,
                          C: int, nf: int, ns: int, selection) -> int:
    """Analytic per-device bytes one mesh exchange round moves — what
    ``dispatch_stats["pool_bytes_gathered"]`` accumulates: the pool-
    candidate heads + probe-batch all-gathers, plus the per-client score
    reduce (two tiny (D, nf) pairs under ``local_argmin`` selection, the
    full (nf, ns) float32 error matrix otherwise, nothing for policies
    that skip Eq.-7 scoring)."""
    if selection.needs_errors:
        if selection.local_argmin:
            reduce_b = C * D * nf * 8       # f32 minima + int32 indices
        else:
            reduce_b = C * nf * ns * 4      # gathered (nf, ns) errors
    else:
        reduce_b = 0
    return heads_bytes + probe_bytes + reduce_b


def stack_pool(pool: HeadPool, names: Sequence[str], nf: int):
    """A HeadPool's entries as the batched engine's stacked ``(C, nf, ...)``
    tree — the one place that defines the stacked pool layout, shared by
    the executor and by benchmarks profiling its building blocks."""
    return _stack_trees(
        [_stack_trees([pool.entries[(n, f)] for f in range(nf)])
         for n in names])


def _hold_client_copies_on_host(fed) -> None:
    """Move the clients' own params / Adam state / best params and the
    pool entries to the host.  A mesh fit calls this once its state is
    partitioned: until its ``sync()`` writes rows back they are stale
    copies, which would otherwise sit whole on the default device."""
    for c in fed.clients:
        c.params, c.opt_state, c.best_params = jax.device_get(
            (c.params, c.opt_state, c.best_params))
    fed.pool.entries = jax.device_get(fed.pool.entries)


def _tree_row(tree, i):
    """Client i's slice of a stacked (C, ...) tree (of a host tree: array
    views, 0-d for a (C,) leaf)."""
    return jax.tree_util.tree_map(lambda p: p[i, ...], tree)


def _selection_lut(names: Sequence[str], nf: int) -> np.ndarray:
    """Map the batched engine's row-major (client, feature) flat pool index
    to the sequential oracle's excluded, sorted-by-(name, feature) index —
    so both engines log identical selections."""
    C = len(names)
    lut = np.full((C, C * nf), -1, np.int64)
    for i in range(C):
        others = sorted((names[j], j) for j in range(C) if j != i)
        for rank, (_, j) in enumerate(others):
            for g in range(nf):
                lut[i, j * nf + g] = rank * nf + g
    return lut


@functools.lru_cache(maxsize=None)
def _make_batched_fns(lr: float):
    """vmap-over-clients versions of the exact same per-client step/eval the
    sequential engine jits (see hfl._train_step / hfl._eval_mse)."""
    opt = adam(lr)
    step = jax.jit(jax.vmap(functools.partial(_train_step, opt)))
    evaluate = jax.jit(jax.vmap(_eval_mse))
    return step, evaluate


def _epoch_body(lr: float, nf: int, policies: FederationPolicies,
                use_kernel: bool, do_federate: bool, do_eval: bool, *,
                exchange_every: int = 1, gather=None, local_rows=None,
                shard=None, admission=None, trust=None, telemetry=None):
    """The fused whole-epoch computation shared by BOTH batched backends:
    a scan over the epoch's sub-rounds (vmapped Adam step on that round's
    R-slice, then the fused policy round), with the per-epoch validation
    eval and save-best ``where``-merge folded in when ``do_eval``.

    ``gather`` / ``local_rows`` are the pool-exchange hooks — the ONLY
    point where the two backends differ.  Identity (the default) on the
    single-device path, where every array already holds all C clients.
    The mesh backend (``repro.core.mesh_federation``) injects an
    all-gather along the `clients` axis (pool candidates + probe batches
    to the global client order) and a dynamic-slice taking the device's
    own client block back out of the blended heads; the probe gathers are
    issued BEFORE the train step, which has no data dependency on them, so
    XLA's scheduler may overlap the collective with the step's compute.
    ``shard`` is forwarded to :func:`_policy_round_body` (client-sharded
    Eq.-7 scoring).

    ``exchange_every`` = k > 1 (with ``do_federate``) restructures the scan
    into SEGMENTS: an outer scan over groups of k sub-rounds whose body
    runs k-1 train-only steps plus one train+exchange step on the group's
    last sub-round (its own R-batch is the probe batch, exactly the
    oracle's ``_recent``), then a train-only scan over the ``n_sub % k``
    leftover rounds.  No ``lax.cond`` around collectives — the cadence is
    static, so the mesh path segments identically on every device.  k=1
    traces the historical flat scan unchanged (the bit-identity pin).

    ``admission`` (a norm bound, or None) forwards to
    :func:`_policy_round_body`'s pool admission guard; when set, the epoch
    function returns ONE extra trailing output — the stacked
    ``(exchange_rounds, C)`` bool per-opportunity rejection mask.

    ``trust`` (a :class:`~repro.core.trust.TrustPlan`, or None) threads the
    trust layer through the scan.  The epoch function then takes ONE extra
    trailing runtime argument ``trust_arrays`` — the watermark signature
    stack (C, nf, ...) under ``trust.watermark``, the host-derived
    ``(net_masks, correction)`` pair (leading axis = this epoch's exchange
    rounds, consumed as an extra scan leg) under ``trust.secure_agg``, an
    ignored dummy under DP-only — and returns one extra trailing output
    AFTER the admission mask: the stacked ``((rounds, C) clip, (rounds, C)
    wm_failed)`` bool pair.  Secure aggregation replaces the per-client
    selection scan with ``trust.secure_round`` (masked mean transfer — the
    pool stores masked payloads, ``chosen`` is all -1).  ``trust=None``
    traces the byte-identical pre-trust graph (the bit-identity pin,
    mirroring ``faults=None``).

    ``telemetry`` (a :class:`~repro.core.telemetry.TelemetryPlan` with
    ``rounds`` on, or None) threads the in-graph metrics carry through the
    scan: the epoch function returns one extra LAST output — the stacked
    per-exchange-round series ``((rounds, C) foreign-pick counts,
    (rounds, C) score_min, (rounds, C) score_mean, (rounds, C) pool_age
    snapshots)`` — still inside the same single dispatch.  Unpack order at
    every call site: telemetry pops FIRST (it is appended last), then
    trust, then admission.  ``telemetry=None`` traces the byte-identical
    pre-instrumentation graph.

    The computation carries the named scopes ``train_step``,
    ``policy_round`` (``eq7_score`` inside) and ``eval_best``: metadata on
    its ops, which a device profile reads, and nothing else."""
    opt = adam(lr)
    step = jax.vmap(functools.partial(_train_step, opt))
    evaluate = jax.vmap(_eval_mse)
    bounded = policies.pool.bounded
    k_ex = int(exchange_every)
    secure = trust is not None and trust.secure_agg is not None
    # secure masks ride the scan as an extra xs leg only when the scan
    # actually exchanges; a do_federate=False dispatch ignores them
    secure_in_scan = secure and do_federate
    sel_trust = None if secure else trust
    if gather is None:
        gather = lambda t: t
    if local_rows is None:
        local_rows = lambda t: t

    def epoch(params, opt_state, pool_heads, pool_age, key, best_val,
              best_params, xs_r, xd_r, y_r, active, val_xs, val_xd, val_y,
              trust_arrays=None):
        C = active.shape[0]
        n_sub = y_r.shape[0]

        def body(carry, batch):
            params, opt_state, pool_heads, pool_age, key = carry
            if secure_in_scan:
                (xs_b, xd_b, y_b), (mask_e, corr_e) = batch
            else:
                xs_b, xd_b, y_b = batch
            if do_federate and not secure:  # secure needs no probe gathers
                xd_g, y_g = gather(xd_b), gather(y_b)   # overlaps the step
            with jax.named_scope("train_step"):
                params, opt_state, _ = step(params, opt_state, xs_b, xd_b,
                                            y_b)
            if do_federate:
                if bounded:
                    pool_age = pool_age + 1
                key, sub = jax.random.split(key)
                if secure:
                    with jax.named_scope("policy_round"):
                        (new_heads, pool_heads, pool_age, chosen, rej,
                         clip) = TR.secure_round(
                            gather(params["heads"]), pool_heads, pool_age,
                            active, mask_e, corr_e, sub,
                            sa=trust.secure_agg, dp=trust.dp, nf=nf,
                            admission=admission)
                    tstats = (clip, jnp.zeros((C,), bool))
                else:
                    with jax.named_scope("policy_round"):
                        out = _policy_round_body(
                            gather(params["heads"]), pool_heads, pool_age,
                            xd_g, y_g, active, sub, nf=nf,
                            policies=policies, use_kernel=use_kernel,
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
                params = {**params, "heads": local_rows(new_heads)}
            else:
                chosen = jnp.full((C, nf), -1, jnp.int32)
                if admission is not None:
                    rej = jnp.zeros((C,), bool)
                if trust is not None:
                    tstats = (jnp.zeros((C,), bool), jnp.zeros((C,), bool))
            if telemetry is not None:
                if not do_federate or secure:
                    # a non-exchanging (or masked secure) round scores
                    # nothing: the series carry the inf/0 sentinels
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
            return (params, opt_state, pool_heads, pool_age, key), ys

        def train_only(carry, batch):
            params, opt_state, pool_heads, pool_age, key = carry
            xs_b, xd_b, y_b = batch
            with jax.named_scope("train_step"):
                params, opt_state, _ = step(params, opt_state, xs_b, xd_b,
                                            y_b)
            return (params, opt_state, pool_heads, pool_age, key), None

        carry = (params, opt_state, pool_heads, pool_age, key)
        if not do_federate or k_ex == 1:
            # the historical flat scan — one (train, exchange?) step per
            # sub-round; exchange_every=1 must stay bit-identical to it
            xs = (xs_r, xd_r, y_r)
            if secure_in_scan:
                xs = (xs, trust_arrays)
            carry, ys = jax.lax.scan(body, carry, xs)
        else:
            n_grp, rem = divmod(n_sub, k_ex)
            grouped = jax.tree_util.tree_map(
                lambda t: t[:n_grp * k_ex].reshape(
                    (n_grp, k_ex) + t.shape[1:]),
                (xs_r, xd_r, y_r))

            def group(carry, batch_k):
                # k-1 train-only rounds, then train + exchange on the
                # group's LAST round (probes = that round's own R-batch)
                if secure_in_scan:
                    batch_k, masks_e = batch_k
                carry, _ = jax.lax.scan(
                    train_only, carry,
                    jax.tree_util.tree_map(lambda t: t[:k_ex - 1], batch_k))
                last = jax.tree_util.tree_map(lambda t: t[k_ex - 1], batch_k)
                if secure_in_scan:
                    last = (last, masks_e)
                return body(carry, last)

            xs = (grouped, trust_arrays) if secure_in_scan else grouped
            carry, ys = jax.lax.scan(group, carry, xs)
            if rem:                       # leftover rounds never exchange
                carry, _ = jax.lax.scan(
                    train_only, carry,
                    jax.tree_util.tree_map(lambda t: t[n_grp * k_ex:],
                                           (xs_r, xd_r, y_r)))
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
        (params, opt_state, pool_heads, pool_age, key) = carry
        if do_eval:
            with jax.named_scope("eval_best"):
                v = evaluate(params, val_xs, val_xd, val_y)  # (local,)
                improved = v < best_val
                best_val = jnp.where(improved, v, best_val)
                n_loc = v.shape[0]
                best_params = jax.tree_util.tree_map(
                    lambda b, p: jnp.where(
                        improved.reshape((n_loc,) + (1,) * (p.ndim - 1)),
                        p, b),
                    best_params, params)
        else:
            v = None
        out = (params, opt_state, pool_heads, pool_age, key, best_val,
               best_params, v, chosen)
        if admission is not None:
            out = out + (rejected,)
        if trust is not None:
            out = out + (tstats,)
        if telemetry is not None:
            out = out + (tele,)
        return out

    return epoch


@functools.lru_cache(maxsize=None)
def _make_epoch_fn(lr: float, nf: int, policies: FederationPolicies,
                   use_kernel: bool, do_federate: bool, do_eval: bool,
                   exchange_every: int = 1, admission=None, trust=None,
                   telemetry=None):
    """Compile-cached whole-epoch function: ONE dispatch scans every
    sub-round of an epoch — the vmapped Adam step on that round's R-slice,
    then the fused policy round (selection, blend, publish, aging, RNG
    fold-in) — and, when ``do_eval``, folds the per-epoch validation eval
    and the save-best ``where``-merge into the same compiled function.
    The computation itself is :func:`_epoch_body` with identity exchange
    hooks; the client-sharded twin wraps the same body in ``shard_map``
    (``mesh_federation._make_mesh_epoch_fn``).

    The whole carried state (stacked params, opt state, pool, ages, PRNG
    key, best-val, best-params) is DONATED, so XLA reuses the stacked
    buffers across epochs instead of copying them every dispatch.  The
    per-round ``chosen`` indices come back stacked ``(n_rounds, C, nf)``
    as a scan output: selection traces materialize in one device-to-host
    transfer per epoch, not one per round.

    The cache key is the trace-relevant statics — (lr, nf, policies,
    use_kernel, do_federate, do_eval, exchange_every); jit itself caches
    per shape, so one factory entry serves every (C, n_rounds, R)
    geometry.  The chunked fallback (per-round callbacks) dispatches the
    same function over 1-round slices with ``do_eval`` only on the last
    chunk and the exchange cadence applied through per-round
    ``do_federate`` gating (a non-exchange round IS a ``do_federate=False``
    round)."""
    epoch = _epoch_body(lr, nf, policies, use_kernel, do_federate, do_eval,
                        exchange_every=exchange_every, admission=admission,
                        trust=trust, telemetry=telemetry)
    return jax.jit(epoch, donate_argnums=(0, 1, 2, 3, 4, 5, 6))


def _is_homogeneous(clients: Sequence[FederatedClient]) -> bool:
    """The single-stack fast path's precondition: every client has the same
    feature count nf AND identical train/valid/test array shapes (the
    per-client state is stacked on a leading axis and scanned as one
    geometry).  Mixed populations no longer error — they route through the
    cohort engine (``repro.core.cohorts``), which partitions them into
    homogeneous cohorts and exchanges heads through a padded union pool."""
    nf = clients[0].nf
    shapes = [tuple(np.shape(a) for a in c.train) for c in clients]
    return (all(c.nf == nf for c in clients) and len(set(shapes)) == 1
            and len({tuple(np.shape(a) for a in c.valid)
                     for c in clients}) == 1
            and len({tuple(np.shape(a) for a in c.test)
                     for c in clients}) == 1)


def _fit_batched(fed: "Federation", n_epochs: int, cbs) -> None:
    """The batched executor: stack the population, scan whole epochs inside
    one compiled dispatch (see :func:`_make_epoch_fn`), and — when the
    Federation carries a multi-device mesh — run that same scan client-
    sharded under ``shard_map`` (see ``repro.core.mesh_federation``).
    Heterogeneous populations (mixed nf / ragged split lengths) route
    through the cohort engine (``repro.core.cohorts._fit_cohorted``), which
    reproduces the same oracle semantics via per-cohort stacks and a padded
    union pool.  Writes results back into the clients via :func:`sync` and
    fills ``fed.dispatch_stats``."""
    clients = fed.clients
    if not _is_homogeneous(clients):
        from repro.core import cohorts
        cohorts._fit_cohorted(fed, n_epochs, cbs)
        return
    C = len(clients)
    names = [c.name for c in clients]
    nf = clients[0].nf
    cfg, pol = fed.cfg, fed.policies
    R = fed.schedule.R
    # telemetry layer (core/telemetry.py): `tele` is the enabled plan iff
    # its in-graph per-round series is on (a static jit argument, so
    # tele=None traces the byte-identical pre-instrumentation graph); `rec`
    # is the host-side flight recorder (spans + counters + round events)
    tele = fed._tele_rounds()
    rec = fed._recorder
    n_sub = fed.schedule.sub_rounds(int(np.shape(clients[0].train[2])[0]))

    def rounds_axis(t):
        """(C, n, ...) -> (n_sub, C, R, ...): the schedule's R-slices stacked
        on a leading scan axis (the slices are contiguous from 0, so this is
        a reshape + transpose, done once per fit)."""
        m = n_sub * R
        return np.moveaxis(
            t[:, :m].reshape((C, n_sub, R) + t.shape[2:]), 1, 0)

    # client-sharded execution: with a multi-device mesh the stacked state
    # is partitioned over the `clients` axis once per fit (subsequent
    # epochs carry the shardings through the donated outputs) and the
    # epoch function is the shard_map twin of _make_epoch_fn
    mesh = fed._exec_mesh()
    key = fed._key
    with TEL.span(rec, "restack"):
        xs_r, xd_r, y_r = (
            rounds_axis(_stack_data([c.train[k] for c in clients]))
            for k in range(3))
        val = tuple(_stack_data([c.valid[k] for c in clients])
                    for k in range(3))
        params = _stack_trees([c.params for c in clients])
        opt_state = _stack_trees([c.opt_state for c in clients])
        # pool state comes from the canonical HeadPool (a fresh fit sees
        # the initial publication; a restored fit sees the checkpointed
        # pool)
        pool_heads = stack_pool(fed.pool, names, nf)
        pool_age = np.asarray([fed.pool.age_of(n_) for n_ in names],
                              np.int32)
        best_val = np.asarray([c.best_val for c in clients], np.float32)
        best_params = _stack_trees([c.best_params for c in clients])
        _count_restack(rec, params, opt_state, best_params, pool_heads)
        # one placement per fit, straight from the host on a mesh
        if mesh is not None:
            (params, opt_state, pool_heads, pool_age, key, best_val,
             best_params, (xs_r, xd_r, y_r), val) = MF.shard_fit_state(
                mesh, nf, cfg.w, C, params=params, opt_state=opt_state,
                pool_heads=pool_heads, pool_age=pool_age, key=key,
                best_val=best_val, best_params=best_params,
                rounds_data=(xs_r, xd_r, y_r), val_data=val)
            _hold_client_copies_on_host(fed)
        else:
            (params, opt_state, pool_heads, pool_age, best_val,
             best_params, (xs_r, xd_r, y_r), val) = jax.device_put(
                (params, opt_state, pool_heads, pool_age, best_val,
                 best_params, (xs_r, xd_r, y_r), val))
    use_kernel = cfg.use_pool_kernel
    lut = _selection_lut(names, nf)
    admission = fed._admission()
    smask = fed._straggler_mask
    trust = fed._trust
    secure = trust is not None and trust.secure_agg is not None
    # host templates/derivations the trust layer needs (captured before the
    # stacked state is donated away)
    head_tmpl = jax.tree_util.tree_map(
        np.asarray, clients[0].params["heads"]) if secure else None
    sig_stack = None
    if trust is not None and trust.watermark is not None:
        sig_stack = jax.tree_util.tree_map(
            jnp.asarray,
            TR.stack_trees_np([fed._wm_sig(c) for c in clients]))
    clip_total = 0
    wm_fail = np.zeros(C, np.int64)
    dp_pubs = np.zeros(C, np.int64)
    heads_rejected = 0
    k_ex = fed.schedule.exchange_every
    exch_mask = fed.schedule.exchange_mask(n_sub)
    n_exch_epoch = fed.schedule.exchanges(n_sub)
    exchange_rounds = 0
    pool_bytes = 0
    # per-device bytes one mesh exchange round moves (0 on a single device)
    heads_bytes = _tree_bytes(pool_heads)
    probe_bytes = C * R * (nf * cfg.w + 1) * 4
    exch_bytes = _exchange_round_bytes(
        MF.mesh_devices(fed._exec_mesh()), heads_bytes, probe_bytes,
        C, nf, C * nf, pol.selection) if fed._exec_mesh() is not None else 0

    histories = [list(c.val_history) for c in clients]
    # device-resident learnable state for this fit (the participation
    # orchestrator's gather/scatter unit and its bounded-working-set meter)
    state_bytes = (_tree_bytes(params) + _tree_bytes(opt_state)
                   + _tree_bytes(best_params))
    n_rounds = np.zeros(C, np.int64)
    base_rounds = dict(fed.n_rounds)

    def make_epoch_fn(do_federate: bool, do_eval: bool,
                      exchange_every: int = 1):
        if mesh is not None:
            return MF._make_mesh_epoch_fn(cfg.lr, nf, cfg.w, pol,
                                          use_kernel, do_federate, do_eval,
                                          mesh, C, exchange_every,
                                          admission, trust, tele)
        return _make_epoch_fn(cfg.lr, nf, pol, use_kernel, do_federate,
                              do_eval, exchange_every, admission, trust,
                              tele)

    def trust_args(active, n_exch: int, e_off: int = 0):
        """The epoch function's trailing ``trust_arrays`` argument for one
        dispatch: the replicated signature stack (watermark), the wave's
        ``(net_masks, correction)`` pair covering ``n_exch`` exchange
        rounds starting at within-epoch round ``e_off`` (secure), or a
        scalar dummy (DP-only).  Returns () when the trust layer is off."""
        if trust is None:
            return ()
        if secure:
            wave = fed._trust_wave_base + fed.epoch
            masks = TR.net_masks(trust.secure_agg, wave, n_exch,
                                 fed._trust_ids, head_tmpl,
                                 round_offset=e_off)
            corr = TR.mask_correction(masks, active)
            ta = jax.tree_util.tree_map(jnp.asarray, (masks, corr))
        elif sig_stack is not None:
            ta = sig_stack
        else:
            ta = jnp.zeros((), jnp.float32)
        if mesh is not None:
            ta = MF.replicate(mesh, ta)
        return (ta,)

    # the fused path runs the whole epoch in ONE dispatch; any callback that
    # needs per-round delivery forces the chunked path (one dispatch per
    # sub-round through the SAME compiled function, on_round after each)
    fused = not any(_wants_per_round(cb) for cb in cbs)
    n_dispatch = 0

    def account_trust(tstats, rej, active, federated: bool, n_exch: int):
        """Fold one dispatch's trust outputs into the fit's counters: clip
        events, per-client watermark failures, and the DP release count —
        publications actually made (active exchange opportunities minus
        watermark-blocked minus admission-rejected; the three are disjoint
        by the in-graph publication chain)."""
        nonlocal clip_total
        if trust is None:
            return
        clip_r, wmf_r = (np.asarray(t) for t in tstats)
        clip_total += int(clip_r.sum())
        wmf_pc = wmf_r.reshape(-1, C).sum(axis=0).astype(np.int64)
        wm_fail[:] += wmf_pc
        if trust.dp is not None and federated:
            rej_pc = (np.asarray(rej).reshape(-1, C).sum(axis=0)
                      if rej is not None else np.zeros(C, np.int64))
            dp_pubs[:] += (active.astype(np.int64) * n_exch
                           - wmf_pc - rej_pc)

    def sync():
        """Write the stacked loop state back into the clients / pool / rng —
        run after the loop, and on demand when a callback checkpoints the
        federation mid-fit (Federation.save calls this hook)."""
        with TEL.span(rec, "writeback"):
            params_h, opt_h, best_h, heads_h, ages, bv = _to_host(
                (params, opt_state, best_params, pool_heads, pool_age,
                 best_val))
            for i, c in enumerate(clients):
                c.params = _tree_row(params_h, i)
                c.opt_state = _tree_row(opt_h, i)
                c.val_history = histories[i]
                c.best_val = float(bv[i])
                c.best_params = _tree_row(best_h, i)
                fed.pool.publish(c.name, _tree_row(heads_h, i), nf,
                                 age=int(ages[i]))
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
        active_dev = jnp.asarray(active)
        if mesh is not None:
            active_dev = MF.replicate(mesh, active_dev)
        do_federate = bool(active.any()) and C >= 2
        state = (params, opt_state, pool_heads, pool_age, key, best_val,
                 best_params)
        fed._mid_epoch = True
        if fused:
            epoch_fn = make_epoch_fn(do_federate, True, k_ex)
            args = (*state, xs_r, xd_r, y_r, active_dev, *val,
                    *trust_args(active, n_exch_epoch))
            if rec is not None:
                rec.note_program(epoch_fn, *args)
            with TEL.span(rec, "dispatch", epoch=epoch, path="fused"):
                out = epoch_fn(*args)
            if tele is not None:   # telemetry rides LAST: pop it first
                tele_out, out = out[-1], out[:-1]
            if trust is not None:
                tstats, out = out[-1], out[:-1]
            if admission is not None:
                (*state, v, chosen, rej) = out
                heads_rejected += int(np.asarray(rej).sum())
            else:
                (*state, v, chosen) = out
                rej = None
            account_trust(tstats, rej, active, do_federate,
                          n_exch_epoch) if trust is not None else None
            n_dispatch += 1
        else:
            chunks = []
            tele_chunks = []
            e_done = 0          # exchange rounds executed so far this epoch
                                # (the trust layer's within-epoch mask index)
            for rnd in range(n_sub):
                # cadence on the chunked path: a non-exchange sub-round is
                # exactly a do_federate=False dispatch (train + eval only)
                fed_r = do_federate and bool(exch_mask[rnd])
                epoch_fn = make_epoch_fn(fed_r, rnd == n_sub - 1)
                with TEL.span(rec, "dispatch", epoch=epoch, round=rnd,
                              path="chunked"):
                    out = epoch_fn(
                        *state, xs_r[rnd:rnd + 1], xd_r[rnd:rnd + 1],
                        y_r[rnd:rnd + 1], active_dev, *val,
                        *trust_args(active, 1 if fed_r else 0, e_done))
                if tele is not None:
                    tele_chunks.append(out[-1])
                    out = out[:-1]
                if trust is not None:
                    tstats, out = out[-1], out[:-1]
                if admission is not None:
                    (*state, v, ch, rej) = out
                    heads_rejected += int(np.asarray(rej).sum())
                else:
                    (*state, v, ch) = out
                    rej = None
                account_trust(tstats, rej, active, fed_r,
                              1 if fed_r else 0) if trust is not None \
                    else None
                if fed_r:
                    e_done += 1
                chunks.append(ch)
                n_dispatch += 1
                # sync the carried state (and the live round counters)
                # before handing control to the callback so a mid-epoch
                # reader sees current state, as on the sequential engine
                (params, opt_state, pool_heads, pool_age, key, best_val,
                 best_params) = state
                if active.any() and exch_mask[rnd]:
                    n_rounds += active
                for cb in cbs:
                    cb.on_round(fed, epoch, rnd)
            if n_sub == 0:      # no trainable sub-round: eval-only dispatch
                epoch_fn = make_epoch_fn(do_federate, True)
                with TEL.span(rec, "dispatch", epoch=epoch,
                              path="eval-only"):
                    out = epoch_fn(*state, xs_r, xd_r, y_r, active_dev,
                                   *val, *trust_args(active, 0))
                if tele is not None:
                    out = out[:-1]
                if trust is not None:
                    out = out[:-1]
                if admission is not None:
                    (*state, v, ch, _rej) = out
                else:
                    (*state, v, ch) = out
                chunks.append(ch)
                n_dispatch += 1
            chosen = jnp.concatenate(chunks) if chunks else None
            tele_out = tuple(
                np.concatenate([np.asarray(t[k]) for t in tele_chunks])
                for k in range(4)) if tele is not None and tele_chunks \
                else None
        (params, opt_state, pool_heads, pool_age, key, best_val,
         best_params) = state
        with TEL.span(rec, "readback", epoch=epoch):
            # ONE device->host materialization of the epoch's results
            v = np.asarray(v, np.float64)
            chosen = np.asarray(chosen) if do_federate else None
        with TEL.span(rec, "record", epoch=epoch):
            if do_federate:
                for ch in chosen:
                    for i in range(C):
                        if active[i] and ch[i][0] >= 0:
                            fed.selections[names[i]].append(
                                lut[i, ch[i]].tolist())
            if tele is not None and tele_out is not None:
                rec.record_epoch_rounds(epoch, tele_out, active)
            if fused and active.any():   # chunked path counted per round
                n_rounds += active * n_exch_epoch
            if rec is not None and active.any():
                rec.count("client_rounds", int(active.sum()) * n_exch_epoch)
            # refresh the live counters each epoch (idempotent with sync(),
            # a handful of host ints) so epoch-boundary readers —
            # VerboseLogger's throughput line — see current round counts
            # without a device sync
            for i, nm in enumerate(names):
                fed.n_rounds[nm] = base_rounds[nm] + int(n_rounds[i])
            if do_federate:
                exchange_rounds += n_exch_epoch
                pool_bytes += n_exch_epoch * exch_bytes
            for i in range(C):
                histories[i].append(float(v[i]))
        fed.epoch += 1
        fed._mid_epoch = False
        for cb in cbs:
            cb.on_epoch_end(fed, epoch,
                            {names[i]: float(v[i]) for i in range(C)},
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
        # fold this fit's in-graph counters into the flight recorder so an
        # exported trace carries them even when dispatch_stats is later
        # overwritten (the participation orchestrator re-aggregates waves)
        if heads_rejected:
            rec.count("heads_rejected", int(heads_rejected))
        if trust is not None:
            if clip_total:
                rec.count("clip_events", int(clip_total))
            if wm_fail.sum():
                rec.count("watermark_failures", int(wm_fail.sum()))
    fed.dispatch_stats = {"engine": "batched",
                          "path": "fused" if fused else "chunked",
                          "devices": MF.mesh_devices(mesh),
                          "cohorts": 1,
                          "epochs": n_epochs, "dispatches": n_dispatch,
                          "dispatches_per_epoch": n_dispatch / n_epochs,
                          "exchange_every": k_ex,
                          "exchange_rounds": exchange_rounds,
                          "pool_bytes_gathered": pool_bytes,
                          "state_bytes": state_bytes,
                          **fed._fault_stats(heads_rejected),
                          **fed._trust_stats()}
    # write the final state back so the clients / pool / rng stay canonical
    sync()
    fed._sync = None


# ---------------------------------------------------------------------------
# Federation
# ---------------------------------------------------------------------------

def _client_data_shapes(c: FederatedClient):
    """JSON-comparable split shapes, checked at restore time so a client
    rebuilt from different pipeline arguments fails fast, not inside jit."""
    return [[list(np.shape(a)) for a in split]
            for split in (c.train, c.valid, c.test)]


class Federation:
    """A resumable federated-training run: clients + policies + schedule +
    callbacks + all mutable state (pool, RNG streams, counters).

    ``fit()`` trains up to ``schedule.epochs``; ``fit(epochs=k)`` trains k
    MORE epochs from wherever the federation currently is.  ``save(dir)`` /
    ``restore(dir, clients)`` round-trip the full state through
    ``repro.checkpoint`` (data is NOT checkpointed — rebuild the clients the
    same way, then restore overlays params/opt/pool/rng/histories).

    ``engine="batched"`` accepts heterogeneous populations transparently:
    mixed feature counts and ragged split lengths are partitioned into
    homogeneous cohorts by ``repro.core.cohorts`` (an internal planning
    step surfaced in ``dispatch_stats["cohorts"]``/``["per_cohort"]``),
    trained per-cohort at native geometry inside one fused dispatch per
    epoch, and federated through a padded union head pool — selections
    identical to the sequential oracle.

    ``mesh`` (batched engine only) opts into client-sharded execution: a
    1-D :class:`jax.sharding.Mesh` with a ``clients`` axis
    (:func:`repro.core.mesh_federation.make_mesh`) partitions the stacked
    population over its devices — device-local Adam steps, explicit
    all-gather pool exchange per sub-round, selections identical to the
    single-device engine.  A one-device mesh falls back to the plain
    single-device fused path automatically.  On a heterogeneous
    population every cohort's size must divide the device count (checked
    at fit time)."""

    def __init__(self, clients: Sequence[FederatedClient],
                 cfg: Optional[HFLConfig] = None, *,
                 policies: Optional[FederationPolicies] = None,
                 schedule: Optional[RoundSchedule] = None,
                 callbacks: Sequence[Callback] = (),
                 engine: str = "sequential",
                 mesh=None, faults=None, trust=None, telemetry=None):
        if engine not in ("sequential", "batched"):
            raise ValueError(f"unknown engine {engine!r}")
        self.clients = list(clients)
        if mesh is not None:
            if engine != "batched":
                raise ValueError(
                    "mesh= requires engine='batched' (the sequential "
                    "oracle is a host-driven reference loop)")
            MF.validate_mesh(mesh, len(self.clients))
        self.mesh = mesh
        names = [c.name for c in self.clients]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate client names: {names}")
        if cfg is None:
            cfg = self.clients[0].cfg if self.clients else HFLConfig()
        self.cfg = cfg
        self.policies = policies if policies is not None \
            else FederationPolicies.from_config(cfg)
        self.schedule = schedule or RoundSchedule(cfg.epochs, cfg.R)
        self.callbacks = list(callbacks)
        self.engine = engine
        self.epoch = 0
        self.n_rounds: Dict[str, int] = {n: 0 for n in names}
        self.selections: Dict[str, list] = {n: [] for n in names}
        # fault-tolerance layer (core/faults.py): an *enabled* FaultPlan
        # arms the pool admission guard; a disabled plan (all rates zero)
        # or None keeps every engine bit-identical to a fault-free build
        self.faults = faults
        # trust layer (core/trust.py): an *enabled* TrustPlan arms masked
        # secure aggregation / DP releases / watermark verification; a
        # disabled plan or None keeps every engine bit-identical to a
        # trust-free build (the same contract as faults=None)
        if trust is not None and not isinstance(trust, TR.TrustPlan):
            raise TypeError(f"trust: expected a TrustPlan, "
                            f"got {type(trust).__name__}")
        self.trust = trust
        self._trust = trust if trust is not None and trust.enabled else None
        # telemetry layer (core/telemetry.py): an *enabled* TelemetryPlan
        # arms the in-graph per-round metrics carry and the host-side
        # flight recorder; a disabled plan or None keeps every engine
        # bit-identical to an uninstrumented build (same contract as
        # faults=None / trust=None)
        if telemetry is not None \
                and not isinstance(telemetry, TEL.TelemetryPlan):
            raise TypeError(f"telemetry: expected a TelemetryPlan, "
                            f"got {type(telemetry).__name__}")
        self.telemetry = telemetry
        self._telemetry = telemetry if telemetry is not None \
            and telemetry.enabled else None
        self._recorder = (TEL.FlightRecorder(self._telemetry)
                          if self._telemetry is not None else None)
        # wave/identity context the participation orchestrator overrides so
        # trust derivations (masks, oracle DP noise) key on GLOBAL client
        # ids and the wave counter, not per-wave positions
        self._trust_wave_base = 0
        self._trust_ids = tuple(range(len(self.clients)))
        self._dp_counts: Dict[str, int] = {}
        self._wm_failures: Dict[str, int] = {n: 0 for n in names}
        self._clip_events = 0
        self._wm_sigs: Dict[str, object] = {}
        # (C,) bool poked by the participation orchestrator before fit():
        # True rows are this wave's stragglers (they train, never exchange)
        self._straggler_mask = None
        self._seed_rejected = 0
        wm = self._trust.watermark if self._trust is not None else None
        if wm is not None:
            # embed/top-up every client's OWN signature before anything is
            # published — the no-heal rule leaves an already-flipped head
            # (projection at -strength) untouched, so corruption that
            # happened upstream stays detectable
            for c in self.clients:
                new_h, _ = TR.wm_embed(c.params["heads"], self._wm_sig(c),
                                       wm)
                c.params = dict(c.params)
                c.params["heads"] = new_h
        self.pool = HeadPool()
        admission = self._admission()
        for c in self.clients:   # asynchronous start: pool is never empty
            if self._trust is not None \
                    and self._trust.secure_agg is not None:
                # under secure aggregation no raw head may ever reach the
                # pool — the seed rows are zeros (the first masked round
                # overwrites them with masked payloads)
                self.pool.publish(c.name,
                                  FT.zero_heads_like(c.params["heads"]),
                                  c.nf)
            elif wm is not None and not TR.wm_verify_host(
                    c.params["heads"], self._wm_sig(c), wm):
                # a seed head that fails its own signature was tampered
                # with before this federation saw it (the sign-flip
                # fingerprint): quarantine the row, count the failure
                self.pool.publish(c.name,
                                  FT.zero_heads_like(c.params["heads"]),
                                  c.nf, age=FT.QUARANTINE_AGE)
                self._wm_failures[c.name] += 1
            elif admission is not None and not FT.heads_admissible(
                    c.params["heads"], admission):
                # quarantine a poisoned seed head: publish a zeroed row at
                # the sentinel age so no selector ever sees it (a clean
                # republication later revives the row at age 0)
                self.pool.publish(c.name,
                                  FT.zero_heads_like(c.params["heads"]),
                                  c.nf, age=FT.QUARANTINE_AGE)
                self._seed_rejected += 1
            else:
                self.pool.publish(c.name, c.params["heads"], c.nf)
        self._sel_rng = np.random.default_rng(cfg.seed)
        self._switch_rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed, 0x5F]))
        self._key = jax.random.PRNGKey(cfg.seed)
        self._sync = None       # set by the batched executor while it runs
        self._mid_epoch = False  # True inside an epoch: save() would be torn
        # {engine, path, devices, epochs, dispatches, dispatches_per_epoch}
        # for the most recent fit: "fused" = one compiled dispatch per
        # epoch, "chunked" = one per sub-round (per-round callbacks
        # present), "per-round" = the sequential oracle's per-client
        # dispatch pattern; devices = mesh devices actually sharded over
        # (1 on the single-device path)
        self.dispatch_stats: Optional[dict] = None

    def _exec_mesh(self):
        """The mesh the batched executor actually shards over: None when no
        mesh was given OR the mesh has one device — the single-device fused
        path runs then (selection-identical, zero shard_map overhead)."""
        if self.mesh is not None and MF.mesh_devices(self.mesh) > 1:
            return self.mesh
        return None

    def _admission(self) -> Optional[float]:
        """The pool admission guard's norm bound, or None when the guard is
        off (no FaultPlan, or a disabled all-zero plan — the engines then
        trace exactly the fault-free computation)."""
        if self.faults is not None and self.faults.enabled:
            return float(self.faults.norm_bound)
        return None

    def _tele_rounds(self):
        """The TelemetryPlan the epoch factories receive as their static
        telemetry argument — the enabled plan iff its in-graph per-round
        series is on, else None (the factories then trace exactly the
        uninstrumented computation)."""
        if self._telemetry is not None and self._telemetry.rounds:
            return self._telemetry
        return None

    def _fault_stats(self, heads_rejected: int) -> dict:
        """The fault counters every engine folds into ``dispatch_stats``.
        Dropout / wave degradation happen a layer up (the participation
        orchestrator re-rounds wave geometry before this Federation even
        exists), so a plain Federation reports zeros there and the
        orchestrator overwrites them with wave-aggregated counts."""
        smask = self._straggler_mask
        return {"heads_rejected": int(heads_rejected)
                + int(self._seed_rejected),
                "clients_dropped": 0,
                "stragglers": 0 if smask is None else int(np.sum(smask)),
                "waves_degraded": 0}

    def _wm_sig(self, c: FederatedClient):
        """The client's cached watermark signature tree (a pure function of
        the watermark seed and the client NAME, so it is identical across
        engines, waves and restores)."""
        if c.name not in self._wm_sigs:
            self._wm_sigs[c.name] = TR.signature(
                self._trust.watermark, c.name,
                jax.tree_util.tree_map(np.asarray, c.params["heads"]))
        return self._wm_sigs[c.name]

    def _trust_stats(self) -> dict:
        """The trust counters every engine folds into ``dispatch_stats``:
        ``epsilon_spent`` is the worst per-client analytic (eps, delta)
        bound over all DP releases so far (cumulative across fits),
        ``clip_events`` / ``watermark_failures`` the cumulative event
        counts.  All zero when the trust layer is off."""
        t = self._trust
        eps = 0.0
        if t is not None and t.dp is not None:
            eps = max((t.dp.epsilon(v) for v in self._dp_counts.values()),
                      default=0.0)
        return {"epsilon_spent": float(eps),
                "clip_events": int(self._clip_events),
                "watermark_failures": int(sum(self._wm_failures.values()))}

    # -- training ----------------------------------------------------------

    def fit(self, epochs: Optional[int] = None, verbose: bool = False):
        """Train `epochs` more epochs (default: up to ``schedule.epochs``
        total) and return the legacy history dict
        {name: {val, test, rounds, best_val, selections}}."""
        target = self.schedule.epochs if epochs is None \
            else self.epoch + epochs
        n = max(0, target - self.epoch)
        cbs = list(self.callbacks)
        if verbose and not any(isinstance(cb, VerboseLogger) for cb in cbs):
            cbs.append(VerboseLogger())
        # generation-2 collections during the fit become `gc` spans
        with TEL.gc_spans(self._recorder):
            for cb in cbs:
                cb.on_fit_start(self)
            if n:
                dropped = {c.name: self.schedule.leftover(len(c.train[2]))
                           for c in self.clients}
                dropped = {k: v for k, v in dropped.items() if v}
                if dropped:
                    warnings.warn(
                        f"RoundSchedule(R={self.schedule.R}) drops the "
                        f"trailing partial batch every epoch: {dropped} "
                        f"train events per epoch are never trained on "
                        f"(train lengths are not multiples of R); truncate "
                        f"to a multiple of R or pick a divisor R to "
                        f"silence this", UserWarning, stacklevel=2)
                with TEL.span(self._recorder, "fit", epochs=n,
                              engine=self.engine):
                    if self.engine == "batched":
                        _fit_batched(self, n, cbs)
                    else:
                        _fit_sequential(self, n, cbs)
            results = self.results()
            for cb in cbs:
                cb.on_fit_end(self, results)
        return results

    def results(self):
        """Per-client history in the legacy run_federated_training format."""
        with TEL.span(self._recorder, "results"):
            if self._sync is not None:   # mid-fit (batched executor)
                self._sync()
            with TEL.span(self._recorder, "test_pass"):
                test = self._test_mses()
            return {c.name: {"val": list(c.val_history),
                             "test": test[c.name],
                             "rounds": self.n_rounds[c.name],
                             "best_val": float(c.best_val),
                             "selections": [list(s) for s in
                                            self.selections[c.name]]}
                    for c in self.clients}

    def _test_mses(self) -> Dict[str, float]:
        """Best-params test MSE per client — ONE vmapped dispatch per cohort
        on the batched engine (matching its training-path batching) instead
        of C per-client jit calls.  A homogeneous population is one cohort;
        singleton cohorts fall back to the client's own jitted eval."""
        if self.engine == "batched" and len(self.clients) > 1:
            from repro.core import cohorts
            plan = cohorts.plan_cohorts(self.clients, self.schedule.R)
            _, eval_fn = _make_batched_fns(self.cfg.lr)
            out: Dict[str, float] = {}
            for co in plan.cohorts:
                cl = [self.clients[i] for i in co.members]
                if len(cl) == 1:
                    out[cl[0].name] = cl[0].test_mse()
                    continue
                tst = tuple(_stack_data([c.test[k] for c in cl])
                            for k in range(3))
                bp = _stack_trees([c.best_params for c in cl])
                v = np.asarray(eval_fn(*jax.device_put((bp, *tst))),
                               np.float64)
                out.update({c.name: float(v[i]) for i, c in enumerate(cl)})
            return out
        return {c.name: c.test_mse() for c in self.clients}

    # -- persistence -------------------------------------------------------

    def save(self, directory) -> Path:
        """Checkpoint the complete federation state for mid-training resume:
        per-client params/opt/best, the pool (entries + ages), both host RNG
        streams, the device PRNG key, and every counter/history.

        Durable against interrupts: the state tree goes to an epoch-stamped
        file first and the manifest — the commit point, written atomically
        last — is what references it, so a crash anywhere mid-save leaves
        the previously committed checkpoint fully readable.  Only valid at
        an epoch boundary (on_epoch_end / between fits); a mid-epoch save
        from an on_round callback raises."""
        if self._mid_epoch:
            raise RuntimeError(
                "Federation.save is only valid at an epoch boundary "
                "(on_epoch_end or between fits); mid-epoch state has "
                "unlogged selections and an un-advanced epoch counter")
        if self._sync is not None:  # mid-fit (batched executor): pull the
            self._sync()            # stacked loop state into the clients
        d = Path(directory)
        d.mkdir(parents=True, exist_ok=True)
        state = {
            "epoch": self.epoch,   # cross-checked against the manifest so a
                                   # torn pair is detected (belt+braces)
            "clients": [{"params": c.params, "opt_state": c.opt_state,
                         "best_params": c.best_params}
                        for c in self.clients],
            "pool": {f"{u}|{i}": entry
                     for (u, i), entry in self.pool.entries.items()},
            "key": np.asarray(self._key),
        }
        state_name = f"state_{self.epoch:08d}.msgpack"
        ckpt.save(d / state_name, state)
        manifest = {
            "format": 1,
            "state_file": state_name,
            "epoch": self.epoch,
            "engine": self.engine,
            "cfg": dataclasses.asdict(self.cfg),
            "policies": self.policies.spec(),
            "schedule": {"epochs": self.schedule.epochs,
                         "R": self.schedule.R,
                         "exchange_every": self.schedule.exchange_every},
            # informational: the device count the run sharded over.  The
            # checkpointed state itself is mesh-agnostic (gathered to host
            # trees), so a restore may use any mesh — or none.
            "mesh_devices": MF.mesh_devices(self.mesh),
            "names": [c.name for c in self.clients],
            "nf": [c.nf for c in self.clients],
            "data_shapes": [_client_data_shapes(c) for c in self.clients],
            "val_histories": {c.name: c.val_history for c in self.clients},
            "best_val": {c.name: float(c.best_val) for c in self.clients},
            "n_rounds": self.n_rounds,
            "selections": self.selections,
            "pool_ages": {f"{u}|{i}": a
                          for (u, i), a in self.pool.ages.items()},
            "sel_rng": self._sel_rng.bit_generator.state,
            "switch_rng": self._switch_rng.bit_generator.state,
            "faults": (self.faults.spec()
                       if self.faults is not None else None),
            "trust": (self.trust.spec()
                      if self.trust is not None else None),
            # integer counters only — the accountant's state restores
            # bit-identically by construction (epsilons are recomputed
            # analytically from the counts)
            "trust_state": {"dp_counts": self._dp_counts,
                            "wm_failures": self._wm_failures,
                            "clip_events": self._clip_events,
                            "wave_base": self._trust_wave_base,
                            "ids": list(self._trust_ids)},
            "telemetry": (self.telemetry.spec()
                          if self.telemetry is not None else None),
            # the flight recorder's ring buffer + counters + clock offset:
            # a restored run's spans continue the trace monotonically
            "telemetry_state": (self._recorder.to_json()
                                if self._recorder is not None else None),
        }
        # atomic manifest write = the commit; only then prune state files
        # superseded by it (the previous pair stays intact until here)
        tmp = d / "manifest.json.tmp"
        tmp.write_text(json.dumps(manifest))
        os.replace(tmp, d / "manifest.json")
        for p in d.glob("state_*.msgpack"):
            if p.name != state_name:
                p.unlink()
        return d

    @classmethod
    def restore(cls, directory, clients: Sequence[FederatedClient], *,
                engine: Optional[str] = None,
                callbacks: Sequence[Callback] = (),
                mesh=None) -> "Federation":
        """Rebuild a saved federation over freshly-constructed clients (the
        data pipeline is re-run by the caller; everything learned/random is
        overlaid from the checkpoint, bit-identically).  ``mesh`` re-shards
        the resumed run over a device mesh — checkpoints are mesh-agnostic,
        so saving from a 4-device run and restoring onto 1 device (or vice
        versa) is bit-identical either way."""
        d = Path(directory)
        manifest = json.loads((d / "manifest.json").read_text())
        names = [c.name for c in clients]
        if names != manifest["names"]:
            raise ValueError(f"client names {names} do not match "
                             f"checkpoint {manifest['names']}")
        nfs = [c.nf for c in clients]
        if nfs != manifest["nf"]:
            raise ValueError(f"client feature counts {nfs} do not match "
                             f"checkpoint {manifest['nf']}")
        shapes = [_client_data_shapes(c) for c in clients]
        if shapes != manifest.get("data_shapes", shapes):
            raise ValueError(
                "client data shapes do not match the checkpoint — rebuild "
                "the clients with the same data pipeline arguments "
                f"(got {shapes}, checkpoint has {manifest['data_shapes']})")
        ck_cfg = manifest["cfg"]
        for c in clients:
            # lr is baked into the client's jitted train step at
            # construction (and w into its schema) — a mismatch would
            # silently resume on the wrong optimizer/model
            if c.cfg.lr != ck_cfg["lr"] or c.cfg.w != ck_cfg["w"]:
                raise ValueError(
                    f"client {c.name!r} was built with lr={c.cfg.lr}, "
                    f"w={c.cfg.w} but the checkpoint has "
                    f"lr={ck_cfg['lr']}, w={ck_cfg['w']} — rebuild the "
                    f"clients with the checkpointed config")
        cfg = HFLConfig(**manifest["cfg"])
        fspec = manifest.get("faults")
        tspec = manifest.get("trust")
        espec = manifest.get("telemetry")
        fed = cls(clients, cfg,
                  policies=FederationPolicies.from_spec(manifest["policies"]),
                  schedule=RoundSchedule(**manifest["schedule"]),
                  callbacks=callbacks,
                  engine=engine or manifest["engine"],
                  mesh=mesh,
                  faults=policy_from_spec(fspec) if fspec else None,
                  trust=policy_from_spec(tspec) if tspec else None,
                  telemetry=policy_from_spec(espec) if espec else None)
        state = ckpt.load(d / manifest.get("state_file", "state.msgpack"))
        if state.get("epoch") != manifest["epoch"]:
            raise ValueError(
                f"checkpoint is torn: state.msgpack is at epoch "
                f"{state.get('epoch')} but manifest.json at "
                f"{manifest['epoch']} (a save was interrupted between the "
                f"two writes) — re-save or fall back to an older checkpoint")
        for c, cs in zip(fed.clients, state["clients"]):
            c.params = cs["params"]
            c.opt_state = cs["opt_state"]
            c.best_params = cs["best_params"]
            c.val_history = list(manifest["val_histories"][c.name])
            c.best_val = float(manifest["best_val"][c.name])
        fed.pool.entries = {
            (k.rsplit("|", 1)[0], int(k.rsplit("|", 1)[1])): entry
            for k, entry in state["pool"].items()}
        fed.pool.ages = {
            (k.rsplit("|", 1)[0], int(k.rsplit("|", 1)[1])): int(a)
            for k, a in manifest["pool_ages"].items()}
        fed.epoch = int(manifest["epoch"])
        fed.n_rounds = {n: int(v) for n, v in manifest["n_rounds"].items()}
        fed.selections = {n: [list(s) for s in v]
                          for n, v in manifest["selections"].items()}
        fed._key = jnp.asarray(state["key"])
        fed._sel_rng.bit_generator.state = manifest["sel_rng"]
        fed._switch_rng.bit_generator.state = manifest["switch_rng"]
        ts = manifest.get("trust_state")
        if ts is not None:
            # the constructor's init-time embedding/seeding side effects
            # were fully overwritten by the params/pool overlays above;
            # the counters below make the accountant/reputation state
            # replay bit-identically
            fed._dp_counts = {k: int(v)
                              for k, v in ts.get("dp_counts", {}).items()}
            fed._wm_failures = {k: int(v)
                                for k, v in ts.get("wm_failures",
                                                   {}).items()}
            fed._clip_events = int(ts.get("clip_events", 0))
            fed._trust_wave_base = int(ts.get("wave_base", 0))
            fed._trust_ids = tuple(int(i) for i in ts.get(
                "ids", range(len(clients))))
        rs = manifest.get("telemetry_state")
        if rs is not None and fed._telemetry is not None:
            fed._recorder = TEL.FlightRecorder.from_json(fed._telemetry, rs)
        return fed


# ---------------------------------------------------------------------------
# Non-federated loop on the shared schedule (benchmark systems)
# ---------------------------------------------------------------------------

def fit_local(step_fn, eval_fn, params, opt_state, train, valid,
              schedule: RoundSchedule, callbacks: Sequence[Callback] = ()):
    """Single-model training on the shared :class:`RoundSchedule` with
    save-best-on-validation (paper §5.2) and the same callback hooks as
    :meth:`Federation.fit` — the benchmark systems' loop.

    ``step_fn(params, opt_state, xs, xd, y) -> (params, opt_state)``;
    ``eval_fn(params, xs, xd, y) -> scalar``.  Returns
    ``(params, opt_state, best_params, best_val)``."""
    xs, xd, y = train
    best_val, best_params = np.inf, params
    for cb in callbacks:
        cb.on_fit_start(None)
    for epoch in range(schedule.epochs):
        for rnd, sl in enumerate(schedule.slices(len(y))):
            params, opt_state = step_fn(params, opt_state,
                                        xs[sl], xd[sl], y[sl])
            for cb in callbacks:
                cb.on_round(None, epoch, rnd)
        v = float(eval_fn(params, *valid))
        if v < best_val:
            best_val, best_params = v, params
        for cb in callbacks:
            cb.on_epoch_end(None, epoch, {"val": v}, {})
    for cb in callbacks:
        cb.on_fit_end(None, {"best_val": best_val})
    return params, opt_state, best_params, best_val
