"""Sampled partial participation over a host-resident client store.

Production federation is 10⁴–10⁶ clients with a *fraction* participating
per round — the classic FedAvg ``client_fraction`` regime — while every
engine in :mod:`repro.core.federation` assumes the whole population's
params/opt-states are stacked device-resident.  This module inverts that
memory model:

  * The **head pool (+ ages)** is the only always-resident structure
    (host numpy copies between waves); it CARRIES across waves, so
    knowledge transfer spans the whole population transitively — a head
    blended from wave-1 partners is what wave-5 partners select against.
  * Client params / opt-states / best-params live in a host-side
    :class:`ClientStore` (numpy arrays keyed by client name, bit-exact
    round-trip), populated lazily: only clients that have ever been
    sampled occupy store memory.
  * The population itself is a :class:`ClientPopulation` — O(N) cheap
    metadata (feature counts, optional sizes) plus a ``build(indices)``
    factory that materializes exactly the sampled subset, so a 100k-client
    population never exists in memory at once.

Each **wave** (one federated epoch over a sampled subset) a seeded
:class:`ParticipationPolicy` — the fifth pluggable policy protocol
alongside switch/selection/transfer/pool, registered through the same
:func:`repro.core.policies.register_policy` hook — samples the active set;
:class:`ParticipatingFederation` gathers the sampled clients' stored state
to device, runs the existing fused epoch on the gathered view (batched,
cohorted, and mesh engines all unchanged — an inner
:class:`~repro.core.federation.Federation` over the subset), and scatters
the updated state back.  The device working set is bounded by the sample
size, never the population (``dispatch_stats["resident_state_bytes"]``).

Semantics are the subset-federation semantics: a wave's Eq.-7 selection
sees the sampled clients' pool entries (with values carried from their
previous waves), and selections for the sampled subset are IDENTICAL to a
sequential oracle run on that same subset — the inner federation with
``engine="sequential"`` *is* that oracle, so parity is inherited from the
engine-parity invariant rather than re-proven.  Entry ages tick per
exchange opportunity while their owner is resident and stand still
otherwise (age = staleness among the exchanges the owner could have
refreshed at).

All three RNG streams (participation sampler, selection, switching) and
the device PRNG key persist across waves and checkpoint with the store,
so a sampled run is replayable: same seed ⇒ identical participation
schedule, bit-identical histories, including across ``save``/``restore``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import zlib
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.core import faults as FT
from repro.core import mesh_federation as MF
from repro.core import telemetry as TEL
from repro.core import trust as TR
from repro.core.federation import (Federation, RoundSchedule, _tree_bytes)
from repro.core.hfl import FederatedClient, HFLConfig
from repro.core.policies import (FederationPolicies, _Spec, policy_from_spec,
                                 register_policy)


def host_tree(tree):
    """A bit-exact host copy of a pytree: every leaf as a numpy array.
    ``np.array`` is a dtype-preserving byte copy, so a store round-trip
    (device → store → device) is exact, and an entry owns its bytes: a
    batched fit hands clients row views of its whole stacked state, which
    a stored view would keep alive."""
    return jax.tree_util.tree_map(np.array, tree)


# ---------------------------------------------------------------------------
# ClientStore — host-resident learnable state
# ---------------------------------------------------------------------------

class StoreCorruption(RuntimeError):
    """A stored entry failed its checksum after the bounded reread budget.
    The orchestrator's recovery is to discard the entry and rebuild the
    client from its deterministic per-index builder (see
    :meth:`ParticipatingFederation.fit`)."""


def entry_checksum(entry: dict) -> int:
    """crc32 over every byte a store entry round-trips: the three numpy
    trees' leaf buffers plus the float64 encodings of best_val and the
    val history.  Bit-exact round-trip ⇒ checksum match; any single-byte
    corruption flips it."""
    crc = 0
    for tree in (entry["params"], entry["opt_state"], entry["best_params"]):
        for leaf in jax.tree_util.tree_leaves(tree):
            crc = zlib.crc32(np.ascontiguousarray(leaf).tobytes(), crc)
    crc = zlib.crc32(np.float64(entry["best_val"]).tobytes(), crc)
    crc = zlib.crc32(np.asarray(entry["val_history"],
                                np.float64).tobytes(), crc)
    return crc


class ClientStore:
    """Host-side store of per-client learnable state (params / opt_state /
    best_params as numpy trees, plus best_val + val_history scalars).

    Grows only with clients that have actually been sampled — a population
    index never drawn costs nothing here; its first wave starts from the
    deterministic fresh init its :class:`ClientPopulation` builds.  Values
    are bit-exact round-trips of whatever was scattered in.

    Every entry carries a crc32 over its leaf bytes, written at
    :meth:`put` and verified at :meth:`get` with a bounded reread budget
    (``GET_RETRIES``).  A persistent mismatch raises
    :class:`StoreCorruption` — the store never silently serves corrupted
    state."""

    GET_RETRIES = 3

    def __init__(self):
        self._states: Dict[str, dict] = {}
        self._crcs: Dict[str, int] = {}

    def put(self, name: str, *, params, opt_state, best_params,
            best_val: float, val_history: Sequence[float]) -> None:
        entry = {
            "params": host_tree(params),
            "opt_state": host_tree(opt_state),
            "best_params": host_tree(best_params),
            "best_val": float(best_val),
            "val_history": [float(v) for v in val_history],
        }
        self._states[name] = entry
        self._crcs[name] = entry_checksum(entry)

    def get(self, name: str) -> dict:
        entry = self._states[name]
        for _ in range(self.GET_RETRIES):
            if entry_checksum(entry) == self._crcs[name]:
                return entry
        raise StoreCorruption(
            f"store entry {name!r} failed checksum verification "
            f"{self.GET_RETRIES} times (host memory corruption); rebuild "
            f"it from the population's deterministic builder")

    def discard(self, name: str) -> None:
        """Drop an entry (the corruption-recovery path: the client's next
        wave starts from its deterministic fresh init again)."""
        self._states.pop(name, None)
        self._crcs.pop(name, None)

    def __contains__(self, name: str) -> bool:
        return name in self._states

    def __len__(self) -> int:
        return len(self._states)

    def names(self) -> List[str]:
        return sorted(self._states)

    def nbytes(self) -> int:
        """Host bytes held by the stored trees (the resident-store meter)."""
        return sum(_tree_bytes((s["params"], s["opt_state"],
                                s["best_params"]))
                   for s in self._states.values())


# ---------------------------------------------------------------------------
# ClientPopulation — lazy description of a (possibly huge) population
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ClientPopulation:
    """A federated population as metadata + a lazy factory.

    ``size`` clients exist in principle; ``nfs[i]`` is client i's feature
    count (the stratified sampler's key — cheap to declare without building
    anything); ``sizes[i]``, when given, is its declared local dataset
    weight (the weighted sampler's probabilities); ``build(indices)``
    materializes exactly those clients, deterministically — calling it
    twice for the same index must produce the same name, data, and fresh
    parameter init, so a client rebuilt in a later wave is the same client.
    ``name_of(i)`` must match ``build``'s names (the store key)."""

    size: int
    nfs: np.ndarray
    build: Callable[[Sequence[int]], List[FederatedClient]]
    sizes: Optional[np.ndarray] = None
    name_of: Callable[[int], str] = lambda i: f"h{i:06d}"

    def __post_init__(self):
        self.nfs = np.asarray(self.nfs, np.int64)
        if self.nfs.shape != (self.size,):
            raise ValueError(f"nfs must have shape ({self.size},), "
                             f"got {self.nfs.shape}")
        if self.sizes is not None:
            self.sizes = np.asarray(self.sizes, np.float64)
            if self.sizes.shape != (self.size,):
                raise ValueError(f"sizes must have shape ({self.size},), "
                                 f"got {self.sizes.shape}")
            if not (self.sizes > 0).all():
                raise ValueError("sizes must be positive")

    def fingerprint(self) -> int:
        """Cheap identity check for checkpoints: size + feature layout."""
        return zlib.crc32(self.nfs.tobytes()) ^ self.size


# ---------------------------------------------------------------------------
# ParticipationPolicy — the fifth policy protocol (who is even present)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParticipationPolicy(_Spec):
    """Samples each wave's active subset of the population — host-side only
    (it runs before any engine is built, so unlike the four jitted-bundle
    protocols it never becomes a static jit argument).  Implementations
    must be deterministic functions of ``(population, rng state)`` so a
    seeded run is replayable, and must return SORTED global indices so the
    wave's client order — and with it cohort planning and the selection
    log — is engine-independent.

    ``fraction`` of the population participates per wave (at least
    ``min_clients``, at most all); ``multiple_of`` (the mesh device count,
    see :func:`repro.core.mesh_federation.participation_multiple`) rounds
    counts so the sampled set shards evenly."""

    fraction: float = 0.1
    min_clients: int = 2

    def __post_init__(self):
        if not 0 < self.fraction <= 1:
            raise ValueError(f"fraction must be in (0, 1], "
                             f"got {self.fraction}")
        if self.min_clients < 1:
            raise ValueError(f"min_clients must be >= 1, "
                             f"got {self.min_clients}")

    def n_active(self, N: int, multiple_of: int = 1) -> int:
        """The wave's sample size: fraction·N clamped to
        [min_clients, N], then rounded UP to ``multiple_of`` (capped at the
        largest multiple ≤ N)."""
        if N < 1:
            raise ValueError("empty population")
        n = min(N, max(self.min_clients, int(round(self.fraction * N))))
        if multiple_of > 1:
            if N < multiple_of:
                raise ValueError(
                    f"population of {N} cannot shard over {multiple_of} "
                    f"devices (need at least one client per device)")
            n = min(N - N % multiple_of,
                    -(-n // multiple_of) * multiple_of)
        return n

    def sample(self, population: ClientPopulation,
               rng: np.random.Generator, *,
               multiple_of: int = 1) -> np.ndarray:
        raise NotImplementedError


@register_policy
@dataclasses.dataclass(frozen=True)
class UniformParticipation(ParticipationPolicy):
    """Classic FedAvg client sampling: every client equally likely, without
    replacement."""

    def sample(self, population, rng, *, multiple_of=1):
        n = self.n_active(population.size, multiple_of)
        return np.sort(rng.choice(population.size, size=n, replace=False))


@register_policy
@dataclasses.dataclass(frozen=True)
class WeightedParticipation(ParticipationPolicy):
    """Size-weighted sampling: probability ∝ ``population.sizes`` (local
    dataset size), without replacement — large hospitals participate more
    often, mirroring FedAvg's size-weighted aggregation."""

    def sample(self, population, rng, *, multiple_of=1):
        if population.sizes is None:
            raise ValueError(
                "WeightedParticipation requires population.sizes "
                "(per-client dataset sizes); declare them on the "
                "ClientPopulation or use UniformParticipation")
        n = self.n_active(population.size, multiple_of)
        p = population.sizes / population.sizes.sum()
        return np.sort(rng.choice(population.size, size=n,
                                  replace=False, p=p))


@register_policy
@dataclasses.dataclass(frozen=True)
class StratifiedParticipation(ParticipationPolicy):
    """Stratified-by-cohort sampling: the wave quota is apportioned across
    nf strata (largest-remainder method, ascending-nf order) and drawn
    uniformly within each stratum.

    Two properties make this THE policy for heterogeneous populations:
    per-stratum counts are deterministic in the population alone, so every
    wave's :class:`~repro.core.cohorts.CohortPlan` has the same geometry
    (compile-cache hits instead of a recompile per wave); and with
    ``multiple_of=D`` each stratum count is rounded to the device count,
    which is exactly the mesh cohort engine's every-cohort-divides-D
    requirement (strata too small for one multiple are skipped)."""

    def sample(self, population, rng, *, multiple_of=1):
        from repro.core.cohorts import nf_strata
        strata = nf_strata(population.nfs)
        n = self.n_active(population.size, 1)
        # largest-remainder apportionment of n over strata
        quotas = {k: n * len(ix) / population.size
                  for k, ix in strata.items()}
        counts = {k: int(q) for k, q in quotas.items()}
        rem = n - sum(counts.values())
        for k in sorted(quotas, key=lambda k: (-(quotas[k] - counts[k]), k)):
            if rem <= 0:
                break
            counts[k] += 1
            rem -= 1
        if multiple_of > 1:
            counts = {k: min(len(strata[k]) - len(strata[k]) % multiple_of,
                             -(-c // multiple_of) * multiple_of)
                      for k, c in counts.items() if c > 0}
            counts = {k: c for k, c in counts.items() if c > 0}
            if not counts:
                sizes = {k: len(v) for k, v in strata.items()}
                raise ValueError(
                    f"no stratum of {sizes} can host a multiple of "
                    f"{multiple_of} sampled clients")
        picks = [rng.choice(ix, size=counts[k], replace=False)
                 for k, ix in strata.items() if counts.get(k, 0) > 0]
        return np.sort(np.concatenate(picks))


# ---------------------------------------------------------------------------
# ParticipatingFederation — the wave orchestrator
# ---------------------------------------------------------------------------

class ParticipatingFederation:
    """Federated training over a sampled fraction of a lazy population.

    Each wave: sample indices → ``population.build`` exactly those clients
    → overlay their stored state (params/opt/best + val history) and pool
    entries (+ ages) from the previous waves they appeared in → run ONE
    federated epoch as an inner :class:`Federation` over the subset
    (``engine``/``mesh`` pass straight through, so the batched, cohorted,
    and mesh engines all run unchanged on the gathered view) → scatter the
    updated state back to the :class:`ClientStore` and the resident pool.

    ``schedule.epochs`` is the total wave budget; ``schedule.R`` and
    ``exchange_every`` apply within each wave.  ``fit(waves=k)`` runs k
    more waves.  ``save``/``restore`` checkpoint the store, the pool, the
    sampler RNG, and both engine RNG streams — resuming mid-schedule
    replays the exact participation schedule and histories an
    uninterrupted run would have produced.

    ``faults=`` takes a :class:`~repro.core.faults.FaultPlan`: each wave
    the seeded injector drops clients (the wave re-rounds its geometry and
    proceeds degraded), marks stragglers (they train but miss every
    exchange, aging their pool entries), and corrupts byzantine clients'
    heads (quarantined by the inner engines' pool admission guard).  The
    plan spec and the accumulated fault log ride the checkpoint manifest,
    so a restored run replays the identical failure scenario.

    ``trust=`` takes a :class:`~repro.core.trust.TrustPlan`: the inner
    engines run their trust hooks each wave (masks/noise keyed by the
    GLOBAL wave number and client ids, so derivations are wave-unique and
    engine-independent), while the orchestrator owns the cross-wave state:
    a per-client :class:`~repro.core.trust.DPAccountant` composing epsilon
    over every wave, and a :class:`~repro.core.trust.ReputationBook` that
    strikes clients failing watermark verification and QUARANTINES repeat
    offenders — dropped from subsequent waves (geometry re-rounded like
    dropout; a wave never goes empty, so if every sampled client is
    quarantined the first-drawn are revived) with their resident pool rows
    zeroed at ``faults.QUARANTINE_AGE``.  Both books ride the checkpoint
    manifest bit-identically."""

    def __init__(self, population: ClientPopulation,
                 cfg: Optional[HFLConfig] = None, *,
                 policies: Optional[FederationPolicies] = None,
                 participation: Optional[ParticipationPolicy] = None,
                 schedule: Optional[RoundSchedule] = None,
                 engine: str = "batched",
                 mesh=None,
                 sample_multiple: Optional[int] = None,
                 faults: Optional[FT.FaultPlan] = None,
                 trust: Optional[TR.TrustPlan] = None,
                 telemetry: Optional[TEL.TelemetryPlan] = None):
        self.population = population
        self.cfg = cfg or HFLConfig()
        self.policies = policies if policies is not None \
            else FederationPolicies.from_config(self.cfg)
        self.participation = participation or UniformParticipation()
        self.schedule = schedule or RoundSchedule(self.cfg.epochs,
                                                  self.cfg.R)
        if engine not in ("sequential", "batched"):
            raise ValueError(f"unknown engine {engine!r}")
        if mesh is not None and engine != "batched":
            raise ValueError("mesh= requires engine='batched'")
        self.engine = engine
        self.mesh = mesh
        # deterministic fault injection (core/faults.py): a disabled or
        # absent plan is exactly "no faults" — the wave loop and the inner
        # engines run their historical bit-identical paths
        self.faults = faults
        self._injector = FT.FaultInjector(faults) \
            if faults is not None and faults.enabled else None
        self.fault_log: List[FT.WaveFaults] = []
        # trust layer (core/trust.py): the inner engines privatize/verify
        # per wave; the orchestrator composes the cross-wave books
        if trust is not None and not isinstance(trust, TR.TrustPlan):
            raise TypeError(f"trust: expected a TrustPlan, "
                            f"got {type(trust).__name__}")
        self.trust = trust
        self._trust = trust if trust is not None and trust.enabled else None
        self.accountant = (TR.DPAccountant(trust.dp)
                           if self._trust is not None
                           and trust.dp is not None else None)
        self.reputation = (TR.ReputationBook(trust.watermark)
                           if self._trust is not None
                           and trust.watermark is not None else None)
        self.clip_events = 0
        self.wm_failures: Dict[str, int] = {}
        # telemetry: ONE flight recorder spans all waves — each wave's inner
        # Federation is handed this recorder (its spans and in-graph round
        # series land in the shared ring buffer), so the exported trace
        # shows the whole sampled run: sample → gather → exchange(fit(
        # dispatch…)) → scatter per wave
        if telemetry is not None \
                and not isinstance(telemetry, TEL.TelemetryPlan):
            raise TypeError(f"telemetry: expected a TelemetryPlan, "
                            f"got {type(telemetry).__name__}")
        self.telemetry = telemetry
        self._telemetry = telemetry if telemetry is not None \
            and telemetry.enabled else None
        self._recorder = (TEL.FlightRecorder(self._telemetry)
                          if self._telemetry is not None else None)
        # the granularity sampled counts are rounded to — defaults to the
        # mesh device count; pass it explicitly to reproduce a D-device
        # run's exact participation schedule on another engine/mesh (the
        # oracle-parity tests' lever: the sequential oracle with
        # sample_multiple=D sees the same subsets a D-device mesh run does)
        self.sample_multiple = sample_multiple
        self.store = ClientStore()
        # the always-resident structure: head-pool entries + ages, host-side
        self.pool_entries: Dict[tuple, dict] = {}
        self.pool_ages: Dict[tuple, int] = {}
        self.wave = 0
        self.n_rounds: Dict[str, int] = {}
        self.selections: Dict[str, list] = {}
        self.last_test: Dict[str, float] = {}
        self.wave_log: List[dict] = []
        seed = self.cfg.seed
        # sampler stream distinct from both engine streams (which keep the
        # inner Federation's seeds so a full-participation wave IS a plain
        # Federation epoch)
        self._part_rng = np.random.default_rng(
            np.random.SeedSequence([seed, 0x9A]))
        self._sel_rng = np.random.default_rng(seed)
        self._switch_rng = np.random.default_rng(
            np.random.SeedSequence([seed, 0x5F]))
        self._key = jax.random.PRNGKey(seed)
        self.dispatch_stats: Optional[dict] = None

    # -- training ----------------------------------------------------------

    def _wave_multiple(self) -> int:
        if self.sample_multiple is not None:
            return self.sample_multiple
        return MF.participation_multiple(
            self.mesh if self.mesh is not None
            and MF.mesh_devices(self.mesh) > 1 else None)

    def fit(self, waves: Optional[int] = None, verbose: bool = False):
        """Run ``waves`` more sampling waves (default: up to
        ``schedule.epochs`` total) and return per-touched-client history
        {name: {val, rounds, best_val, selections, test}} — ``test`` is
        the client's test MSE as of its LAST resident wave (test data is
        not resident between waves)."""
        target = self.schedule.epochs if waves is None \
            else self.wave + waves
        mult = self._wave_multiple()
        n_waves = 0
        gather_bytes = scatter_bytes = 0
        resident_clients = resident_bytes = 0
        dispatches = exchange_rounds = pool_bytes = 0
        heads_rejected = clients_dropped = stragglers_n = 0
        waves_degraded = store_rebuilds = 0
        cohorts_max = 1
        path = None
        quarantined_drops = 0
        rec = self._recorder
        while self.wave < target:
            with TEL.span(rec, "sample", wave=self.wave):
                idx = self.participation.sample(self.population,
                                                self._part_rng,
                                                multiple_of=mult)
                active = [int(i) for i in idx]
                if self.reputation is not None:
                    # reputation quarantine: strip quarantined clients from
                    # the wave BEFORE fault injection / building (geometry
                    # re-rounded like dropout; the sampler's RNG sequence
                    # is untouched, so the participation schedule stays
                    # replayable)
                    quar = [i for i in active
                            if self.reputation.is_quarantined(
                                self.population.name_of(i))]
                    if quar:
                        active, _ = FT.reround_wave(active, quar, mult)
                        quarantined_drops += len(quar)
                        if rec is not None:
                            rec.count("quarantined_drops", len(quar))
                wf = None
                if self._injector is not None:
                    # dropout-tolerant wave: drop drawn clients and
                    # re-round the geometry BEFORE anything is built or
                    # gathered — the fused engines never see a ragged
                    # stack.  The draw is a pure function of (plan.seed,
                    # wave, index), so a restored run replays the
                    # identical degraded schedule.
                    wf = self._injector.wave_faults(self.wave, active, mult)
                    dropped = set(wf.dropped)
                    active = [i for i in active if i not in dropped]
                    self.fault_log.append(wf)
                    clients_dropped += len(wf.dropped)
                    stragglers_n += len(wf.stragglers)
                    waves_degraded += int(wf.degraded)
                    if rec is not None:
                        if wf.dropped:
                            rec.count("clients_dropped", len(wf.dropped))
                        if wf.stragglers:
                            rec.count("stragglers", len(wf.stragglers))
                        if wf.degraded:
                            rec.count("waves_degraded", 1)
            with TEL.span(rec, "gather", wave=self.wave,
                          clients=len(active)):
                clients = self.population.build(active)
                names = [self.population.name_of(i) for i in active]
                got = [c.name for c in clients]
                if got != names:
                    raise ValueError(
                        f"population.build returned names {got} for "
                        f"indices {active}, expected {names} (name_of and "
                        f"build must agree — the store is keyed by name)")
                # gather: stored state onto the freshly built clients.  A
                # checksum-corrupt entry is discarded and the client
                # rebuilt from its deterministic fresh init (the
                # self-healing path).
                for c in clients:
                    if c.name in self.store:
                        try:
                            st = self.store.get(c.name)
                        except StoreCorruption:
                            self.store.discard(c.name)
                            store_rebuilds += 1
                            if rec is not None:
                                rec.count("store_rebuilds", 1)
                            continue
                        c.params = st["params"]
                        c.opt_state = st["opt_state"]
                        c.best_params = st["best_params"]
                        c.best_val = st["best_val"]
                        c.val_history = list(st["val_history"])
                if wf is not None and wf.byzantine:
                    # byzantine clients' heads are corrupted host-side
                    # before the wave trains; the inner Federation's
                    # admission guard quarantines the poisoned publication
                    # at pool-seed time and rejects any poisoned
                    # republication in-graph
                    byz = set(wf.byzantine)
                    for c, i in zip(clients, active):
                        if i in byz:
                            c.params = dict(c.params)
                            c.params["heads"] = \
                                self._injector.corrupt_heads(
                                    c.params["heads"], self.wave, i)
            fed = Federation(
                clients, self.cfg, policies=self.policies,
                schedule=RoundSchedule(1, self.schedule.R,
                                       self.schedule.exchange_every),
                engine=self.engine, mesh=self.mesh, faults=self.faults,
                trust=self.trust, telemetry=self.telemetry)
            if self._recorder is not None:
                # ONE ring buffer for the whole sampled run: the inner
                # Federation's spans, round series, and counters land in
                # this orchestrator's recorder instead of a per-wave one
                fed._recorder = self._recorder
            # trust derivations (pairwise masks, oracle DP noise) key on the
            # GLOBAL wave number and GLOBAL client ids: unique per wave,
            # identical across engines/meshes for the same sampled subset
            fed._trust_wave_base = self.wave
            fed._trust_ids = tuple(active)
            if wf is not None and wf.stragglers:
                # stragglers train but miss every exchange this wave: the
                # engines mask their switch off, so their pool entries age
                # under the bounded-staleness clock
                strag = set(wf.stragglers)
                fed._straggler_mask = np.array([i in strag for i in active],
                                               bool)
            # the RNG streams and device key persist ACROSS waves: the
            # generators are shared by reference (mutated in place by the
            # inner fit), the key is threaded through explicitly
            fed._sel_rng = self._sel_rng
            fed._switch_rng = self._switch_rng
            fed._key = self._key
            # pool carry: clients seen before serve their carried entries
            # (+ ages); first-timers keep the fresh publication the inner
            # Federation just made (asynchronous start, age 0)
            for c in clients:
                for f in range(c.nf):
                    k = (c.name, f)
                    if k in self.pool_entries:
                        fed.pool.entries[k] = self.pool_entries[k]
                        fed.pool.ages[k] = self.pool_ages[k]
            with TEL.span(rec, "exchange", wave=self.wave):
                hist = fed.fit()
            self._key = fed._key
            # scatter: updated state back to the store, pool back to the
            # resident pool
            with TEL.span(rec, "scatter", wave=self.wave):
                for c in fed.clients:
                    self.store.put(c.name, params=c.params,
                                   opt_state=c.opt_state,
                                   best_params=c.best_params,
                                   best_val=c.best_val,
                                   val_history=c.val_history)
                    self.n_rounds[c.name] = (self.n_rounds.get(c.name, 0)
                                             + fed.n_rounds[c.name])
                    self.selections.setdefault(c.name, []).extend(
                        fed.selections[c.name])
                    self.last_test[c.name] = hist[c.name]["test"]
                    for f in range(c.nf):
                        k = (c.name, f)
                        self.pool_entries[k] = host_tree(
                            fed.pool.entries[k])
                        self.pool_ages[k] = int(fed.pool.ages[k])
            newly_q: List[str] = []
            if self._trust is not None:
                # fold the wave's trust counters into the cross-wave books
                self.clip_events += fed._clip_events
                if self.accountant is not None:
                    for nm, k in sorted(fed._dp_counts.items()):
                        self.accountant.record(nm, k)
                for nm, k in sorted(fed._wm_failures.items()):
                    if k:
                        self.wm_failures[nm] = (self.wm_failures.get(nm, 0)
                                                + int(k))
                        if self.reputation is not None \
                                and self.reputation.strike(nm):
                            newly_q.append(nm)
                # quarantine action: a newly quarantined client's resident
                # pool rows are zeroed at the QUARANTINE sentinel, so no
                # engine ever serves its poisoned knowledge again
                for nm in newly_q:
                    for k in list(self.pool_entries):
                        if k[0] == nm:
                            self.pool_entries[k] = jax.tree_util.tree_map(
                                np.zeros_like, self.pool_entries[k])
                            self.pool_ages[k] = FT.QUARANTINE_AGE
            st = fed.dispatch_stats or {}
            sb = int(st.get("state_bytes", 0))
            gather_bytes += sb
            scatter_bytes += sb
            resident_clients = max(resident_clients, len(clients))
            resident_bytes = max(resident_bytes, sb)
            dispatches += int(st.get("dispatches", 0))
            exchange_rounds += int(st.get("exchange_rounds", 0))
            pool_bytes += int(st.get("pool_bytes_gathered", 0))
            heads_rejected += int(st.get("heads_rejected", 0))
            cohorts_max = max(cohorts_max, int(st.get("cohorts", 1)))
            path = st.get("path", path)
            # a byzantine client's own validation goes NaN (it trains on
            # its corrupted state, sacrificially) — the wave mean reports
            # over the finite clients so the degradation curve stays real
            finals = [hist[n]["val"][-1] for n in names]
            finite = [v for v in finals if np.isfinite(v)]
            mean_val = float(np.mean(finite)) if finite else float("nan")
            row = {
                "wave": self.wave, "active": active,
                "mean_val": mean_val,
                "state_bytes": sb,
                "rounds": sum(fed.n_rounds.values()),
            }
            if wf is not None:
                row["dropped"] = list(wf.dropped)
                row["stragglers"] = list(wf.stragglers)
                row["byzantine"] = list(wf.byzantine)
            if self._trust is not None:
                if self.accountant is not None:
                    row["epsilon"] = self.accountant.max_epsilon
                if newly_q:
                    row["quarantined"] = newly_q
            self.wave_log.append(row)
            if verbose:
                print(f"[wave {self.wave:3d}] {len(clients)}/"
                      f"{self.population.size} clients  "
                      f"val={mean_val:9.4f}  resident={sb / 1e6:.1f}MB  "
                      f"store={len(self.store)}")
            self.wave += 1
            n_waves += 1
        self.dispatch_stats = {
            "engine": f"participating+{self.engine}",
            "path": path,
            "devices": MF.mesh_devices(self.mesh) if self.mesh is not None
            else 1,
            "cohorts": cohorts_max,
            "population": self.population.size,
            "participation": type(self.participation).__name__,
            "participation_fraction": self.participation.fraction,
            "waves": n_waves,
            "resident_clients": resident_clients,
            "resident_state_bytes": resident_bytes,
            "store_clients": len(self.store),
            "store_bytes": self.store.nbytes(),
            "gather_bytes": gather_bytes,
            "scatter_bytes": scatter_bytes,
            "epochs": n_waves,
            "dispatches": dispatches,
            "dispatches_per_epoch": dispatches / max(n_waves, 1),
            "exchange_every": self.schedule.exchange_every,
            "exchange_rounds": exchange_rounds,
            "pool_bytes_gathered": pool_bytes,
            "heads_rejected": heads_rejected,
            "clients_dropped": clients_dropped,
            "stragglers": stragglers_n,
            "waves_degraded": waves_degraded,
            "store_rebuilds": store_rebuilds,
            "epsilon_spent": (self.accountant.max_epsilon
                              if self.accountant is not None else 0.0),
            "clip_events": self.clip_events,
            "watermark_failures": sum(self.wm_failures.values()),
            "quarantined": (sorted(self.reputation.quarantined)
                            if self.reputation is not None else []),
            "quarantined_drops": quarantined_drops,
        }
        return self.results()

    def results(self):
        """Per-touched-client history in the legacy format (see fit)."""
        return {n: {"val": list(self.store.get(n)["val_history"]),
                    "test": self.last_test[n],
                    "rounds": self.n_rounds[n],
                    "best_val": float(self.store.get(n)["best_val"]),
                    "selections": [list(s) for s in self.selections[n]]}
                for n in self.store.names()}

    # -- persistence -------------------------------------------------------

    def save(self, directory) -> Path:
        """Checkpoint the orchestrator for replayable resume: the client
        store, the resident pool (+ ages), the participation sampler's RNG,
        both engine RNG streams, the device key, and every counter —
        restore + fit reproduces the exact waves and histories an
        uninterrupted run would have.  Same durable two-file layout as
        :meth:`Federation.save` (atomic manifest commit)."""
        d = Path(directory)
        d.mkdir(parents=True, exist_ok=True)
        state = {
            "wave": self.wave,
            "store": {n: self.store.get(n) for n in self.store.names()},
            "pool": {f"{u}|{i}": e
                     for (u, i), e in self.pool_entries.items()},
            "key": np.asarray(self._key),
        }
        state_name = f"state_{self.wave:08d}.msgpack"
        ckpt.save(d / state_name, state)
        manifest = {
            "format": 1,
            "kind": "participating_federation",
            "state_file": state_name,
            "wave": self.wave,
            "engine": self.engine,
            "cfg": dataclasses.asdict(self.cfg),
            "policies": self.policies.spec(),
            "participation": self.participation.spec(),
            "schedule": {"epochs": self.schedule.epochs,
                         "R": self.schedule.R,
                         "exchange_every": self.schedule.exchange_every},
            "population_size": self.population.size,
            "population_fingerprint": self.population.fingerprint(),
            # the EFFECTIVE rounding multiple, so a restore reproduces this
            # run's exact schedule even onto a different mesh (or none)
            "sample_multiple": self._wave_multiple(),
            "n_rounds": self.n_rounds,
            "selections": self.selections,
            "last_test": self.last_test,
            "wave_log": self.wave_log,
            "pool_ages": {f"{u}|{i}": a
                          for (u, i), a in self.pool_ages.items()},
            "part_rng": self._part_rng.bit_generator.state,
            "sel_rng": self._sel_rng.bit_generator.state,
            "switch_rng": self._switch_rng.bit_generator.state,
            # the failure scenario rides the manifest: the plan spec
            # re-seeds the injector (draws are pure functions of
            # (seed, wave, index), so no RNG state to carry) and the log
            # records the faults that already fired, so a restored run
            # replays the exact degraded schedule
            "faults": (self.faults.spec()
                       if self.faults is not None else None),
            "fault_log": FT.fault_log_json(self.fault_log),
            # the trust books are integer counts / name sets — a JSON
            # round-trip is bit-identical by construction
            "trust": (self.trust.spec()
                      if self.trust is not None else None),
            "trust_state": {
                "accountant": (self.accountant.to_json()
                               if self.accountant is not None else None),
                "reputation": (self.reputation.to_json()
                               if self.reputation is not None else None),
                "clip_events": self.clip_events,
                "wm_failures": self.wm_failures,
            },
            # the flight recorder rides the manifest so a restored run
            # CONTINUES its trace: same ring, monotonically later
            # timestamps, counters picking up where they stopped
            "telemetry": (self.telemetry.spec()
                          if self.telemetry is not None else None),
            "telemetry_state": (self._recorder.to_json()
                                if self._recorder is not None else None),
        }
        tmp = d / "manifest.json.tmp"
        tmp.write_text(json.dumps(manifest))
        os.replace(tmp, d / "manifest.json")
        for p in d.glob("state_*.msgpack"):
            if p.name != state_name:
                p.unlink()
        return d

    @classmethod
    def restore(cls, directory, population: ClientPopulation, *,
                engine: Optional[str] = None,
                mesh=None,
                sample_multiple: Optional[int] = None
                ) -> "ParticipatingFederation":
        """Rebuild a saved orchestrator over the same (re-declared) lazy
        population.  The population is identity-checked by size + feature
        layout; its ``build`` is only ever called for newly sampled waves,
        with stored state overlaid as usual."""
        d = Path(directory)
        manifest = json.loads((d / "manifest.json").read_text())
        if manifest.get("kind") != "participating_federation":
            raise ValueError(
                f"{d} is not a ParticipatingFederation checkpoint "
                f"(kind={manifest.get('kind')!r}); Federation checkpoints "
                f"restore via Federation.restore")
        if manifest["population_size"] != population.size \
                or manifest["population_fingerprint"] \
                != population.fingerprint():
            raise ValueError(
                f"population mismatch: checkpoint was taken over "
                f"{manifest['population_size']} clients (fingerprint "
                f"{manifest['population_fingerprint']}), got "
                f"{population.size} ({population.fingerprint()}) — "
                f"re-declare the population with the same arguments")
        cfg = HFLConfig(**manifest["cfg"])
        fspec = manifest.get("faults")
        tspec = manifest.get("trust")
        espec = manifest.get("telemetry")
        fed = cls(population, cfg,
                  policies=FederationPolicies.from_spec(
                      manifest["policies"]),
                  participation=policy_from_spec(manifest["participation"]),
                  schedule=RoundSchedule(**manifest["schedule"]),
                  engine=engine or manifest["engine"],
                  mesh=mesh,
                  sample_multiple=sample_multiple
                  or manifest.get("sample_multiple"),
                  faults=policy_from_spec(fspec) if fspec else None,
                  trust=policy_from_spec(tspec) if tspec else None,
                  telemetry=policy_from_spec(espec) if espec else None)
        state = ckpt.load(d / manifest["state_file"])
        if state.get("wave") != manifest["wave"]:
            raise ValueError(
                f"checkpoint is torn: state file at wave "
                f"{state.get('wave')} but manifest at {manifest['wave']} — "
                f"re-save or fall back to an older checkpoint")
        for n, s in state["store"].items():
            fed.store.put(n, params=s["params"], opt_state=s["opt_state"],
                          best_params=s["best_params"],
                          best_val=s["best_val"],
                          val_history=s["val_history"])
        fed.pool_entries = {
            (k.rsplit("|", 1)[0], int(k.rsplit("|", 1)[1])): e
            for k, e in state["pool"].items()}
        fed.pool_ages = {
            (k.rsplit("|", 1)[0], int(k.rsplit("|", 1)[1])): int(a)
            for k, a in manifest["pool_ages"].items()}
        fed.wave = int(manifest["wave"])
        fed.n_rounds = {n: int(v)
                        for n, v in manifest["n_rounds"].items()}
        fed.selections = {n: [list(s) for s in v]
                          for n, v in manifest["selections"].items()}
        fed.last_test = {n: float(v)
                         for n, v in manifest["last_test"].items()}
        fed.wave_log = list(manifest["wave_log"])
        fed.fault_log = FT.fault_log_from_json(
            manifest.get("fault_log", []))
        fed._key = jnp.asarray(state["key"])
        fed._part_rng.bit_generator.state = manifest["part_rng"]
        fed._sel_rng.bit_generator.state = manifest["sel_rng"]
        fed._switch_rng.bit_generator.state = manifest["switch_rng"]
        ts = manifest.get("trust_state") or {}
        if fed.accountant is not None:
            fed.accountant = TR.DPAccountant.from_json(
                fed.trust.dp, ts.get("accountant"))
        if fed.reputation is not None:
            fed.reputation = TR.ReputationBook.from_json(
                fed.trust.watermark, ts.get("reputation"))
        fed.clip_events = int(ts.get("clip_events", 0))
        fed.wm_failures = {n: int(v)
                           for n, v in (ts.get("wm_failures") or {}).items()}
        rs = manifest.get("telemetry_state")
        if rs is not None and fed._telemetry is not None:
            fed._recorder = TEL.FlightRecorder.from_json(
                fed._telemetry, rs)
        return fed
