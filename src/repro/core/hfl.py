"""Heterogeneous Federated Learning primitives (paper §4.2).

Implements, faithfully:
  * the asynchronous **head pool** (decentralized: every user publishes its
    nf global-head weight sets; stale versions remain usable),
  * **heterogeneous domain selection** (Eq. 7): for each target head H_i pick
    the pool model with the smallest preliminary-prediction *squared* error
    on the target's own last R samples (Eq. 7 as printed omits the square;
    Eqs. 3/6 define the error as squared — we use squared, noted in DESIGN),
  * **alpha-blending** (Eq. 8): H_i <- alpha * H_hat + (1-alpha) * H_i,
  * the **switching mechanism**: selection+blend only in epochs where the
    validation loss has not improved for `patience` consecutive epochs,
  * the ablation modes of §5.5: no / random / always / hfl.

Training protocol per the paper §4.2/§5.2: one gradient-descent update per R
consecutive periods (batch = R samples), Adam lr 0.01, 50 epochs, save-best
on validation.

Orchestration lives in `core/federation.py` (the composable Federation API:
pluggable policies, callbacks, resumable state, the sequential and batched
executors); the pluggable policy implementations live in `core/policies.py`.
This module keeps the paper primitives — the client, the pool, Eq.-7
scoring, Eq.-8 blending — plus :func:`run_federated_training`, the thin
legacy entry point that maps ``HFLConfig.mode`` strings onto the policy API.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import networks as N
from repro.core.policies import plateaued
from repro.optim import adam, apply_updates
from repro.sharding import spec as S


@dataclasses.dataclass
class HFLConfig:
    w: int = 3
    R: int = 50
    alpha: float = 0.2
    lr: float = 0.01
    epochs: int = 50
    patience: int = 3
    mode: str = "hfl"            # hfl | no | random | always
    use_pool_kernel: bool = False  # Pallas pool-scoring kernel (compiled on
                                   # an accelerator, interpret mode on CPU)
    seed: int = 0


def switch_active(val_history: Sequence[float], cfg: HFLConfig) -> bool:
    """Switching mechanism: FL only when validation has plateaued for
    `patience` epochs (always/random modes bypass; no disables).  The core
    plateau rule is :func:`repro.core.policies.plateaued`; explicit policy
    objects (policies.PlateauSwitch etc.) are the composable form."""
    mode = cfg.mode
    if mode == "no":
        return False
    if mode in ("always", "random"):
        return True
    return plateaued(val_history, cfg.patience)


# ---------------------------------------------------------------------------
# Client
# ---------------------------------------------------------------------------

def _train_step(opt, params, opt_state, xs, xd, y):
    """One Adam update on one client's R-batch.  The SINGLE definition both
    engines build on — sequential jits it directly, batched vmaps it — so
    they cannot drift apart."""
    (loss, parts), grads = jax.value_and_grad(
        N.hfl_loss, has_aux=True)(params, xs, xd, y)
    updates, opt_state = opt.update(grads, opt_state, params)
    return apply_updates(params, updates), opt_state, loss


def _eval_mse(params, xs, xd, y):
    y_hat, _ = N.hfl_forward(params, xs, xd)
    return jnp.mean((y - y_hat) ** 2)


@functools.lru_cache(maxsize=None)
def _client_fns(lr: float):
    """Per-lr shared (optimizer, jitted train step, jitted eval) so N clients
    compile once, not N times."""
    opt = adam(lr)
    return (opt, jax.jit(functools.partial(_train_step, opt)),
            jax.jit(_eval_mse))


class FederatedClient:
    """One hospital: local data, local model, recent-R scoring buffer."""

    def __init__(self, name: str, nf: int, cfg: HFLConfig,
                 train, valid, test, rng):
        self.name, self.nf, self.cfg = name, nf, cfg
        self.train, self.valid, self.test = train, valid, test  # (xs, xd, y)
        schema = N.hfl_schema(nf, cfg.w)
        self.params = S.materialize(schema, rng)
        self.opt, self._train_step, self._eval_mse = _client_fns(cfg.lr)
        self.opt_state = self.opt.init(self.params)
        self.val_history: List[float] = []
        self.best_val = np.inf
        self.best_params = self.params
        self._recent: Optional[Tuple[np.ndarray, np.ndarray]] = None  # xd, y

    def train_epoch(self, R: Optional[int] = None) -> Iterator[None]:
        """Generator over the epoch's R-batches: one Adam update per batch,
        yielding after each — a yield is one federated opportunity.  `R`
        defaults to the client's config (a Federation passes its schedule's
        R so both executors slice identically)."""
        xs, xd, y = self.train
        R = self.cfg.R if R is None else R
        for start in range(0, len(y) - R + 1, R):
            sl = slice(start, start + R)
            self.params, self.opt_state, _ = self._train_step(
                self.params, self.opt_state, xs[sl], xd[sl], y[sl])
            self._recent = (xd[sl], y[sl])
            yield

    def val_mse(self) -> float:
        return float(self._eval_mse(self.params, *self.valid))

    def test_mse(self, params=None) -> float:
        return float(self._eval_mse(params if params is not None
                                    else self.best_params, *self.test))

    def end_epoch(self) -> None:
        v = self.val_mse()
        self.val_history.append(v)
        if v < self.best_val:
            self.best_val = v
            self.best_params = self.params

    def fl_active(self) -> bool:
        return switch_active(self.val_history, self.cfg)


# ---------------------------------------------------------------------------
# Pool
# ---------------------------------------------------------------------------

class HeadPool:
    """Decentralized asynchronous pool of shared head-layer weights.

    Entries persist until overwritten ("the last version stored in the
    pool"), so a user that skips publication rounds still contributes its
    stale heads — the paper's asynchrony semantics.  Each entry carries an
    age (federated opportunities since publication, advanced by
    :meth:`tick`) so a bounded :class:`~repro.core.policies.PoolPolicy` can
    hide — not delete — entries that have gone unrefreshed too long."""

    def __init__(self):
        self.entries: Dict[Tuple[str, int], dict] = {}
        self.ages: Dict[Tuple[str, int], int] = {}

    def publish(self, user: str, head_params_stacked, nf: int,
                age: int = 0) -> None:
        for i in range(nf):
            entry = jax.tree_util.tree_map(lambda p: p[i], head_params_stacked)
            self.entries[(user, i)] = entry
            self.ages[(user, i)] = age

    def tick(self) -> None:
        """Advance every entry's age by one federated opportunity."""
        for k in self.ages:
            self.ages[k] += 1

    def age_of(self, user: str) -> int:
        """A user's publication age (its entries are published together)."""
        return self.ages.get((user, 0), 0)

    def stacked_for(self, exclude_user: str):
        """All pool heads from OTHER users, stacked to (ns, ...)."""
        keys = [k for k in sorted(self.entries) if k[0] != exclude_user]
        if not keys:
            return None, []
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[self.entries[k] for k in keys])
        return stacked, keys

    def fresh_mask(self, exclude_user: str, max_age: Optional[int] = None,
                   keys: Optional[List[Tuple[str, int]]] = None) -> np.ndarray:
        """Validity mask aligned with :meth:`stacked_for`'s sorted keys:
        True where the entry is young enough to be served (always, when
        `max_age` is None — last-write-wins).  Pass the `keys` that
        stacked_for returned to guarantee alignment with its rows."""
        from repro.core import faults as FT
        if keys is None:
            keys = [k for k in sorted(self.entries) if k[0] != exclude_user]
        if max_age is None:
            # Unbounded pools still hide quarantined rows (entries seeded
            # from an inadmissible head at FT.QUARANTINE_AGE) — a clean
            # republication resets the age and revives the row.
            return np.array([self.ages.get(k, 0) < FT.QUARANTINE_AGE
                             for k in keys], bool)
        return np.array([self.ages.get(k, 0) <= max_age for k in keys],
                        bool)


# ---------------------------------------------------------------------------
# Selection scoring (Eq. 7) + blending (Eq. 8)
# ---------------------------------------------------------------------------

@jax.jit
def pool_errors(pool_stacked, xd_i, y):
    """Mean squared preliminary-prediction error of every pool head on the
    client's last-R dense vectors of feature i.  xd_i: (R, w); y: (R,).
    Returns (ns,).  Non-finite errors (a NaN/Inf pool head or probe) are
    pinned to +inf so ``argmin`` never selects a poisoned candidate —
    finite scores pass through bit-exactly."""
    preds = N.head_pool_apply(pool_stacked, xd_i)      # (ns, R)
    errs = jnp.mean((y[None, :] - preds) ** 2, axis=1)
    return jnp.where(jnp.isfinite(errs), errs, jnp.inf)


@jax.jit
def blend(target_heads_stacked, selected_stacked, alpha: float):
    """Eq. 8 applied to all nf heads at once."""
    return jax.tree_util.tree_map(
        lambda t, s: alpha * s + (1 - alpha) * t,
        target_heads_stacked, selected_stacked)


def federated_round(client: FederatedClient, pool: HeadPool,
                    rng: np.random.Generator) -> Optional[List[int]]:
    """One heterogeneous-transfer round for `client` (paper Fig. 6) under the
    client's legacy ``cfg.mode`` — a shim over
    :func:`repro.core.federation.policy_round` with the mode's policy bundle.
    Returns the selected pool indices per feature (for logging), or None."""
    from repro.core.federation import policy_round
    from repro.core.policies import FederationPolicies
    return policy_round(client, pool, rng,
                        FederationPolicies.from_config(client.cfg),
                        use_kernel=client.cfg.use_pool_kernel)


# ---------------------------------------------------------------------------
# Orchestration (legacy entry point over the Federation API)
# ---------------------------------------------------------------------------

def run_federated_training(clients: Sequence[FederatedClient],
                           cfg: HFLConfig, verbose: bool = False,
                           engine: str = "sequential"):
    """Decentralized HFL over a set of clients — compat shim over
    :class:`repro.core.federation.Federation` with the ``cfg.mode`` legacy
    shorthand expanded to an explicit policy bundle.

    engine="sequential": the reference oracle (Python loop, HeadPool object,
    host-side per-feature argmin); handles heterogeneous nf / ragged data.
    engine="batched": vmapped train steps + one fused selection scan per
    round; heterogeneous populations are cohort-planned automatically
    (see ``repro.core.cohorts``).  Both record the same history:
    {name: {"val": [...], "test": float, "rounds": int, "best_val": float,
    "selections": [[...], ...]}} — selections are indices into the pool
    sorted by (user, feature) excluding the client itself, identical across
    engines for modes hfl/always/no (random draws from different rng
    streams)."""
    from repro.core.federation import Federation
    return Federation(clients, cfg, engine=engine).fit(verbose=verbose)
