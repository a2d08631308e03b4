"""Federated-round scaling benchmark: sequential oracle vs batched engine.

  PYTHONPATH=src python -m benchmarks.fl_scale_bench [--clients 2,8,32,128]

Sweeps the number of simulated hospitals and reports, per engine, the mean
wall time of one federated sub-round (train step + selection + blend +
publication for every client), the round throughput in client-rounds/s, and
the number of compiled-function dispatches per epoch.  The sequential
engine dispatches C train steps, C x nf pool scorings, and C x nf host-side
argmin syncs per sub-round; the batched engine scans the WHOLE epoch inside
one jitted dispatch (train steps, policy rounds, eval, save-best merge)
with donated state buffers.  Each engine run is preceded by an
identically-shaped warmup run so compile time is excluded.

``--mesh`` adds a ``batched+mesh`` row per client count: the same fused
epoch, client-sharded over a `clients` device mesh spanning every local
device (see `repro.core.mesh_federation` and docs/SCALING.md) — the
devices x clients scaling axis.  ``--force-devices N`` splits the host CPU
into N virtual devices (must be handled before jax initializes, so it is
read straight from argv) to exercise the sharded path without
accelerators; client counts not divisible by the device count skip the
mesh row.

``--exchange-every 1,2`` sweeps bounded-staleness cadences
(``RoundSchedule.exchange_every``): heads are exchanged every k-th
sub-round, so each row also reports ``exchange_rounds`` and the analytic
``pool_bytes_gathered`` comms counter from ``dispatch_stats``.  The
sequential oracle runs only at k=1 (the speedup baseline), and
``--max-seq-clients`` skips it entirely above a client count (its Python
loop dominates at large C; speedup becomes null).  Throughput counts TRAIN
sub-rounds at every cadence, so rows at different k measure the same work.

Uses deterministic random tensors (not the synthetic-hospital generator) so
the sweep measures the engine, not data generation; ``--population`` switches
to `repro.data.synthetic.make_population` data instead.  ``--profile`` adds
a per-phase (train / policy / eval) wall-time split of the batched engine's
building blocks at each client count.

``--hetero`` additionally sweeps a MIXED-nf population (feature counts
cycling nf-1 / nf / nf+1 — up to three cohorts): the batched engine routes it
through the cohort subsystem (`repro.core.cohorts` — per-cohort stacks, one
fused dispatch per epoch, padded union-pool exchange) while the sequential
oracle remains the only other engine that can run it at all.  Those rows
are tagged ``hetero: true`` and carry the cohort count, and their
speedup-vs-sequential column is computed within the hetero pair.

``--population-size N`` adds a SAMPLED-PARTICIPATION row per cadence: a
lazy ``tensor_population`` of N clients (declared in O(N) metadata — no
tensors materialize until sampled) trained through
`repro.core.participation.ParticipatingFederation`, with ``--fraction`` /
``--participation {uniform,weighted,stratified}`` / ``--waves`` shaping
the policy.  Those rows report the POPULATION columns every row now
carries: ``population`` (total declared clients), ``participation_fraction``,
``resident_clients`` and ``resident_state_bytes`` (the peak device-resident
learnable state — the bounded-working-set meter; full-population rows
report their own C / 1.0 / C / state_bytes).  This is how the 100k-client
row in BENCH_fl_scale.json is produced.

``--fault-rate 0,0.2,0.4`` (with ``--population-size``) adds one
fault-injected participation row per rate — a seeded
`repro.core.faults.FaultPlan` with that per-wave dropout probability and
``--byzantine-frac`` NaN-head corruption — emitting the
graceful-degradation curve: every row carries ``fault_rate`` /
``byzantine_frac`` / ``heads_rejected`` / ``waves_degraded`` / ``mean_val``
(final-wave mean validation MSE over finite clients), 0 / 0 / 0 / 0 / null
on faultless rows.

Besides the CSV on stdout, writes a machine-readable ``BENCH_fl_scale.json``
at the repo root (``--out`` to redirect, ``--out ""`` to disable;
:func:`validate_payload` pins its schema, and CI smoke-runs a tiny sweep
against it) so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
import warnings
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO_ROOT / "src"))


def _force_devices_from_argv() -> None:
    """Apply ``--force-devices N`` BEFORE jax first initializes — jax locks
    the host platform device count at first init, so argparse (which runs
    after the imports below) would be too late.  Accepts both the
    space-separated and ``--force-devices=N`` spellings; a missing value
    is left for argparse to report."""
    n = None
    for i, arg in enumerate(sys.argv):
        if arg == "--force-devices" and i + 1 < len(sys.argv):
            n = sys.argv[i + 1]
        elif arg.startswith("--force-devices="):
            n = arg.split("=", 1)[1]
    if n is None:
        return
    try:
        count = int(n)
    except ValueError:
        count = -1
    if count < 1:
        raise SystemExit(f"--force-devices must be a positive integer, "
                         f"got {n!r}")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={count}").strip()


_force_devices_from_argv()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.federation import Federation, RoundSchedule
from repro.core.hfl import FederatedClient, HFLConfig
from repro.core.mesh_federation import make_mesh, mesh_devices
from repro.core.telemetry import metric_spec


def _make_clients(C: int, cfg: HFLConfig, nf: int, n: int, w: int,
                  population: bool, hetero: bool = False):
    if population:
        if hetero:
            from repro.core.experiment import hetero_population_clients
            clients, _ = hetero_population_clients(
                C, cfg, seed=0, n_patients=6, n_events=max(10 * n, 300),
                nf_choices=(max(1, nf - 1), nf, nf + 1))
            return clients
        from repro.core.experiment import population_task_data
        # ~1/5 of events are label ticks, so size the streams to give each
        # patient enough packed samples for the requested sub-round count
        packs = population_task_data(C, w, seed=0, n_patients=6,
                                     n_events=max(10 * n, 300), nf=nf)
        return [FederatedClient(p["name"], nf, cfg, p["train"], p["valid"],
                                p["test"], jax.random.PRNGKey(31 * i))
                for i, p in enumerate(packs)]
    out = []
    # --hetero: mixed feature counts cycling (nf-1, nf, nf+1) — 3 cohorts
    # of ~C/3 clients on the batched engine's cohort path (lengths stay
    # uniform so the client-round accounting below holds exactly)
    nfs = [max(1, nf - 1), nf, nf + 1] if hetero else [nf]
    for i in range(C):
        nf_i = nfs[i % len(nfs)]
        rng = np.random.default_rng(1000 + i)
        mk = lambda m, nf_i=nf_i: (
            rng.normal(size=(m, nf_i, w)).astype(np.float32),
            rng.normal(size=(m, nf_i, w)).astype(np.float32),
            rng.normal(size=m).astype(np.float32))
        out.append(FederatedClient(f"h{i:03d}", nf_i, cfg, mk(n),
                                   mk(2 * cfg.R), mk(2 * cfg.R),
                                   jax.random.PRNGKey(i)))
    return out


def _run_once(engine: str, C: int, cfg: HFLConfig, nf: int, n: int,
              population: bool, mesh=None, hetero: bool = False,
              exchange_every: int = 1, telemetry=None):
    clients = _make_clients(C, cfg, nf, n, cfg.w, population, hetero)
    # population (and hetero) data has data-dependent per-client lengths,
    # so the expected round counts come from the actual tensors, not n
    sched = RoundSchedule(cfg.epochs, cfg.R, exchange_every=exchange_every)
    train_per_client = [cfg.epochs * sched.sub_rounds(len(c.train[2]))
                        for c in clients]
    # under a k-cadence a client participates in sub_rounds // k exchanges
    # per epoch — what the engines' per-client round counters track
    exch_per_client = [
        cfg.epochs * (sched.sub_rounds(len(c.train[2])) // exchange_every)
        for c in clients]
    if not any(train_per_client):
        raise SystemExit(
            f"train splits too short for a single sub-round "
            f"(< R={cfg.R} events); raise --batches or the data sizes")
    fed = Federation(clients, cfg, engine=engine, mesh=mesh, schedule=sched,
                     telemetry=telemetry)
    t0 = time.perf_counter()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)   # ragged-length drop
        hist = fed.fit()
    elapsed = time.perf_counter() - t0
    total_rounds = sum(h["rounds"] for h in hist.values())
    assert total_rounds == sum(exch_per_client), (total_rounds,
                                                  exch_per_client)
    # global sub-rounds executed = the longest client's (epochs x per-epoch);
    # throughput counts TRAIN sub-rounds (k-independent, so rows at
    # different cadences measure the same work)
    sub_rounds = max(train_per_client)
    return elapsed, sub_rounds, sum(train_per_client), fed.dispatch_stats


def bench(engine: str, C: int, cfg: HFLConfig, nf: int, n: int,
          population: bool, mesh=None, hetero: bool = False,
          exchange_every: int = 1):
    _run_once(engine, C, cfg, nf, n, population, mesh, hetero,
              exchange_every)                                     # warmup
    elapsed, sub_rounds, train_rounds, dispatch = _run_once(
        engine, C, cfg, nf, n, population, mesh, hetero, exchange_every)
    return {
        "round_ms": 1e3 * elapsed / sub_rounds,           # all C clients
        "client_rounds_per_s": train_rounds / elapsed,
        "dispatches_per_epoch": dispatch["dispatches_per_epoch"],
        "dispatch_path": dispatch["path"],
        "devices": dispatch.get("devices", 1),
        "cohorts": dispatch.get("cohorts", 1),
        "exchange_every": dispatch.get("exchange_every", 1),
        "exchange_rounds": dispatch.get("exchange_rounds", 0),
        "pool_bytes_gathered": dispatch.get("pool_bytes_gathered", 0),
        # full-population run: everyone is resident every round
        "population": C,
        "participation_fraction": 1.0,
        "resident_clients": C,
        "resident_state_bytes": int(dispatch.get("state_bytes", 0)),
    }


_PARTICIPATIONS = {"uniform": "UniformParticipation",
                   "weighted": "WeightedParticipation",
                   "stratified": "StratifiedParticipation"}


def _run_sampled(args, cfg: HFLConfig, n: int, exchange_every: int,
                 faults=None):
    from repro.core import participation as PT
    from repro.core.experiment import tensor_population

    pop = tensor_population(args.population_size, cfg, seed=0,
                            nf_choices=(args.nf,), n_train=n,
                            n_eval=2 * cfg.R,
                            weighted_sizes=args.participation == "weighted")
    policy_cls = getattr(PT, _PARTICIPATIONS[args.participation])
    pf = PT.ParticipatingFederation(
        pop, cfg,
        participation=policy_cls(fraction=args.fraction, min_clients=2),
        schedule=RoundSchedule(args.waves, cfg.R,
                               exchange_every=exchange_every),
        faults=faults)
    t0 = time.perf_counter()
    pf.fit()
    elapsed = time.perf_counter() - t0
    st = pf.dispatch_stats
    # throughput counts TRAIN sub-rounds (k-independent), same as bench():
    # each resident client trains sub_rounds-per-epoch rounds per wave
    sub = RoundSchedule(1, cfg.R).sub_rounds(n)
    train_rounds = sum(len(w["active"]) * sub for w in pf.wave_log)
    mean_val = pf.wave_log[-1]["mean_val"] if pf.wave_log else None
    return elapsed, args.waves * sub, train_rounds, st, mean_val


def bench_sampled(args, cfg: HFLConfig, n: int, exchange_every: int,
                  faults=None):
    """One sampled-participation row: warmup run (compile — the stratified
    sampler keeps every wave's cohort geometry identical, so one warmup
    covers all waves), then the measured run.  ``faults`` (a
    :class:`repro.core.faults.FaultPlan`) makes it a graceful-degradation
    row: the row carries the fault rates, the rejection/degradation
    counters, and the final wave's mean validation MSE."""
    _run_sampled(args, cfg, n, exchange_every, faults)            # warmup
    elapsed, sub_rounds, train_rounds, st, mean_val = _run_sampled(
        args, cfg, n, exchange_every, faults)
    return {
        "round_ms": 1e3 * elapsed / sub_rounds,
        "client_rounds_per_s": train_rounds / elapsed,
        "dispatches_per_epoch": st["dispatches_per_epoch"],
        "dispatch_path": st["path"],
        "devices": st["devices"],
        "cohorts": st["cohorts"],
        "exchange_every": st["exchange_every"],
        "exchange_rounds": st["exchange_rounds"],
        "pool_bytes_gathered": st["pool_bytes_gathered"],
        "population": st["population"],
        "participation_fraction": st["participation_fraction"],
        "resident_clients": st["resident_clients"],
        "resident_state_bytes": st["resident_state_bytes"],
        "fault_rate": float(faults.dropout) if faults is not None else 0.0,
        "byzantine_frac": (float(faults.byzantine)
                           if faults is not None else 0.0),
        "heads_rejected": int(st.get("heads_rejected", 0)),
        "waves_degraded": int(st.get("waves_degraded", 0)),
        "mean_val": (None if mean_val is None or mean_val != mean_val
                     else float(mean_val)),
    }


def profile_phases(C: int, cfg: HFLConfig, nf: int, n: int,
                   population: bool, repeats: int = 20):
    """Per-phase wall time of the batched engine's building blocks at this
    client count: one vmapped train step, one fused policy round, one
    vmapped eval — the three phases the fused epoch scan stitches together.
    Returns per-dispatch microseconds plus each phase's share of an epoch
    (train and policy run once per sub-round, eval once per epoch)."""
    from repro.core.federation import (_make_batched_fns, _stack_trees,
                                       fused_policy_round, stack_pool)
    from repro.core.policies import FederationPolicies

    clients = _make_clients(C, cfg, nf, n, cfg.w, population)
    pol = FederationPolicies.from_config(cfg)
    R = cfg.R
    xs = jnp.stack([np.asarray(c.train[0][:R]) for c in clients])
    xd = jnp.stack([np.asarray(c.train[1][:R]) for c in clients])
    y = jnp.stack([np.asarray(c.train[2][:R]) for c in clients])
    val = tuple(jnp.stack([np.asarray(c.valid[k]) for c in clients])
                for k in range(3))
    params = _stack_trees([c.params for c in clients])
    opt_state = _stack_trees([c.opt_state for c in clients])
    # the engine's own stacked-pool layout, from a Federation's initial
    # publication — profiled shapes cannot drift from executed shapes
    fed = Federation(clients, cfg)
    pool_heads = stack_pool(fed.pool, [c.name for c in clients], nf)
    pool_age = jnp.zeros(C, jnp.int32)
    active = jnp.ones(C, bool)
    key = jax.random.PRNGKey(0)
    step_fn, eval_fn = _make_batched_fns(cfg.lr)

    def timed(fn):
        jax.block_until_ready(fn())                       # compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn()
        jax.block_until_ready(out)
        return 1e6 * (time.perf_counter() - t0) / repeats

    train_us = timed(lambda: step_fn(params, opt_state, xs, xd, y))
    policy_us = timed(lambda: fused_policy_round(
        params["heads"], pool_heads, pool_age, xd, y, active, key,
        nf=nf, policies=pol, use_kernel=False))
    eval_us = timed(lambda: eval_fn(params, *val))

    n_eff = len(clients[0].train[2])
    sub = RoundSchedule(cfg.epochs, R).sub_rounds(n_eff)
    epoch_us = sub * (train_us + policy_us) + eval_us
    return {
        "train_us_per_round": train_us,
        "policy_us_per_round": policy_us,
        "eval_us_per_epoch": eval_us,
        "sub_rounds_per_epoch": sub,
        "phase_split": {
            "train": sub * train_us / epoch_us,
            "policy": sub * policy_us / epoch_us,
            "eval": eval_us / epoch_us,
        },
    }


def bench_telemetry_overhead(C: int, cfg: HFLConfig, nf: int, n: int,
                             population: bool, repeats: int = 5) -> dict:
    """--telemetry: the metrics-carry cost row.  Runs the fused batched
    epoch with the in-graph telemetry carry ON vs OFF and reports the
    throughput regression — the number the <3% acceptance gate in CI
    checks.  The carry adds four small per-round outputs to the epoch
    scan; the epoch still compiles to ONE dispatch either way.

    Measurement discipline: one compile warmup apiece, then the on/off
    timings are INTERLEAVED (off, on, off, on, ...) so slow machine-load
    drift hits both arms equally, and each arm reports its best (noise
    floor) throughput over ``repeats`` runs."""
    from repro.core.telemetry import TelemetryPlan

    plans = {"off": None, "on": TelemetryPlan()}
    for telemetry in plans.values():                            # warmups
        _run_once("batched", C, cfg, nf, n, population, telemetry=telemetry)
    thr = {"off": [], "on": []}
    for _ in range(repeats):
        for arm, telemetry in plans.items():
            elapsed, _, train_rounds, _ = _run_once(
                "batched", C, cfg, nf, n, population, telemetry=telemetry)
            thr[arm].append(train_rounds / elapsed)
    off, on = max(thr["off"]), max(thr["on"])
    return {"clients": C,
            "on_client_rounds_per_s": on,
            "off_client_rounds_per_s": off,
            "overhead_pct": 100.0 * (off - on) / off}


def _engine_tag_valid(tag: str) -> bool:
    """The closed set of engine row tags this bench emits: the three full
    engines plus ``participating+<policy>`` / ``participating+fault<rate>``.
    Downstream dashboards key on these strings, so an unknown tag is a
    schema violation, not a forward-compatible extension."""
    if tag in ("sequential", "batched", "batched+mesh"):
        return True
    if tag.startswith("participating+"):
        rest = tag[len("participating+"):]
        if rest in ("uniform", "weighted", "stratified"):
            return True
        if rest.startswith("fault"):
            try:
                return 0.0 <= float(rest[len("fault"):]) <= 1.0
            except ValueError:
                return False
    return False


#: The bench-row columns, in emission order.  Each name is a catalog
#: entry in ``repro.core.telemetry.METRICS`` — ``validate_payload`` takes
#: the accepted types from there, ONE schema for engines and bench alike.
BENCH_ROW_FIELDS = (
    "clients", "engine", "devices", "hetero", "cohorts", "round_ms",
    "client_rounds_per_s", "dispatches_per_epoch", "dispatch_path",
    "exchange_every", "exchange_rounds", "pool_bytes_gathered",
    "population", "participation_fraction", "resident_clients",
    "resident_state_bytes", "fault_rate", "byzantine_frac",
    "heads_rejected", "waves_degraded", "mean_val",
    "speedup_vs_sequential",
)


def validate_payload(payload: dict) -> None:
    """Structural schema check for BENCH_fl_scale.json — CI smoke-runs a
    tiny sweep and validates the emitted file through this, so the schema
    can't drift silently under downstream tooling.  Row columns are
    validated against the telemetry metrics registry (see
    ``BENCH_ROW_FIELDS``)."""
    def need(obj, key, types, where):
        if key not in obj:
            raise ValueError(f"{where}: missing key {key!r}")
        if not isinstance(obj[key], types):
            raise ValueError(f"{where}[{key!r}]: expected {types}, "
                             f"got {type(obj[key]).__name__}")

    need(payload, "benchmark", str, "payload")
    if payload["benchmark"] != "fl_scale":
        raise ValueError(f"payload[benchmark]: {payload['benchmark']!r}")
    need(payload, "unix_time", int, "payload")
    need(payload, "backend", str, "payload")
    need(payload, "device_count", int, "payload")
    need(payload, "platform", str, "payload")
    need(payload, "config", dict, "payload")
    need(payload, "results", list, "payload")
    for k in ("epochs", "R", "nf", "batches"):
        need(payload["config"], k, int, "config")
    need(payload["config"], "clients", list, "config")
    need(payload["config"], "engines", list, "config")
    need(payload["config"], "exchange_every", list, "config")
    need(payload["config"], "population_size", (int, type(None)), "config")
    need(payload["config"], "fraction", (int, float, type(None)), "config")
    need(payload["config"], "participation", (str, type(None)), "config")
    need(payload["config"], "waves", (int, type(None)), "config")
    need(payload["config"], "fault_rate", list, "config")
    need(payload["config"], "byzantine_frac", (int, float), "config")
    if not all(isinstance(k, int) and k >= 1
               for k in payload["config"]["exchange_every"]):
        raise ValueError("config[exchange_every]: expected a list of "
                         "positive ints")
    if not payload["results"]:
        raise ValueError("results: empty")
    for i, r in enumerate(payload["results"]):
        where = f"results[{i}]"
        # the row schema IS the metrics registry: every bench column
        # resolves through repro.core.telemetry.METRICS (name + accepted
        # JSON types), so the bench columns and the engines' own
        # dispatch_stats names cannot drift apart
        for key in BENCH_ROW_FIELDS:
            need(r, key, metric_spec(key).types, where)
        if not _engine_tag_valid(r["engine"]):
            raise ValueError(f"{where}[engine]: unknown engine tag "
                             f"{r['engine']!r}")
        if not 0 <= r["fault_rate"] <= 1:
            raise ValueError(f"{where}[fault_rate]: must be in [0, 1], "
                             f"got {r['fault_rate']}")
        if not 0 <= r["byzantine_frac"] <= 1:
            raise ValueError(f"{where}[byzantine_frac]: must be in [0, 1], "
                             f"got {r['byzantine_frac']}")
        if r["heads_rejected"] < 0 or r["waves_degraded"] < 0:
            raise ValueError(f"{where}: fault counters must be >= 0")
        if r["exchange_every"] < 1:
            raise ValueError(f"{where}[exchange_every]: must be >= 1, "
                             f"got {r['exchange_every']}")
        if not 0 < r["participation_fraction"] <= 1:
            raise ValueError(f"{where}[participation_fraction]: must be in "
                             f"(0, 1], got {r['participation_fraction']}")
        if r["resident_clients"] > r["population"]:
            raise ValueError(f"{where}: resident_clients "
                             f"{r['resident_clients']} exceeds population "
                             f"{r['population']}")
    to = payload.get("telemetry_overhead")
    if to is not None:
        where = "telemetry_overhead"
        if not isinstance(to, dict):
            raise ValueError(f"{where}: expected dict")
        need(to, "clients", int, where)
        for k in ("on_client_rounds_per_s", "off_client_rounds_per_s",
                  "overhead_pct"):
            need(to, k, (int, float), where)
        if to["on_client_rounds_per_s"] <= 0 \
                or to["off_client_rounds_per_s"] <= 0:
            raise ValueError(f"{where}: throughputs must be positive")
    for key, p in payload.get("profiles", {}).items():
        where = f"profiles[{key!r}]"
        if not isinstance(p, dict):
            raise ValueError(f"{where}: expected dict")
        for k in ("train_us_per_round", "policy_us_per_round",
                  "eval_us_per_epoch"):
            need(p, k, (int, float), where)
        need(p, "sub_rounds_per_epoch", int, where)
        need(p, "phase_split", dict, where)
        for k in ("train", "policy", "eval"):
            need(p["phase_split"], k, (int, float), f"{where}[phase_split]")


def _record(C, label, het, r, speedup):
    return {
        "clients": C, "engine": label,
        "hetero": het,
        "cohorts": r["cohorts"],
        "devices": r["devices"],
        "exchange_every": r["exchange_every"],
        "exchange_rounds": r["exchange_rounds"],
        "pool_bytes_gathered": r["pool_bytes_gathered"],
        "population": r["population"],
        "participation_fraction": r["participation_fraction"],
        "resident_clients": r["resident_clients"],
        "resident_state_bytes": r["resident_state_bytes"],
        "round_ms": r["round_ms"],
        "client_rounds_per_s": r["client_rounds_per_s"],
        "dispatches_per_epoch": r["dispatches_per_epoch"],
        "dispatch_path": r["dispatch_path"],
        # graceful-degradation columns: full-population rows run faultless
        "fault_rate": r.get("fault_rate", 0.0),
        "byzantine_frac": r.get("byzantine_frac", 0.0),
        "heads_rejected": r.get("heads_rejected", 0),
        "waves_degraded": r.get("waves_degraded", 0),
        "mean_val": r.get("mean_val"),
        "speedup_vs_sequential":
            None if speedup != speedup else speedup}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", default="2,8,32,128")
    ap.add_argument("--engines", default="sequential,batched")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--R", type=int, default=20)
    ap.add_argument("--nf", type=int, default=4)
    ap.add_argument("--batches", type=int, default=3,
                    help="train sub-rounds per epoch")
    ap.add_argument("--population", action="store_true",
                    help="use generated N-hospital data instead of random "
                         "tensors")
    ap.add_argument("--profile", action="store_true",
                    help="also report the batched engine's train/policy/"
                         "eval phase split per client count")
    ap.add_argument("--mesh", action="store_true",
                    help="add a batched+mesh row: the fused epoch "
                         "client-sharded over all local devices")
    ap.add_argument("--hetero", action="store_true",
                    help="also sweep a mixed-nf population (feature counts "
                         "cycling nf-1/nf/nf+1): the cohorted fast path vs "
                         "the sequential oracle, rows tagged hetero=true")
    ap.add_argument("--force-devices", type=int, default=None,
                    help="split the host CPU into N virtual devices "
                         "(applied before jax init; see --mesh)")
    ap.add_argument("--exchange-every", default="1",
                    help="comma list of bounded-staleness cadences k: "
                         "exchange heads every k-th sub-round "
                         "(RoundSchedule.exchange_every); sequential rows "
                         "run only at k=1, the speedup baseline")
    ap.add_argument("--population-size", type=int, default=None,
                    help="also bench a sampled-participation row: a lazy "
                         "N-client tensor population trained through "
                         "ParticipatingFederation (see --fraction / "
                         "--participation / --waves)")
    ap.add_argument("--fraction", type=float, default=0.001,
                    help="participation fraction per wave for "
                         "--population-size rows")
    ap.add_argument("--participation", default="stratified",
                    choices=sorted(_PARTICIPATIONS),
                    help="sampling policy for --population-size rows")
    ap.add_argument("--waves", type=int, default=2,
                    help="participation waves for --population-size rows")
    ap.add_argument("--fault-rate", default="",
                    help="comma list of per-wave client dropout "
                         "probabilities; each adds a fault-injected "
                         "sampled-participation row (requires "
                         "--population-size) — the graceful-degradation "
                         "curve of MSE and rounds/s vs fault rate")
    ap.add_argument("--byzantine-frac", type=float, default=0.0,
                    help="per-wave probability a sampled client publishes "
                         "corrupted (NaN) heads in --fault-rate rows "
                         "(quarantined by the pool admission guard)")
    ap.add_argument("--telemetry", action="store_true",
                    help="measure the in-graph telemetry carry's overhead: "
                         "fused-epoch throughput with the metrics carry ON "
                         "vs OFF at the largest client count (min-of-3 "
                         "each); writes payload['telemetry_overhead'] — "
                         "CI gates overhead_pct < 3")
    ap.add_argument("--max-seq-clients", type=int, default=None,
                    help="skip the sequential oracle above this client "
                         "count (its per-client Python loop dominates the "
                         "wall clock at large C; batched rows then report "
                         "speedup=null)")
    ap.add_argument("--out", default=str(_REPO_ROOT / "BENCH_fl_scale.json"),
                    help="machine-readable results path (empty to disable)")
    args = ap.parse_args()
    counts = [int(x) for x in args.clients.split(",")]
    engines = args.engines.split(",")
    ks = [int(x) for x in args.exchange_every.split(",")]
    if any(k < 1 for k in ks):
        raise SystemExit("--exchange-every entries must be >= 1")
    fault_rates = [float(x) for x in args.fault_rate.split(",") if x]
    if fault_rates and not args.population_size:
        raise SystemExit("--fault-rate rows ride the participation path; "
                         "pass --population-size too")
    if not all(0 <= f <= 1 for f in fault_rates) \
            or not 0 <= args.byzantine_frac <= 1:
        raise SystemExit("--fault-rate / --byzantine-frac entries must be "
                         "probabilities in [0, 1]")
    cfg = HFLConfig(mode="always", epochs=args.epochs, R=args.R)
    n = args.batches * args.R

    runs = [(e, None, False) for e in engines]
    if args.mesh:
        mesh = make_mesh()
        if mesh_devices(mesh) == 1:
            # a 1-device mesh would just re-measure the single-device path
            # under a misleading label — skip it rather than record it
            print("[mesh] 1 local device: skipping batched+mesh rows (the "
                  "engine would fall back to the single-device path; use "
                  "--force-devices N to split the host CPU)",
                  file=sys.stderr)
        else:
            runs.append(("batched+mesh", mesh, False))
    if args.hetero:
        # the cohorted fast path vs the sequential oracle on mixed nf —
        # same engines, hetero-tagged rows, speedup computed within the
        # hetero pair (oracle heterogeneity was the old ceiling; the gap
        # between these rows IS the cohort engine's contribution)
        runs += [(e, None, True) for e in engines]

    records = []
    profiles = {}
    print("clients,engine,hetero,exchange_every,devices,cohorts,round_ms,"
          "client_rounds_per_s,dispatches_per_epoch,exchange_rounds,"
          "pool_bytes_gathered,population,participation_fraction,"
          "resident_clients,speedup_vs_sequential")
    for C in counts:
        rows = {}
        for k in ks:
            for label, mesh_, het in runs:
                if label == "sequential":
                    if k != 1:       # the oracle baseline runs at k=1 only
                        continue
                    if args.max_seq_clients is not None \
                            and C > args.max_seq_clients:
                        print(f"[seq] skipping C={C}: above "
                              f"--max-seq-clients={args.max_seq_clients}",
                              file=sys.stderr)
                        continue
                if mesh_ is not None and C % mesh_devices(mesh_):
                    print(f"[mesh] skipping C={C}: not divisible by "
                          f"{mesh_devices(mesh_)} devices", file=sys.stderr)
                    continue
                engine = "batched" if mesh_ is not None else label
                rows[(label, het, k)] = bench(engine, C, cfg, args.nf, n,
                                              args.population, mesh_, het,
                                              k)
        for k in ks:
            for label, _, het in runs:
                if (label, het, k) not in rows:
                    continue
                r = rows[(label, het, k)]
                base = rows.get(("sequential", het, 1))
                speedup = (r["client_rounds_per_s"]
                           / base["client_rounds_per_s"]
                           if base else float("nan"))
                print(f"{C},{label},{int(het)},{k},{r['devices']},"
                      f"{r['cohorts']},{r['round_ms']:.2f},"
                      f"{r['client_rounds_per_s']:.1f},"
                      f"{r['dispatches_per_epoch']:.1f},"
                      f"{r['exchange_rounds']},{r['pool_bytes_gathered']},"
                      f"{r['population']},{r['participation_fraction']},"
                      f"{r['resident_clients']},"
                      f"{speedup:.2f}", flush=True)
                records.append(_record(C, label, het, r, speedup))
        if args.profile:
            p = profile_phases(C, cfg, args.nf, n, args.population)
            profiles[str(C)] = p
            s = p["phase_split"]
            print(f"[profile] C={C}: train {p['train_us_per_round']:.0f}us"
                  f"/round, policy {p['policy_us_per_round']:.0f}us/round, "
                  f"eval {p['eval_us_per_epoch']:.0f}us/epoch -> "
                  f"split train {100 * s['train']:.0f}% / "
                  f"policy {100 * s['policy']:.0f}% / "
                  f"eval {100 * s['eval']:.0f}%", file=sys.stderr)
    if args.population_size:
        # sampled-participation rows: population >> resident working set;
        # engine label comes from dispatch_stats ("participating+batched")
        for k in ks:
            r = bench_sampled(args, cfg, n, k)
            label = f"participating+{args.participation}"
            print(f"{r['resident_clients']},{label},0,{k},{r['devices']},"
                  f"{r['cohorts']},{r['round_ms']:.2f},"
                  f"{r['client_rounds_per_s']:.1f},"
                  f"{r['dispatches_per_epoch']:.1f},"
                  f"{r['exchange_rounds']},{r['pool_bytes_gathered']},"
                  f"{r['population']},{r['participation_fraction']},"
                  f"{r['resident_clients']},nan", flush=True)
            records.append(_record(r["resident_clients"], label, False, r,
                                   float("nan")))
        # graceful-degradation curve: one fault-injected row per rate at
        # the first cadence (MSE + rounds/s vs fault rate; same seed, so
        # the schedules are comparable across rates)
        from repro.core.faults import FaultPlan
        for rate in fault_rates:
            plan = FaultPlan(dropout=rate, byzantine=args.byzantine_frac,
                             corruption="nan", seed=0)
            r = bench_sampled(args, cfg, n, ks[0], faults=plan)
            label = f"participating+fault{rate:g}"
            print(f"{r['resident_clients']},{label},0,{ks[0]},"
                  f"{r['devices']},{r['cohorts']},{r['round_ms']:.2f},"
                  f"{r['client_rounds_per_s']:.1f},"
                  f"{r['dispatches_per_epoch']:.1f},"
                  f"{r['exchange_rounds']},{r['pool_bytes_gathered']},"
                  f"{r['population']},{r['participation_fraction']},"
                  f"{r['resident_clients']},nan", flush=True)
            print(f"[faults] rate={rate:g} byz={args.byzantine_frac:g}: "
                  f"mean_val={r['mean_val']}, "
                  f"heads_rejected={r['heads_rejected']}, "
                  f"waves_degraded={r['waves_degraded']}",
                  file=sys.stderr)
            records.append(_record(r["resident_clients"], label, False, r,
                                   float("nan")))
    tele_overhead = None
    if args.telemetry:
        tele_overhead = bench_telemetry_overhead(
            max(counts), cfg, args.nf, n, args.population)
        print(f"[telemetry] C={tele_overhead['clients']}: "
              f"carry on {tele_overhead['on_client_rounds_per_s']:.1f} "
              f"vs off {tele_overhead['off_client_rounds_per_s']:.1f} "
              f"client-rounds/s -> overhead "
              f"{tele_overhead['overhead_pct']:.2f}%", file=sys.stderr)
    if args.out:
        payload = {
            "benchmark": "fl_scale",
            "unix_time": int(time.time()),
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "platform": platform.platform(),
            "config": {"epochs": args.epochs, "R": args.R, "nf": args.nf,
                       "batches": args.batches, "mode": cfg.mode,
                       "population": bool(args.population),
                       "mesh": bool(args.mesh),
                       "hetero": bool(args.hetero),
                       "clients": counts, "engines": engines,
                       "exchange_every": ks,
                       "population_size": args.population_size,
                       "fraction": args.fraction if args.population_size
                       else None,
                       "participation": args.participation
                       if args.population_size else None,
                       "waves": args.waves if args.population_size
                       else None,
                       "fault_rate": fault_rates,
                       "byzantine_frac": args.byzantine_frac},
            "results": records,
        }
        if profiles:
            payload["profiles"] = profiles
        if tele_overhead is not None:
            payload["telemetry_overhead"] = tele_overhead
        validate_payload(payload)
        Path(args.out).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
