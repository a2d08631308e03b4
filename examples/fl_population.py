"""N-hospital federated population on the composable Federation API.

  PYTHONPATH=src python examples/fl_population.py [--clients 16]

Generates `--clients` synthetic hospitals (each observing the shared latent
physiology through its own perturbed observation operator — see
repro.data.synthetic.population_spec), then trains them as one
:class:`repro.core.federation.Federation`.  The default policy bundle is the
paper's: plateau-gated switching, Eq.-7 argmin selection, Eq.-8
alpha-blending, last-write-wins pool asynchrony — every piece swappable from
the command line:

  --selection softmax --temperature 0.5     # softmax-weighted selection
  --selection topk --k 3                    # uniform over the 3 best heads
  --max-staleness 4                         # hide pool entries older than 4
  --switch-prob 0.5                         # Bernoulli per-epoch switching
  --exchange-every 2                        # pool exchange every 2 sub-rounds

``--population N`` switches to SAMPLED PARTICIPATION over a lazily
declared N-hospital population (`repro.core.participation`): only each
wave's sampled clients ever materialize or occupy the device, everyone
else lives in the host-side ClientStore, and the head pool carries
knowledge across waves.  ``--fraction`` sets the per-wave sample and
``--participation {uniform,weighted,stratified}`` picks the sampling
policy (stratified keeps each wave's cohort geometry identical, so wave
2+ reuses wave 1's compiled epoch).  ``--epochs`` then counts WAVES:

  --population 100000 --fraction 0.0003 --participation stratified

``--fault-rate`` / ``--byzantine-frac`` turn on DETERMINISTIC FAULT
INJECTION for --population runs (`repro.core.faults.FaultPlan`): each
wave drops clients with probability ``--fault-rate`` (the wave re-rounds
its geometry and continues) and poisons each survivor's published heads
with probability ``--byzantine-frac`` — the in-graph pool admission
guard quarantines the poisoned heads so they never reach a neighbour.
The summary line reports what was survived:

  --population 64 --fraction 0.25 --fault-rate 0.2 --byzantine-frac 0.1

With ``--engine batched`` (default) every Adam step is vmapped across
hospitals and each federated opportunity runs as ONE fused selection+blend
scan; ``--engine sequential`` runs the reference oracle instead — same
selections, ~an order of magnitude slower at this scale.  ``--mesh``
client-shards the batched engine over every local device (a 1-D
``clients`` mesh — see docs/SCALING.md; selections stay identical, and on
a 1-device host it falls back to the plain path).

``--hetero`` generates a MIXED-nf population (hospitals cycle through
``--nf-choices`` feature counts): the batched engine partitions it into
homogeneous cohorts automatically and exchanges heads through a padded
union pool (`repro.core.cohorts`) — still one fused dispatch per epoch,
still the oracle's selections.  The summary line reports the cohort
layout.

``--telemetry`` turns on the flight recorder
(`repro.core.telemetry.TelemetryPlan`): in-graph per-round series (still
one fused dispatch per epoch) plus host-side gather/dispatch/exchange/
scatter spans in a bounded ring buffer.  ``--trace-out run.json``
additionally exports the recording as Chrome-trace/Perfetto JSON
(open it at https://ui.perfetto.dev) with the counter registry snapshot
under a top-level ``metrics`` key:

  --population 64 --fraction 0.25 --telemetry --trace-out run.json

``--save-dir d`` checkpoints the full federation at the end (and ``--resume``
restarts from such a checkpoint and trains ``--epochs`` MORE epochs —
bit-identical to never having stopped).
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core.experiment import (hetero_population_clients,
                                   population_clients)
from repro.core.federation import (Federation, MetricsCapture,
                                   RoundSchedule)
from repro.core.hfl import HFLConfig
from repro.core.policies import (FederationPolicies, MaxStaleness,
                                 ProbSwitch, SoftmaxSelection, TopKSelection)


def build_policies(args, cfg) -> FederationPolicies:
    pol = FederationPolicies.from_config(cfg)       # legacy-mode shorthand
    if args.selection == "softmax":
        pol = dataclasses.replace(
            pol, selection=SoftmaxSelection(args.temperature))
    elif args.selection == "topk":
        pol = dataclasses.replace(pol, selection=TopKSelection(args.k))
    if args.max_staleness is not None:
        pol = dataclasses.replace(pol, pool=MaxStaleness(args.max_staleness))
    if args.switch_prob is not None:
        pol = dataclasses.replace(pol, switch=ProbSwitch(args.switch_prob))
    return pol


def _policy_flags_customized(args) -> bool:
    return (args.selection != "mode" or args.mode != "hfl"
            or args.max_staleness is not None
            or args.switch_prob is not None)


_PARTICIPATIONS = {"uniform": "UniformParticipation",
                   "weighted": "WeightedParticipation",
                   "stratified": "StratifiedParticipation"}


def telemetry_plan(args):
    """--telemetry / --trace-out: the flight-recorder plan (or None)."""
    if not (args.telemetry or args.trace_out):
        return None
    from repro.core.telemetry import TelemetryPlan
    return TelemetryPlan()


def export_trace(fed, args):
    """Summarize the flight recording; export Perfetto JSON if asked."""
    rec = getattr(fed, "_recorder", None)
    if rec is None:
        return
    # one metrics payload: the recorder's counters plus every numeric
    # dispatch_stats entry the engines reported (canonical names)
    snap = dict(rec.snapshot())
    for k, v in (fed.dispatch_stats or {}).items():
        if isinstance(v, (int, float)) and k not in snap:
            snap[k] = v
    spans = sum(1 for e in rec.events if e["type"] == "span")
    rounds = sum(1 for e in rec.events if e["type"] == "round")
    print(f"=> telemetry: {spans} spans + {rounds} round records in the "
          f"ring ({len(rec.events)}/{rec.plan.ring_size}), counters: "
          + ", ".join(f"{k}={snap[k]}" for k in sorted(snap)
                      if isinstance(snap[k], int)))
    if args.trace_out:
        sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                               / "tools"))
        from trace_export import (assert_spans_nest, chrome_trace,
                                  validate_trace)
        trace = chrome_trace(rec.events, metrics=snap)
        validate_trace(trace)
        assert_spans_nest(trace["traceEvents"])
        Path(args.trace_out).write_text(json.dumps(trace))
        print(f"=> trace: {len(trace['traceEvents'])} events -> "
              f"{args.trace_out} (open at https://ui.perfetto.dev)")


def run_sampled(args, mesh):
    """--population N: sampled partial participation over a lazy population
    (repro.core.participation) — the resident working set is the WAVE, not
    the population."""
    from repro.core import participation as PT
    from repro.core.experiment import lazy_hetero_population

    cfg = HFLConfig(epochs=args.epochs, mode=args.mode, R=20)
    nf_choices = tuple(int(x) for x in args.nf_choices.split(","))
    pop = lazy_hetero_population(
        args.population, cfg, n_patients=args.patients,
        n_events=args.events, nf_choices=nf_choices,
        weighted_sizes=args.participation == "weighted")
    faults = None
    if args.fault_rate or args.byzantine_frac:
        from repro.core.faults import FaultPlan
        faults = FaultPlan(dropout=args.fault_rate,
                           byzantine=args.byzantine_frac,
                           corruption="nan")
    if args.resume:
        if not args.save_dir:
            raise SystemExit("--resume requires --save-dir")
        pf = PT.ParticipatingFederation.restore(args.save_dir, pop,
                                                mesh=mesh)
        print(f"== resumed {args.population}-hospital sampled federation "
              f"at wave {pf.wave} ==")
        t0 = time.time()
        pf.fit(waves=pf.wave + args.epochs, verbose=args.verbose)
    else:
        policy_cls = getattr(PT, _PARTICIPATIONS[args.participation])
        pf = PT.ParticipatingFederation(
            pop, cfg, policies=build_policies(args, cfg),
            participation=policy_cls(fraction=args.fraction, min_clients=2),
            schedule=RoundSchedule(args.epochs, cfg.R,
                                   exchange_every=args.exchange_every),
            mesh=mesh, faults=faults, telemetry=telemetry_plan(args))
        print(f"== {args.population}-hospital population, "
              f"{args.participation} participation "
              f"(fraction={args.fraction}), {args.epochs} waves =="
              + (f" [faults: dropout={args.fault_rate:g}, "
                 f"byzantine={args.byzantine_frac:g}]" if faults else ""))
        t0 = time.time()
        pf.fit(verbose=args.verbose)
    wall = time.time() - t0
    st = pf.dispatch_stats
    print(f"=> {st['waves']} waves x {st['resident_clients']} resident "
          f"clients of {st['population']:,} declared; device working set "
          f"{st['resident_state_bytes'] / 1e6:.1f}MB, store "
          f"{st['store_clients']} clients / {st['store_bytes'] / 1e6:.1f}MB "
          f"host-side, gathered {st['gather_bytes'] / 1e6:.1f}MB in "
          f"{wall:.1f}s")
    if st.get("clients_dropped") or st.get("heads_rejected") \
            or st.get("stragglers"):
        print(f"=> faults survived: {st['clients_dropped']} clients "
              f"dropped across {st['waves_degraded']} degraded waves, "
              f"{st['stragglers']} stragglers, {st['heads_rejected']} "
              f"poisoned heads quarantined at the pool gate")
    export_trace(pf, args)
    if args.save_dir:
        pf.save(args.save_dir)
        print(f"=> sampled federation checkpointed to {args.save_dir} "
              f"(restore with --resume)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--engine", choices=("batched", "sequential"),
                    default=None,
                    help="default: batched for fresh runs, the CHECKPOINTED "
                         "engine for --resume")
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--patients", type=int, default=10)
    ap.add_argument("--events", type=int, default=300)
    ap.add_argument("--mode", default="hfl",
                    choices=("hfl", "no", "random", "always"))
    ap.add_argument("--selection", default="mode",
                    choices=("mode", "softmax", "topk"),
                    help="override the mode's selection policy")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="hide pool entries unrefreshed for this many rounds")
    ap.add_argument("--switch-prob", type=float, default=None,
                    help="Bernoulli(p) per-epoch switching policy "
                         "(ProbSwitch; previously spelled --participation)")
    ap.add_argument("--population", type=int, default=None,
                    help="declare this many hospitals LAZILY and train by "
                         "sampled participation (repro.core.participation) "
                         "— --epochs counts waves; see --fraction / "
                         "--participation")
    ap.add_argument("--fraction", type=float, default=0.1,
                    help="participation fraction per wave (--population)")
    ap.add_argument("--participation", default="stratified",
                    choices=sorted(_PARTICIPATIONS),
                    help="wave sampling policy for --population runs")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="per-wave client dropout probability "
                         "(repro.core.faults.FaultPlan; --population only)")
    ap.add_argument("--byzantine-frac", type=float, default=0.0,
                    help="per-wave probability a sampled client publishes "
                         "poisoned (NaN) heads — the pool admission guard "
                         "quarantines them (--population only)")
    ap.add_argument("--mesh", action="store_true",
                    help="client-shard the batched engine over all local "
                         "devices (docs/SCALING.md; falls back to the "
                         "single-device path on 1 device)")
    ap.add_argument("--hetero", action="store_true",
                    help="generate a MIXED-nf population (feature counts "
                         "cycling --nf-choices): the batched engine "
                         "cohort-plans it automatically (repro.core."
                         "cohorts), the sequential oracle loops it")
    ap.add_argument("--nf-choices", default="3,4,5",
                    help="comma-separated feature counts cycled across "
                         "hospitals under --hetero")
    ap.add_argument("--exchange-every", type=int, default=1,
                    help="bounded-staleness cadence: run the pool exchange "
                         "only on every k-th sub-round (docs/SCALING.md)")
    ap.add_argument("--telemetry", action="store_true",
                    help="flight-recorder telemetry (repro.core.telemetry): "
                         "in-graph per-round series + host-side spans")
    ap.add_argument("--trace-out", default=None,
                    help="export the flight recording as Chrome-trace/"
                         "Perfetto JSON here (implies --telemetry)")
    ap.add_argument("--save-dir", default=None,
                    help="checkpoint the federation here after training")
    ap.add_argument("--resume", action="store_true",
                    help="restore from --save-dir, train --epochs more")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    mesh = None
    if args.mesh:
        from repro.core.mesh_federation import make_mesh
        mesh = make_mesh()
    if args.population:
        run_sampled(args, mesh)
        return
    cfg = HFLConfig(epochs=args.epochs, mode=args.mode, R=20)
    if args.hetero:
        nf_choices = tuple(int(x) for x in args.nf_choices.split(","))
        clients, packs = hetero_population_clients(
            args.clients, cfg, n_patients=args.patients,
            n_events=args.events, nf_choices=nf_choices)
    else:
        clients, packs = population_clients(args.clients, cfg,
                                            n_patients=args.patients,
                                            n_events=args.events)
    scale = {p["name"]: p["label_var"] for p in packs}  # raw-unit MSEs
    metrics = MetricsCapture()
    if args.resume:
        if not args.save_dir:
            raise SystemExit("--resume requires --save-dir")
        if _policy_flags_customized(args):
            print("note: --resume continues with the CHECKPOINTED policy "
                  "bundle; --mode/--selection/--max-staleness/"
                  "--switch-prob are ignored", file=sys.stderr)
        fed = Federation.restore(args.save_dir, clients,
                                 engine=args.engine, callbacks=[metrics],
                                 mesh=mesh)
        print(f"== resumed {args.clients}-hospital federation at epoch "
              f"{fed.epoch}, engine={fed.engine} ==")
        rounds0 = sum(fed.n_rounds.values())
        t0 = time.time()
        hist = fed.fit(epochs=args.epochs, verbose=args.verbose)
    else:
        sched = RoundSchedule(cfg.epochs, cfg.R,
                              exchange_every=args.exchange_every)
        fed = Federation(clients, cfg, policies=build_policies(args, cfg),
                         schedule=sched, engine=args.engine or "batched",
                         callbacks=[metrics], mesh=mesh,
                         telemetry=telemetry_plan(args))
        print(f"== {args.clients}-hospital population, engine={fed.engine}, "
              f"mode={args.mode}, selection={args.selection}"
              + (f", mesh={mesh.devices.size}dev" if mesh is not None
                 else "") + " ==")
        rounds0 = 0
        t0 = time.time()
        hist = fed.fit(verbose=args.verbose)
    wall = time.time() - t0

    tests = sorted((h["test"] * scale[name], name, h["rounds"])
                   for name, h in hist.items())
    total_rounds = sum(h["rounds"] for h in hist.values())
    new_rounds = total_rounds - rounds0      # rounds run in THIS segment
    print(f"{'hospital':>10} {'test MSE':>12} {'fed rounds':>10}")
    for mse, name, rounds in tests[:5]:
        print(f"{name:>10} {mse:12.2f} {rounds:10d}")
    if len(tests) > 5:
        print(f"{'...':>10} ({len(tests) - 5} more hospitals)")
    st = fed.dispatch_stats or {}
    cohort_note = ""
    if st.get("cohorts", 1) > 1:
        sizes = [pc["clients"] for pc in st.get("per_cohort", [])]
        cohort_note = (f", {st['cohorts']} cohorts {sizes} "
                       f"@ {st['dispatches_per_epoch']:.0f} dispatch/epoch")
    print(f"=> {new_rounds} federated rounds ({total_rounds} cumulative) "
          f"across {args.clients} hospitals, {len(metrics.epochs)} epochs "
          f"captured, in {wall:.1f}s "
          f"({max(new_rounds, 1) / wall:.1f} client-rounds/s){cohort_note}")
    export_trace(fed, args)
    if args.save_dir:
        fed.save(args.save_dir)
        print(f"=> federation checkpointed to {args.save_dir} "
              f"(restore with --resume)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
