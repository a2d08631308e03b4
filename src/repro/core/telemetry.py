"""Flight-recorder telemetry for the federated stack.

Three pieces, all optional and all zero-cost when absent:

* **TelemetryPlan** — the seventh pluggable, spec-round-trippable,
  ``register_policy``-able plan (the ``FaultPlan`` / ``TrustPlan``
  pattern).  ``telemetry=None`` and a fully disabled plan trace the
  byte-identical pre-instrumentation graph on every engine (pinned by
  ``tests/test_telemetry.py``); an enabled plan threads an extra metrics
  carry through the fused epoch scan, so one epoch still costs one
  dispatch and the per-round series come back as stacked scan outputs:

    - ``foreign_per_client`` — the selection histogram: how many of each
      client's features picked a foreign head this exchange round (0 =
      the client kept its own head / sat the round out),
    - ``score_min`` / ``score_mean`` — the Eq.-7 score distribution over
      the valid candidate pool per client (``inf`` / 0 when the selection
      policy scores nothing, e.g. ``RandomSelection`` or a secure round),
    - ``pool_age`` — the staleness-age snapshot after the round
      (quarantined rows sit at the ``QUARANTINE_AGE`` sentinel and are
      masked out of the recorded aggregates).

* **FlightRecorder** — a bounded ring buffer (``collections.deque``) of
  host-side events: ``span`` timings, with optional ``jax.profiler``
  trace annotations behind ``plan.profile``, the decoded per-round metric
  records, and a counter registry snapshot.  The batched fit driver (both
  engines) opens ``fit``; inside it ``restack`` (stacking the population,
  pool and best parameters and placing them on the device; the counters
  ``restack_host_leaves`` / ``restack_device_leaves`` say where each
  stacked leaf was stacked), per epoch ``dispatch`` (the enqueue of the
  fused epoch), ``readback`` (the blocking read of its validation MSEs
  and choices) and ``record`` (selections, histories, round series), and
  ``writeback`` (``sync()``); after it ``results`` with ``test_pass``
  inside.  ``gc`` spans mark generation-2 collections during a fit.  The
  participation orchestrator adds ``sample`` / ``gather`` / ``exchange``
  / ``scatter`` per wave.  On the device, the fused epoch programs carry
  the ``jax.named_scope`` names ``policy_round`` (with ``eq7_score``
  inside), ``train_step`` and ``eval_best`` in their ops' ``op_name``
  metadata; under ``profile`` the recorder keeps each epoch program's
  instruction -> ``op_name`` map (``programs``), so a device profile's
  bare op names can be put down to a layer.  The cohort engine's epoch
  compiles as its own module, ``jit_hetero_epoch`` (the homogeneous one
  is ``jit_epoch``), with one more scope, ``union_view``: the assembly
  of the padded union pool and probes and the projection of the blended
  heads back to native nf.  Its counters ``union_rows`` (C * max_nf) and
  ``union_real_rows`` (the clients' summed nf), summed over exchange
  rounds, give the real share of the scored pool; a homogeneous fit
  sets neither.
  It serializes to JSONL, round-trips through checkpoint manifests
  (``to_json`` / ``from_json``) so resumed runs continue their trace, and
  ``tools/trace_export.py`` turns the event list into Chrome-trace /
  Perfetto JSON.

* **MetricsRegistry schema** — the typed, documented catalog of every
  ``dispatch_stats`` name the engines emit (counter / gauge / histogram /
  label, units), machine-readable via ``schema()``.
  ``benchmarks/fl_scale_bench.validate_payload`` validates result rows
  against this one catalog instead of a hand-rolled column list.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import json
import re
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from repro.core.faults import QUARANTINE_AGE
from repro.core.policies import _Spec, register_policy


@register_policy
@dataclasses.dataclass(frozen=True)
class TelemetryPlan(_Spec):
    """What to record.  ``rounds`` turns on the in-graph metrics carry
    (per-round series stacked as extra scan outputs); ``spans`` turns on
    the host-side span tracer; ``ring_size`` bounds the flight recorder;
    ``profile`` additionally wraps every span in a
    ``jax.profiler.TraceAnnotation`` so the spans show up in a captured
    XLA profile.  A plan with both ``rounds`` and ``spans`` off is inert:
    engines treat it exactly like ``telemetry=None``."""
    rounds: bool = True
    spans: bool = True
    ring_size: int = 4096
    profile: bool = False

    def __post_init__(self):
        if not isinstance(self.ring_size, int) or self.ring_size < 1:
            raise ValueError(f"ring_size must be a positive int, got "
                             f"{self.ring_size!r}")

    @property
    def enabled(self) -> bool:
        """Whether anything records.  Disabled plans are inert: engines
        treat them exactly like ``telemetry=None``."""
        return self.rounds or self.spans


# ---------------------------------------------------------------------------
# MetricsRegistry: the one catalog of dispatch_stats / bench metric names
# ---------------------------------------------------------------------------

#: Metric kinds.  ``counter`` only ever increases within a run; ``gauge``
#: is a point-in-time level; ``histogram`` summarizes a distribution;
#: ``label`` is a categorical/structured annotation, not a number.
KINDS = ("counter", "gauge", "histogram", "label")

_NUM = (int, float)
_OPT_NUM = (int, float, type(None))


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """One documented metric name: its kind, accepted python types (the
    JSON-decoded types ``validate_payload`` checks against), unit, and a
    one-line description."""
    name: str
    kind: str
    types: tuple
    unit: str
    description: str

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown metric kind {self.kind!r}")


def _m(name, kind, types, unit, description):
    return MetricSpec(name, kind, tuple(types), unit, description)


#: The catalog.  Every key any engine ever puts in ``dispatch_stats``
#: plus the bench-row columns, under one typed schema.
METRICS: Dict[str, MetricSpec] = {m.name: m for m in [
    # -- engine identity / geometry (labels & gauges) ----------------------
    _m("engine", "label", (str,), "", "engine tag (sequential / batched / "
       "batched+mesh / participating+<policy>)"),
    _m("path", "label", (str, type(None)), "", "dispatch path: fused (one "
       "dispatch per epoch) or chunked/per-round"),
    _m("dispatch_path", "label", (str,), "", "bench-row alias column for "
       "`path`"),
    _m("devices", "gauge", (int,), "devices", "mesh device count the epoch "
       "ran on"),
    _m("clients", "gauge", (int,), "clients", "clients trained in the row"),
    _m("hetero", "label", (bool,), "", "mixed-nf population row"),
    _m("cohorts", "gauge", (int,), "cohorts", "homogeneous cohorts the "
       "population was partitioned into"),
    _m("per_cohort", "label", (list,), "", "per-cohort geometry breakdown "
       "(nf / clients / sub_rounds / dispatches)"),
    # -- work accounting (counters) ----------------------------------------
    _m("epochs", "counter", (int,), "epochs", "epochs executed"),
    _m("dispatches", "counter", (int,), "dispatches", "device dispatches "
       "issued"),
    _m("dispatches_per_epoch", "gauge", _NUM, "dispatches/epoch",
       "dispatch amplification (1.0 = fully fused)"),
    _m("exchange_every", "gauge", (int,), "sub-rounds", "bounded-staleness "
       "cadence k: exchange every k-th sub-round"),
    _m("exchange_rounds", "counter", (int,), "rounds", "federated exchange "
       "rounds executed"),
    _m("round_ms", "gauge", _NUM, "ms", "mean wall-clock per client round"),
    _m("client_rounds_per_s", "gauge", _NUM, "rounds/s", "aggregate client-"
       "round throughput"),
    _m("speedup_vs_sequential", "gauge", _OPT_NUM, "x", "throughput vs the "
       "sequential oracle (null when the oracle was skipped)"),
    # -- comms / memory accounting -----------------------------------------
    _m("pool_bytes_gathered", "counter", (int,), "bytes", "pool + probe "
       "bytes all-gathered per device over the run"),
    _m("state_bytes", "gauge", (int,), "bytes", "resident stacked client "
       "state on device"),
    _m("resident_state_bytes", "gauge", (int,), "bytes", "device working "
       "set of the resident wave"),
    _m("resident_clients", "gauge", (int,), "clients", "clients resident "
       "on device at once"),
    _m("store_clients", "gauge", (int,), "clients", "clients parked in the "
       "host-side ClientStore"),
    _m("store_bytes", "gauge", (int,), "bytes", "host-side ClientStore "
       "footprint"),
    _m("gather_bytes", "counter", (int,), "bytes", "host->device state "
       "gathered across waves"),
    _m("scatter_bytes", "counter", (int,), "bytes", "device->host state "
       "scattered back across waves"),
    # -- participation ------------------------------------------------------
    _m("population", "gauge", (int,), "clients", "declared population size"),
    _m("participation", "label", (str, type(None)), "", "participation "
       "policy kind"),
    _m("participation_fraction", "gauge", _NUM, "", "sampled fraction per "
       "wave"),
    _m("waves", "counter", (int,), "waves", "participation waves executed"),
    # -- fault / trust counters --------------------------------------------
    _m("fault_rate", "gauge", _NUM, "", "injected dropout probability"),
    _m("byzantine_frac", "gauge", _NUM, "", "injected byzantine probability"),
    _m("heads_rejected", "counter", (int,), "heads", "publications the "
       "admission guard quarantined"),
    _m("clients_dropped", "counter", (int,), "clients", "clients dropped "
       "from waves by injected faults"),
    _m("stragglers", "counter", (int,), "clients", "clients masked out of "
       "exchanges as stragglers"),
    _m("waves_degraded", "counter", (int,), "waves", "waves that lost at "
       "least one client"),
    _m("store_rebuilds", "counter", (int,), "entries", "corrupt store "
       "entries rebuilt from the deterministic builder"),
    _m("quarantined", "label", (list,), "", "client names quarantined by "
       "the reputation book"),
    _m("quarantined_drops", "counter", (int,), "clients", "sampled clients "
       "removed by reputation quarantine"),
    _m("epsilon_spent", "gauge", _NUM, "eps", "max per-client analytic DP "
       "epsilon spent"),
    _m("clip_events", "counter", (int,), "heads", "DP L2-clip activations"),
    _m("watermark_failures", "counter", (int,), "heads", "watermark "
       "verification failures"),
    _m("mean_val", "gauge", _OPT_NUM, "", "mean final validation metric "
       "over finite clients (null when not collected)"),
    # -- telemetry's own series (histograms over the round axis) -----------
    _m("foreign_picks", "counter", (int,), "picks", "feature-level foreign "
       "head selections recorded in round events"),
    _m("client_rounds", "counter", (int,), "rounds", "client exchange "
       "rounds executed (throughput numerator)"),
    _m("restack_host_leaves", "counter", (int,), "leaves", "stacked-state "
       "leaves a batched fit stacked on the host (np.stack of host rows)"),
    _m("restack_device_leaves", "counter", (int,), "leaves", "stacked-state "
       "leaves a batched fit stacked on the device (some row a device "
       "array)"),
    _m("union_rows", "counter", (int,), "rows", "rows of the cohort "
       "engine's padded union pool scored per exchange round (C * max_nf), "
       "summed over exchange rounds"),
    _m("union_real_rows", "counter", (int,), "rows", "real (unpadded) rows "
       "among them (the sum of the clients' nf), summed over exchange "
       "rounds"),
    _m("score_min", "histogram", _NUM, "", "per-round minimum Eq.-7 score "
       "over valid candidates"),
    _m("score_mean", "histogram", _NUM, "", "per-round mean Eq.-7 score "
       "over valid candidates"),
    _m("pool_age", "histogram", (int,), "rounds", "per-round pool "
       "staleness-age distribution (quarantine sentinel masked)"),
]}

def metric_spec(name: str) -> MetricSpec:
    return METRICS[name]


def schema() -> dict:
    """The machine-readable metrics schema: name -> {kind, types, unit,
    description}."""
    return {
        name: {
            "kind": m.kind,
            "types": [t.__name__ for t in m.types],
            "unit": m.unit,
            "description": m.description,
        }
        for name, m in sorted(METRICS.items())
    }


def validate_stats(stats: dict, *, where: str = "dispatch_stats") -> None:
    """Every key must be a catalog name carrying a value of the registered
    type."""
    for k, v in stats.items():
        if k not in METRICS:
            raise ValueError(f"{where}: unknown metric {k!r}")
        m = METRICS[k]
        if m.types and not (isinstance(v, m.types)
                            and not (isinstance(v, bool)
                                     and bool not in m.types)):
            raise ValueError(f"{where}[{k!r}]: expected {m.types}, got "
                             f"{type(v).__name__}")


# ---------------------------------------------------------------------------
# FlightRecorder: bounded host-side event ring + span tracer
# ---------------------------------------------------------------------------

def _now_us(origin: float) -> int:
    return int(round((time.perf_counter() - origin) * 1e6))


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction: op_name metadata} of a compiled HLO module's text,
    ``""`` for an instruction the compiler made without one.  The names
    are those a device profile gives its op events (``%fusion.12``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name = _OP_NAME.search(line)
            out[m.group(1)] = name.group(1) if name else ""
    return out


class FlightRecorder:
    """A bounded ring buffer of telemetry events with a span tracer.

    Events are plain JSON-serializable dicts with a ``type`` field:

    * ``{"type": "span", "name", "ts", "dur", "depth", ...}`` — a closed
      host-side span; ``ts``/``dur`` are microseconds on the recorder's
      monotonic clock (which survives checkpoint restore: restored
      recorders keep counting up from their last timestamp).
    * ``{"type": "round", "epoch", "round", "foreign_per_client", ...}``
      — one decoded in-graph exchange round (see ``record_epoch_rounds``).

    The deque drops the OLDEST events at capacity — a flight recorder
    keeps the latest window, like the real thing.
    """

    def __init__(self, plan: Optional[TelemetryPlan]):
        self.plan = plan if plan is not None else TelemetryPlan(
            rounds=False, spans=False)
        self.events: collections.deque = collections.deque(
            maxlen=self.plan.ring_size)
        self.counters: Dict[str, float] = {}
        self._origin = time.perf_counter()
        self._depth = 0
        # under plan.profile: compiled module name -> hlo_op_names, for the
        # programs noted by note_program (not persisted)
        self.programs: Dict[str, Dict[str, str]] = {}
        self._noted: set = set()
        self.wall_start = time.time()

    # -- spans --------------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time a host-side phase.  No-op (zero events) unless the plan
        enables spans; with ``plan.profile`` the span also opens a
        ``jax.profiler.TraceAnnotation`` so it lands in XLA profiles."""
        if not self.plan.spans:
            yield
            return
        ann = (jax.profiler.TraceAnnotation(name) if self.plan.profile
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        ts = _now_us(self._origin)
        depth, self._depth = self._depth, self._depth + 1
        try:
            with ann:
                yield
        finally:
            self._depth -= 1
            dur = int(round((time.perf_counter() - t0) * 1e6))
            self.events.append({"type": "span", "name": name, "ts": ts,
                                "dur": dur, "depth": depth, **attrs})

    @contextlib.contextmanager
    def gc_spans(self):
        """Record every generation-2 garbage collection while open as a
        ``gc`` span (a ``TraceAnnotation`` too under ``plan.profile``), so
        the host pauses they cause carry their cause in a trace.  No hook
        is registered unless the plan enables spans."""
        if not self.plan.spans:
            yield
            return
        open_ = []

        def hook(phase, info):
            if info["generation"] != 2:
                return
            if phase == "start":
                ann = (jax.profiler.TraceAnnotation("gc")
                       if self.plan.profile else contextlib.nullcontext())
                ann.__enter__()
                open_.append((ann, time.perf_counter(),
                              _now_us(self._origin)))
            elif open_:
                ann, t0, ts = open_.pop()
                ann.__exit__(None, None, None)
                self.events.append({
                    "type": "span", "name": "gc", "ts": ts,
                    "dur": int(round((time.perf_counter() - t0) * 1e6)),
                    "depth": self._depth, "generation": 2,
                    "collected": info["collected"]})

        gc.callbacks.append(hook)
        try:
            yield
        finally:
            gc.callbacks.remove(hook)

    def note_program(self, fn, *args) -> None:
        """Under ``plan.profile``, keep the ``op_name`` of every instruction
        of ``fn``'s compiled program for ``args`` in ``programs``, under the
        module's name, once per ``fn``: a device profile names bare
        instructions, and this map puts each one in its named scope.  Call
        it before the dispatch that donates ``args``.  Costs one lowering
        and one compile.  The compile keys JAX's persistent cache with the
        metadata included: under the default key, which leaves it out, a
        hit may serve a compile of the same computation made before its
        scopes existed, whose ``op_name``s lack them."""
        if not self.plan.profile or fn in self._noted:
            return
        self._noted.add(fn)
        opt = "jax_compilation_cache_include_metadata_in_key"
        was = getattr(jax.config, opt)
        jax.config.update(opt, True)
        try:
            text = fn.lower(*args).compile().as_text()
        finally:
            jax.config.update(opt, was)
        module = text.split(",", 1)[0].split()[-1]   # "HloModule <name>, ..."
        self.programs[module] = hlo_op_names(text)

    # -- counters -----------------------------------------------------------

    def count(self, name: str, inc) -> None:
        """Bump a registry counter (name should be a catalog name)."""
        self.counters[name] = self.counters.get(name, 0) + inc

    def snapshot(self) -> dict:
        """The counter registry, canonical names, JSON-clean values."""
        return {k: (int(v) if float(v).is_integer() else float(v))
                for k, v in sorted(self.counters.items())}

    # -- in-graph series decode ---------------------------------------------

    def record_epoch_rounds(self, epoch: int, tele, active=None) -> None:
        """Decode one epoch's stacked in-graph series (the metrics carry's
        scan outputs) into per-round events.

        ``tele`` is the scan-output tuple ``(foreign, score_min,
        score_mean, pool_age)`` with leading round axis; ``active`` is the
        host-side participation mask for the epoch (distinguishes a
        self-keep — active client, zero foreign picks — from a client that
        sat the round out)."""
        if not self.plan.rounds:
            return
        fpick, smin, smean, age = (np.asarray(t) for t in tele)
        act = (np.asarray([bool(active[k]) for k in active])
               if isinstance(active, dict)
               else np.asarray(active, bool) if active is not None
               else None)
        for r in range(fpick.shape[0]):
            fr = fpick[r].astype(int)
            mn, me = smin[r], smean[r]
            finite_mn = mn[np.isfinite(mn)]
            finite_me = me[np.isfinite(me) & (mn != np.inf)]
            live = age[r][age[r] < QUARANTINE_AGE]
            n_active = int(act.sum()) if act is not None \
                else int((fr > 0).sum())
            ev = {
                "type": "round", "epoch": int(epoch), "round": int(r),
                "ts": _now_us(self._origin),
                "foreign_per_client": fr.tolist(),
                "foreign_picks": int(fr.sum()),
                "self_keeps": max(0, n_active - int((fr > 0).sum())),
                "score_min": (float(finite_mn.min())
                              if finite_mn.size else None),
                "score_mean": (float(finite_me.mean())
                               if finite_me.size else None),
                "age_mean": (float(live.mean()) if live.size else None),
                "age_max": (int(live.max()) if live.size else None),
            }
            self.events.append(ev)
            self.count("foreign_picks", int(fr.sum()))

    def last_round_event(self) -> Optional[dict]:
        for ev in reversed(self.events):
            if ev.get("type") == "round":
                return ev
        return None

    # -- persistence ---------------------------------------------------------

    def dump_jsonl(self, path) -> None:
        with open(path, "w") as f:
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")

    @staticmethod
    def load_jsonl(path) -> List[dict]:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]

    def to_json(self) -> dict:
        """Manifest-serializable state: the full event window, counters,
        and the last timestamp so a restored recorder's clock continues
        monotonically past everything already recorded."""
        return {"ring_size": self.plan.ring_size,
                "events": list(self.events),
                "counters": self.snapshot(),
                "last_ts": self._last_ts()}

    def _last_ts(self) -> int:
        last = 0
        for ev in self.events:
            last = max(last, int(ev.get("ts", 0)) + int(ev.get("dur", 0)))
        return last

    @classmethod
    def from_json(cls, plan: Optional[TelemetryPlan], data: dict
                  ) -> "FlightRecorder":
        rec = cls(plan)
        rec.events.extend(data.get("events", []))
        rec.counters.update(data.get("counters", {}))
        # resume the monotonic clock strictly after the restored window
        rec._origin = time.perf_counter() - data.get("last_ts", 0) * 1e-6
        return rec


@contextlib.contextmanager
def span(recorder: Optional[FlightRecorder], name: str, **attrs):
    """``with span(rec, "gather"): ...`` — no-op when ``rec`` is None, so
    call sites need no telemetry-enabled branch."""
    if recorder is None:
        yield
    else:
        with recorder.span(name, **attrs):
            yield


@contextlib.contextmanager
def gc_spans(recorder: Optional[FlightRecorder]):
    """``with gc_spans(rec): ...`` — :meth:`FlightRecorder.gc_spans`, or a
    no-op (no hook registered) when ``rec`` is None."""
    if recorder is None:
        yield
    else:
        with recorder.gc_spans():
            yield
