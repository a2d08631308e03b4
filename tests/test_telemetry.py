"""Flight-recorder telemetry (repro.core.telemetry + tools/trace_export).

Pins the ISSUE-10 acceptance surface: ``telemetry=None``, a fully
disabled plan, and no argument at all trace the byte-identical graph on
the batched, cohort, and (subprocess, forced-4-device) mesh engines —
identical validation histories AND identical selections; an enabled plan
surfaces the per-round in-graph series from a still-single-dispatch
epoch, and those series exactly match the sequential oracle's selection
log at exchange cadences k in {1, 2}; the flight recorder's ring buffer
is bounded; the JSONL -> Chrome-trace/Perfetto export is pinned by
golden files; and a checkpointed recorder restores bit-identically and
keeps its monotonic clock counting upward."""
import gc
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.core import telemetry as TEL
from repro.core.experiment import tensor_population
from repro.core.federation import Callback, Federation, RoundSchedule
from repro.core.hfl import HFLConfig
from repro.core.policies import policy_from_spec

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "tools"))

from trace_export import (assert_spans_nest, chrome_trace,  # noqa: E402
                          load_jsonl, validate_trace)


def _cfg(**kw):
    kw.setdefault("epochs", 3)
    kw.setdefault("R", 10)
    kw.setdefault("mode", "always")
    kw.setdefault("seed", 0)
    return HFLConfig(**kw)


def _pop(cfg, n=6, nf_choices=(3,), seed=0):
    return tensor_population(n, cfg, seed=seed, nf_choices=nf_choices,
                             n_train=20, n_eval=10)


def _fit(cfg, n=6, nf_choices=(3,), engine="batched", exchange_every=1,
         **fed_kw):
    clients = _pop(cfg, n, nf_choices).build(range(n))
    fed = Federation(clients, cfg, engine=engine,
                     schedule=RoundSchedule(cfg.epochs, cfg.R,
                                            exchange_every=exchange_every),
                     **fed_kw)
    hist = fed.fit()
    return fed, hist


# ---------------------------------------------------------------------------
# TelemetryPlan units
# ---------------------------------------------------------------------------

def test_plan_validation():
    with pytest.raises(ValueError, match="ring_size"):
        TEL.TelemetryPlan(ring_size=0)
    with pytest.raises(ValueError, match="ring_size"):
        TEL.TelemetryPlan(ring_size=-5)
    assert TEL.TelemetryPlan().enabled
    assert TEL.TelemetryPlan(rounds=False).enabled       # spans still on
    assert not TEL.TelemetryPlan(rounds=False, spans=False).enabled


def test_plan_spec_round_trip():
    plan = TEL.TelemetryPlan(rounds=True, spans=False, ring_size=128,
                             profile=True)
    spec = plan.spec()
    assert policy_from_spec(spec) == plan
    assert policy_from_spec(json.loads(json.dumps(spec))) == plan


def test_federation_rejects_non_plan():
    cfg = _cfg(epochs=1)
    clients = _pop(cfg, 2).build(range(2))
    with pytest.raises(TypeError, match="TelemetryPlan"):
        Federation(clients, cfg, telemetry={"rounds": True})


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_metrics_schema_is_json_clean_and_self_describing():
    sch = TEL.schema()
    assert json.loads(json.dumps(sch)) == sch
    for name, m in sch.items():
        assert m["kind"] in TEL.KINDS, name
        assert m["description"], name
        assert TEL.metric_spec(name).unit == m["unit"]


def test_validate_stats_rejects_unknown_and_aliased_keys():
    TEL.validate_stats({"heads_rejected": 2, "devices": 1})
    with pytest.raises(ValueError, match="made_up_metric"):
        TEL.validate_stats({"made_up_metric": 1})
    with pytest.raises(ValueError, match="rejected_heads"):
        TEL.validate_stats({"rejected_heads": 2})   # not a catalog name
    with pytest.raises(ValueError, match="heads_rejected"):
        TEL.validate_stats({"heads_rejected": 2.5})


@pytest.mark.parametrize("engine", ("sequential", "batched"))
def test_engine_dispatch_stats_use_canonical_names(engine):
    """Every engine emits catalog names with registered types — the
    satellite-1 unification pin."""
    fed, _ = _fit(_cfg(epochs=2), engine=engine)
    TEL.validate_stats(fed.dispatch_stats)


def test_cohort_dispatch_stats_use_canonical_names():
    fed, _ = _fit(_cfg(epochs=2), nf_choices=(3, 4))
    assert fed.dispatch_stats["cohorts"] == 2
    TEL.validate_stats(fed.dispatch_stats)


# ---------------------------------------------------------------------------
# Bit-parity: telemetry off == telemetry absent, every engine
# ---------------------------------------------------------------------------

def _histories_equal(h0, h1):
    return all(h0[n]["val"] == h1[n]["val"]
               and h0[n]["selections"] == h1[n]["selections"]
               for n in h0)


@pytest.mark.parametrize("nf_choices", ((3,), (3, 4)),
                         ids=("batched", "cohort"))
def test_disabled_plan_bit_parity(nf_choices):
    """No argument, telemetry=None, and a disabled plan produce identical
    histories AND selections on the single-device batched and cohort
    engines; so does the fully enabled plan (the carry is observation,
    never interference)."""
    cfg = _cfg()
    runs = [
        _fit(cfg, nf_choices=nf_choices)[1],
        _fit(cfg, nf_choices=nf_choices, telemetry=None)[1],
        _fit(cfg, nf_choices=nf_choices,
             telemetry=TEL.TelemetryPlan(rounds=False, spans=False))[1],
        _fit(cfg, nf_choices=nf_choices, telemetry=TEL.TelemetryPlan())[1],
    ]
    for other in runs[1:]:
        assert _histories_equal(runs[0], other)


def test_single_dispatch_with_carry():
    """The metrics carry rides the fused epoch scan: one epoch is still
    ONE dispatch with telemetry fully enabled."""
    fed, _ = _fit(_cfg(), telemetry=TEL.TelemetryPlan())
    assert fed.dispatch_stats["dispatches_per_epoch"] == 1.0
    assert fed.dispatch_stats["path"] == "fused"


# ---------------------------------------------------------------------------
# Per-round series vs the sequential oracle's selection log
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", (1, 2))
def test_round_series_match_sequential_oracle(k):
    """mode="always": every active client federates on every exchange
    round, so the in-graph series must show exactly nf foreign picks per
    client per round event, the decoded round count must equal the
    oracle's per-client selection-log length, and the batched selections
    must equal the oracle's — at cadence k in {1, 2}."""
    cfg = _cfg(epochs=2)
    nf = 3
    fed_b, hist_b = _fit(cfg, exchange_every=k,
                         telemetry=TEL.TelemetryPlan())
    fed_s, hist_s = _fit(cfg, engine="sequential", exchange_every=k)
    for n in hist_b:
        assert hist_b[n]["selections"] == hist_s[n]["selections"]
    rounds = [e for e in fed_b._recorder.events if e["type"] == "round"]
    names = sorted(hist_s)
    n_sel = {n: len(hist_s[n]["selections"]) for n in names}
    assert len(rounds) == n_sel[names[0]]      # equal lengths, mode=always
    for ev in rounds:
        assert ev["foreign_picks"] == nf * len(names)
        assert ev["foreign_per_client"] == [nf] * len(names)
        assert ev["self_keeps"] == 0
        assert ev["score_min"] is not None
        assert ev["score_mean"] is not None
        assert ev["score_min"] <= ev["score_mean"]
    total = sum(nf * c for c in n_sel.values())
    assert fed_b._recorder.counters["foreign_picks"] == total


def test_round_series_sentinels_when_not_federating():
    """mode="no": no selection ever scores, so the series records zero
    foreign picks and null score aggregates — the sentinel path."""
    fed, _ = _fit(_cfg(mode="no", epochs=2),
                  telemetry=TEL.TelemetryPlan())
    rounds = [e for e in fed._recorder.events if e["type"] == "round"]
    assert rounds
    for ev in rounds:
        assert ev["foreign_picks"] == 0
        assert ev["score_min"] is None and ev["score_mean"] is None


# ---------------------------------------------------------------------------
# FlightRecorder mechanics
# ---------------------------------------------------------------------------

def test_ring_buffer_bounded_keeps_newest():
    rec = TEL.FlightRecorder(TEL.TelemetryPlan(ring_size=8))
    for i in range(100):
        with rec.span(f"m{i}"):
            pass
    assert len(rec.events) == 8
    assert [e["name"] for e in rec.events] == [f"m{i}"
                                               for i in range(92, 100)]


def test_span_nesting_depth_and_counters():
    rec = TEL.FlightRecorder(TEL.TelemetryPlan())
    with rec.span("fit", epochs=1):
        with rec.span("dispatch", epoch=0):
            rec.count("client_rounds", 4)
        rec.count("client_rounds", 2)
    spans = {e["name"]: e for e in rec.events if e["type"] == "span"}
    assert spans["dispatch"]["depth"] == 1 and spans["fit"]["depth"] == 0
    assert spans["fit"]["dur"] >= spans["dispatch"]["dur"]
    assert rec.snapshot() == {"client_rounds": 6}


def test_disabled_spans_record_nothing():
    rec = TEL.FlightRecorder(TEL.TelemetryPlan(spans=False))
    with rec.span("fit"):
        with rec.span("dispatch"):
            pass
    assert not rec.events
    with TEL.span(None, "anything"):      # module-level no-op form
        pass


def test_recorder_json_round_trip_continues_clock():
    rec = TEL.FlightRecorder(TEL.TelemetryPlan(ring_size=16))
    with rec.span("fit"):
        rec.count("client_rounds", 3)
    data = json.loads(json.dumps(rec.to_json()))
    rec2 = TEL.FlightRecorder.from_json(TEL.TelemetryPlan(ring_size=16),
                                        data)
    assert list(rec2.events) == list(rec.events)
    assert rec2.snapshot() == rec.snapshot()
    last = max(e["ts"] + e.get("dur", 0) for e in rec.events)
    with rec2.span("later"):
        pass
    assert rec2.events[-1]["ts"] >= last  # monotonic past the restored end


# ---------------------------------------------------------------------------
# Export: JSONL + Chrome-trace/Perfetto golden files
# ---------------------------------------------------------------------------

def test_export_golden_files():
    """The golden JSONL event log converts to exactly the golden trace —
    the export format is pinned, not just structurally valid."""
    events = load_jsonl(ROOT / "tests/golden/telemetry_events.jsonl")
    trace = chrome_trace(events, metrics={"foreign_picks": 2,
                                          "client_rounds": 4})
    golden = json.loads(
        (ROOT / "tests/golden/telemetry_trace.json").read_text())
    assert trace == golden
    validate_trace(trace)
    assert_spans_nest(trace["traceEvents"])


def test_live_run_exports_valid_trace(tmp_path):
    fed, _ = _fit(_cfg(epochs=2), telemetry=TEL.TelemetryPlan())
    rec = fed._recorder
    jsonl = tmp_path / "run.jsonl"
    rec.dump_jsonl(jsonl)
    events = load_jsonl(jsonl)
    assert events == list(rec.events)
    trace = chrome_trace(events, metrics=rec.snapshot())
    validate_trace(trace)
    assert_spans_nest(trace["traceEvents"])
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"fit", "restack", "dispatch", "readback", "record",
            "writeback", "results", "test_pass"} <= names
    assert "exchange" not in names    # the orchestrator's wave span only
    assert any(e["ph"] == "C" for e in trace["traceEvents"])


def test_trace_export_cli(tmp_path):
    src = ROOT / "tests/golden/telemetry_events.jsonl"
    out = tmp_path / "trace.json"
    r = subprocess.run([sys.executable, str(ROOT / "tools/trace_export.py"),
                        "--in", str(src), "--out", str(out), "--validate"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    validate_trace(json.loads(out.read_text()))


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({})
    with pytest.raises(ValueError, match="missing 'ph'"):
        validate_trace({"traceEvents": [{"name": "x", "ts": 0, "pid": 1,
                                         "tid": 1}]})
    with pytest.raises(ValueError, match="negative ts"):
        validate_trace({"traceEvents": [{"name": "x", "ph": "i", "ts": -1,
                                         "pid": 1, "tid": 1}]})
    with pytest.raises(ValueError, match="dur"):
        validate_trace({"traceEvents": [{"name": "x", "ph": "X", "ts": 0,
                                         "pid": 1, "tid": 1}]})


def test_assert_spans_nest_rejects_partial_overlap():
    ok = [{"name": "a", "ph": "X", "ts": 0, "dur": 100, "pid": 1, "tid": 1},
          {"name": "b", "ph": "X", "ts": 10, "dur": 20, "pid": 1, "tid": 1},
          {"name": "c", "ph": "X", "ts": 50, "dur": 50, "pid": 1, "tid": 1}]
    assert_spans_nest(ok)
    bad = ok + [{"name": "d", "ph": "X", "ts": 90, "dur": 30,
                 "pid": 1, "tid": 1}]
    with pytest.raises(ValueError, match="partially overlaps"):
        assert_spans_nest(bad)


# ---------------------------------------------------------------------------
# Layer timing: named scopes in the fused epoch, spans in the fit loop
# ---------------------------------------------------------------------------

EPOCH_SCOPES = ("policy_round", "eq7_score", "train_step", "eval_best")


def _spy_epoch_fn(monkeypatch, module, factory):
    """Wrap ``module.factory`` so the first epoch dispatch leaves its
    compiled function and argument shapes behind."""
    import jax
    real = getattr(module, factory)
    seen = {}

    def spy(*a, **kw):
        fn = real(*a, **kw)

        def call(*args):
            seen.setdefault("fn", fn)
            seen.setdefault("args", jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
            return fn(*args)
        call.lower = fn.lower
        return call

    monkeypatch.setattr(module, factory, spy)
    return seen


@pytest.mark.parametrize("nf_choices,kernel", [
    ((3,), False), ((3,), True), ((3, 4), False), ((3, 4), True)],
    ids=("batched", "batched-kernel", "cohort", "cohort-kernel"))
def test_epoch_scopes_in_op_metadata(monkeypatch, nf_choices, kernel):
    """Every layer scope names ops of the lowered and of the compiled
    epoch program, on the vmap scorer and on the Pallas pool kernel; under
    ``profile`` the recorder keeps the compiled program's op names."""
    from repro.core import cohorts, federation
    module, factory = ((federation, "_make_epoch_fn") if len(nf_choices) == 1
                       else (cohorts, "_make_hetero_epoch_fn"))
    seen = _spy_epoch_fn(monkeypatch, module, factory)
    fed, _ = _fit(_cfg(epochs=1, use_pool_kernel=kernel), n=4,
                  nf_choices=nf_choices,
                  telemetry=TEL.TelemetryPlan(rounds=False, profile=True))
    lowered = seen["fn"].lower(*seen["args"]).as_text(dialect="hlo",
                                                      debug_info=True)
    compiled = fed._recorder.programs[
        "jit_epoch" if len(nf_choices) == 1 else "jit_hetero_epoch"]
    assert compiled == TEL.hlo_op_names(
        seen["fn"].lower(*seen["args"]).compile().as_text())
    for names in ([line.split('op_name="', 1)[1].split('"', 1)[0]
                   for line in lowered.splitlines() if 'op_name="' in line],
                  compiled.values()):
        paths = [n.split("/") for n in names]
        for scope in EPOCH_SCOPES:
            assert any(scope in p for p in paths), scope
    # compiled op names carry the whole path (lowered ones start afresh in
    # each called function): the scorer runs inside the policy round
    assert all("policy_round" in p for p in paths if "eq7_score" in p)
    assert all(k.startswith("%") for k in compiled)


@pytest.mark.parametrize("nf_choices", ((3,), (3, 4, 5)),
                         ids=("batched", "cohort"))
def test_union_view_scope_and_counters(nf_choices):
    """The cohort engine's epoch compiles as its own module,
    ``jit_hetero_epoch``, with ``union_view`` ops, and counts the padded
    union's rows and the real ones per exchange round; the homogeneous
    epoch stays ``jit_epoch`` with neither."""
    fed, _ = _fit(_cfg(epochs=2), n=6, nf_choices=nf_choices,
                  telemetry=TEL.TelemetryPlan(rounds=False, profile=True))
    rec, stats = fed._recorder, fed.dispatch_stats
    cohort = len(nf_choices) > 1
    (module, ops), = rec.programs.items()
    assert module == ("jit_hetero_epoch" if cohort else "jit_epoch")
    union_ops = [o for o in ops.values() if "union_view" in o.split("/")]
    counted = {k: rec.counters[k] for k in ("union_rows", "union_real_rows")
               if k in rec.counters}
    if not cohort:
        assert not union_ops and counted == {}
        return
    assert union_ops
    nfs = [c.nf for c in fed.clients]
    assert sorted(set(nfs)) == [3, 4, 5]
    rounds = stats["exchange_rounds"]
    assert rounds == 2 * 2           # 2 epochs x 2 exchange sub-rounds
    assert counted == {"union_rows": len(nfs) * max(nfs) * rounds,
                       "union_real_rows": sum(nfs) * rounds}
    assert {"union_rows", "union_real_rows"} <= set(TEL.schema())


def test_hlo_op_names():
    text = """HloModule jit_epoch, is_scheduled=true

%fused (p.2: f32[4]) -> f32[4] {
  %p.2 = f32[4]{0} parameter(0)
  ROOT %tanh.2 = f32[4]{0} tanh(%p.2), metadata={op_name="jit(epoch)/policy_round/eq7_score/tanh" stack_frame_id=5}
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused, metadata={op_name="jit(epoch)/policy_round/eq7_score/tanh"}
  ROOT %copy-done.3 = f32[4]{0} copy-done(%fusion.1)
}
"""
    assert TEL.hlo_op_names(text) == {
        "%p.2": "", "%tanh.2": "jit(epoch)/policy_round/eq7_score/tanh",
        "%p": "", "%fusion.1": "jit(epoch)/policy_round/eq7_score/tanh",
        "%copy-done.3": ""}


_STALE_CACHE = r"""
import sys
import jax
import jax.numpy as jnp
from repro.core import telemetry as TEL

jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def make(scope):
    def f(x):
        with jax.named_scope(scope):
            return jnp.tanh(x @ x)
    return jax.jit(f)


x = jnp.ones((8, 8))
if sys.argv[2] == "before":
    make("before")(x).block_until_ready()     # fills the persistent cache
else:
    rec = TEL.FlightRecorder(TEL.TelemetryPlan(profile=True))
    g = make("policy_round")
    rec.note_program(g, x)
    g(x).block_until_ready()
    print("RESULT " + " ".join(v for v in rec.programs["jit_f"].values()
                               if v))
"""


def test_note_program_sees_scopes_past_a_stale_cache_entry(tmp_path):
    """JAX's persistent cache keys a program without its metadata, so a
    cache filled before a scope existed serves the scope-less op names;
    ``note_program`` keys its compile with the metadata and sees the
    scope."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    outs = [subprocess.run([sys.executable, "-c", _STALE_CACHE,
                            str(tmp_path), step], env=env,
                           capture_output=True, text=True, timeout=300)
            for step in ("before", "after")]
    assert all(o.returncode == 0 for o in outs), outs[-1].stderr[-2000:]
    line = [ln for ln in outs[1].stdout.splitlines()
            if ln.startswith("RESULT ")]
    names = line[-1].split()[1:]
    assert any("/policy_round/" in n for n in names), names
    assert not any("/before/" in n for n in names), names


def _spans(rec):
    return [e for e in rec.events if e["type"] == "span"]


def _inside(inner, outer):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1)


@pytest.mark.parametrize("nf_choices", ((3,), (3, 4)),
                         ids=("batched", "cohort"))
def test_fit_driver_spans(nf_choices):
    """Both engines record the same fit-driver spans: ``restack``,
    ``dispatch``, ``readback`` and ``record`` per epoch and ``writeback``
    inside ``fit``; ``results`` after it with ``test_pass`` inside."""
    fed, _ = _fit(_cfg(epochs=2), nf_choices=nf_choices,
                  telemetry=TEL.TelemetryPlan(rounds=False))
    spans = _spans(fed._recorder)
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)
    assert set(by) >= {"fit", "restack", "dispatch", "readback", "record",
                       "writeback", "results", "test_pass"}
    assert "exchange" not in by
    for name in ("dispatch", "readback", "record"):
        assert [e["epoch"] for e in by[name]] == [0, 1], name
    (fit,), (results,), (test_pass,) = by["fit"], by["results"], \
        by["test_pass"]
    for name in ("restack", "writeback", "dispatch", "readback", "record"):
        assert all(_inside(e, fit) and e["depth"] == fit["depth"] + 1
                   for e in by[name]), name
    assert _inside(test_pass, results)
    assert test_pass["depth"] == results["depth"] + 1
    assert results["ts"] >= fit["ts"] + fit["dur"] - 1
    assert fed._recorder.programs == {}     # kept under `profile` only


class _CollectOnce(Callback):
    """An ``on_epoch_end`` callback that forces one generation-2
    collection and notes how many gc hooks are registered meanwhile."""

    def __init__(self):
        self.hooks = []

    def on_epoch_end(self, fed, epoch, val, active):
        self.hooks.append(len(gc.callbacks))
        if epoch == 0:
            gc.collect(2)


def _fit_collecting(telemetry):
    cfg = _cfg(epochs=2)
    cb = _CollectOnce()
    fed = Federation(_pop(cfg, 4).build(range(4)), cfg, engine="batched",
                     callbacks=[cb], telemetry=telemetry)
    before = len(gc.callbacks)
    was = gc.isenabled()
    gc.disable()            # no collection but the forced one
    try:
        fed.fit()
    finally:
        if was:
            gc.enable()
    assert len(gc.callbacks) == before     # nothing left registered
    return fed, cb.hooks, before


def test_gc_collection_recorded_as_span():
    fed, hooks, before = _fit_collecting(TEL.TelemetryPlan(rounds=False))
    assert hooks == [before + 1] * 2       # registered for the fit only
    spans = _spans(fed._recorder)
    gcs = [e for e in spans if e["name"] == "gc"]
    assert len(gcs) == 1 and gcs[0]["generation"] == 2
    (fit,) = [e for e in spans if e["name"] == "fit"]
    assert _inside(gcs[0], fit)


@pytest.mark.parametrize("telemetry", (
    None, TEL.TelemetryPlan(rounds=True, spans=False)),
    ids=("none", "spans-off"))
def test_no_gc_hook_without_spans(telemetry):
    fed, hooks, before = _fit_collecting(telemetry)
    assert hooks == [before] * 2
    rec = fed._recorder
    assert rec is None or not _spans(rec)


def test_restack_counters_split_host_and_device_leaves():
    """A fresh Federation's clients hold device arrays from init, so its
    first fit stacks on the device; write-back leaves host rows, so the
    second fit stacks every leaf of the stacked state on the host."""
    import jax
    cfg = _cfg(epochs=2)
    clients = _pop(cfg).build(range(6))
    fed = Federation(clients, cfg, engine="batched",
                     schedule=RoundSchedule(2, cfg.R),
                     telemetry=TEL.TelemetryPlan(rounds=False))
    c0 = clients[0]
    n_leaves = len(jax.tree_util.tree_leaves(
        (c0.params, c0.opt_state, c0.best_params, c0.params["heads"])))
    names = ("restack_host_leaves", "restack_device_leaves")
    fed.fit(epochs=1)
    first = {k: fed._recorder.counters.get(k, 0) for k in names}
    assert first == {"restack_host_leaves": 0,
                     "restack_device_leaves": n_leaves}
    fed.fit(epochs=1)
    second = {k: fed._recorder.counters.get(k, 0) - first[k] for k in names}
    assert second == {"restack_host_leaves": n_leaves,
                      "restack_device_leaves": 0}
    assert set(names) <= set(TEL.schema())


# ---------------------------------------------------------------------------
# Checkpoint: the recorder rides the manifest and continues the trace
# ---------------------------------------------------------------------------

def test_federation_checkpoint_continues_trace():
    cfg = _cfg(epochs=4)
    plan = TEL.TelemetryPlan(ring_size=256)
    clients = _pop(cfg).build(range(6))
    fed = Federation(clients, cfg, schedule=RoundSchedule(4, cfg.R),
                     telemetry=plan)
    fed.fit(epochs=2)
    mid_events = list(fed._recorder.events)
    mid_counts = fed._recorder.snapshot()
    with tempfile.TemporaryDirectory() as d:
        fed.save(d)
        fed2 = Federation.restore(d, _pop(cfg).build(range(6)))
        assert fed2.telemetry == plan
        assert list(fed2._recorder.events) == mid_events
        assert fed2._recorder.snapshot() == mid_counts
        ha = fed.fit(epochs=2)
        hb = fed2.fit(epochs=2)
    assert _histories_equal(ha, hb)
    # the restored recorder CONTINUED: more events, larger counters, and
    # every post-restore timestamp lands after the restored window
    assert len(fed2._recorder.events) > len(mid_events)
    assert fed2._recorder.snapshot()["client_rounds"] \
        > mid_counts["client_rounds"]
    last_mid = max(e["ts"] + e.get("dur", 0) for e in mid_events)
    new = [e for e in fed2._recorder.events if e not in mid_events]
    assert new and all(e["ts"] >= last_mid for e in new)
    assert fed2._recorder.snapshot() == fed._recorder.snapshot()


def test_checkpoint_without_telemetry_restores_none():
    cfg = _cfg(epochs=1)
    fed, _ = _fit(cfg)
    with tempfile.TemporaryDirectory() as d:
        fed.save(d)
        fed2 = Federation.restore(d, _pop(cfg).build(range(6)))
    assert fed2.telemetry is None and fed2._recorder is None


# ---------------------------------------------------------------------------
# VerboseLogger throughput line
# ---------------------------------------------------------------------------

def test_verbose_logger_reports_wall_and_throughput(capsys):
    cfg = _cfg(epochs=2)
    clients = _pop(cfg).build(range(6))
    fed = Federation(clients, cfg, engine="batched",
                     telemetry=TEL.TelemetryPlan())
    fed.fit(verbose=True)
    out = capsys.readouterr().out
    assert "wall:" in out
    assert "client-rounds/s:" in out
    assert "staleness:" in out     # batched + rounds on: age aggregates


def test_verbose_logger_wall_line_without_telemetry(capsys):
    """Satellite 2: the wall/throughput line reports even with no plan —
    only the staleness suffix needs the in-graph series."""
    cfg = _cfg(epochs=1)
    clients = _pop(cfg).build(range(6))
    fed = Federation(clients, cfg, engine="batched")
    fed.fit(verbose=True)
    out = capsys.readouterr().out
    assert "wall:" in out and "client-rounds/s:" in out
    assert "staleness:" not in out


# ---------------------------------------------------------------------------
# Forced-4-device mesh: parity + live series (subprocess, like test_faults)
# ---------------------------------------------------------------------------

_SUBPROCESS = r"""
import json
import jax
assert jax.device_count() == 4, jax.devices()
from repro.core.experiment import tensor_population
from repro.core.federation import Federation, RoundSchedule
from repro.core.hfl import HFLConfig
from repro.core.mesh_federation import make_mesh
from repro.core.telemetry import TelemetryPlan

cfg = HFLConfig(epochs=2, R=10, mode="always", seed=3)
mkpop = lambda: tensor_population(8, cfg, seed=1, nf_choices=(3,),
                                  n_train=20, n_eval=10)
res = {}

def full(telemetry):
    fed = Federation(mkpop().build(range(8)), cfg,
                     schedule=RoundSchedule(2, 10), engine="batched",
                     mesh=make_mesh(), telemetry=telemetry)
    return fed, fed.fit()

f0, h0 = full(None)
f1, h1 = full(TelemetryPlan(rounds=False, spans=False))
f2, h2 = full(TelemetryPlan())
res["parity"] = all(
    h0[n]["val"] == h1[n]["val"] == h2[n]["val"]
    and h0[n]["selections"] == h1[n]["selections"] == h2[n]["selections"]
    for n in h0)
res["devices"] = f2.dispatch_stats["devices"]
res["dispatches_per_epoch"] = f2.dispatch_stats["dispatches_per_epoch"]
rounds = [e for e in f2._recorder.events if e["type"] == "round"]
res["n_rounds"] = len(rounds)
res["foreign_ok"] = all(e["foreign_picks"] == 3 * 8 for e in rounds)
res["scores_ok"] = all(e["score_min"] is not None
                       and e["score_min"] <= e["score_mean"]
                       for e in rounds)
res["counter"] = f2._recorder.counters.get("foreign_picks", 0)
print("RESULT " + json.dumps(res))
"""


_MESH_COHORT = r"""
import json
import jax
assert jax.device_count() == 4, jax.devices()
from repro.core.experiment import tensor_population
from repro.core.federation import Federation, RoundSchedule
from repro.core.hfl import HFLConfig
from repro.core.mesh_federation import make_mesh
from repro.core.telemetry import TelemetryPlan

cfg = HFLConfig(epochs=1, R=10, mode="always", seed=3)
clients = tensor_population(8, cfg, seed=1, nf_choices=(3, 4), n_train=20,
                            n_eval=10).build(range(8))
fed = Federation(clients, cfg, schedule=RoundSchedule(1, 10),
                 engine="batched", mesh=make_mesh(),
                 telemetry=TelemetryPlan(rounds=False, profile=True))
fed.fit()
res = {"modules": sorted(fed._recorder.programs),
       "union_ops": sum("union_view" in o.split("/") for p in
                        fed._recorder.programs.values() for o in p.values()),
       "devices": fed.dispatch_stats["devices"],
       "cohorts": fed.dispatch_stats["cohorts"],
       "rounds": fed.dispatch_stats["exchange_rounds"],
       "nfs": [c.nf for c in fed.clients],
       "counters": fed._recorder.snapshot()}
print("RESULT " + json.dumps(res))
"""


def _run_forced_devices(script: str, n_devices: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout
    return json.loads(line[-1][len("RESULT "):])


def test_telemetry_on_forced_4_device_mesh():
    """ISSUE 10 acceptance: on a forced 4-device mesh, telemetry=None ==
    disabled plan == enabled plan (val + selections); the enabled plan
    still runs ONE dispatch per epoch and surfaces per-round series whose
    replicated aggregates match the single-device semantics."""
    res = _run_forced_devices(_SUBPROCESS, 4)
    assert res["parity"]
    assert res["devices"] == 4
    assert res["dispatches_per_epoch"] == 1.0
    assert res["n_rounds"] == 2 * 2      # 2 epochs x 2 exchange rounds
    assert res["foreign_ok"] and res["scores_ok"]
    assert res["counter"] == 4 * 3 * 8


def test_mesh_cohort_epoch_module_and_union_counters():
    """The client-sharded cohort epoch compiles as ``jit_hetero_epoch``
    too, carries the ``union_view`` scope and counts the union's rows."""
    res = _run_forced_devices(_MESH_COHORT, 4)
    assert res["devices"] == 4 and res["cohorts"] == 2
    assert res["modules"] == ["jit_hetero_epoch"]
    assert res["union_ops"] > 0
    nfs, rounds = res["nfs"], res["rounds"]
    assert rounds == 2
    assert res["counters"]["union_rows"] == len(nfs) * max(nfs) * rounds
    assert res["counters"]["union_real_rows"] == sum(nfs) * rounds
