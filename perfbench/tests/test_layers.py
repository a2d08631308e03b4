"""Per-layer time by named scope and host span: the resolution and the
reductions on hand-made cases, and the readers on an extract recorded on
a TPU v5e (``data/layers_extract.json``: a traced ``silo32.always`` fit's
host spans, and its device ops from 2 ms before the second epoch's end to
2 ms into the third with their modules and scope maps, events as
``layers.extract`` keeps them)."""
import json
from pathlib import Path

import pytest

import devtrace as T
import layers as L
import run as RUN
from harness_cell import small_cell

DATA = Path(__file__).resolve().parent / "data"


def _hand_made():
    # one op name in two modules: only the epoch module's counts
    modules = [["jit_epoch(1)", 0, 100], ["jit_stack(2)", 200, 50]]
    ops = [["%while.1", 0, 100], ["%fusion.1", 10, 30], ["%fusion.2", 30, 30],
           ["%copy.3", 80, 10], ["%fusion.1", 210, 30]]
    scopes = {"jit_epoch(1)": {
        "%while.1": "jit(epoch)/while",
        "%fusion.1": "jit(epoch)/while/body/policy_round/eq7_score/dot",
        "%fusion.2": "jit(epoch)/while/body/policy_round/select",
        "%copy.3": "jit(epoch)/eval_best/copy"},
        "jit_stack(2)": {"%fusion.1": "jit(stack)/policy_round/x"}}
    return {"ops": ops, "modules": modules}, scopes


def test_scopes_resolve_per_module():
    dev, scopes = _hand_made()
    assert L.scope_ns(dev, scopes, "jit_epoch", 0, 300,
                      ("policy_round",)) == 50      # [10, 60]
    assert L.scope_ns(dev, scopes, "jit_epoch", 0, 300, ("eq7_score",)) == 30
    assert L.scope_ns(dev, scopes, "jit_epoch", 0, 300, L.TOP_SCOPES) == 60
    assert L.scope_ns(dev, scopes, "jit_stack", 0, 300,
                      ("policy_round",)) == 30
    assert not L.in_scope("jit(epoch)/policy_rounds/x", "policy_round")
    by = L.by_scope_ms(dev, scopes, "jit_epoch", 0, 300, 1)
    assert by["policy_round"] == pytest.approx(50e-6)
    # busy 130 (the while covers [0, 100], the stack op 30) less 60 scoped
    assert by["unscoped"] == pytest.approx(70e-6)


def test_idle_split_by_innermost_span():
    ops = [["a", 0, 10], ["b", 50, 10]]
    host = [["fit", 0, 100], ["record", 10, 20], ["dispatch", 35, 10]]
    got = dict(L.idle_by_span(ops, host, 0, 120))
    # gap [10, 50]: record 20, fit 5 + 5, dispatch 10; [60, 120]: fit 40,
    # no span 20
    assert got == pytest.approx({"record": 20e-9, "fit": 50e-9,
                                 "dispatch": 10e-9, "no span": 20e-9})


def test_span_readers_need_the_fit_driver_spans():
    host = [["bench.fit", 0, 100], ["fit", 1, 98]]
    ctx = {"trace": {"host": host}, "window": (0, 100)}
    for name in ("restack_ms", "writeback_ms", "results_ms",
                 "epoch_record_ms", "gc_pause_ms"):
        assert RUN._load_reader(name)(ctx) is None, name
    host += [["restack", 2, 10e6], ["record", 20e6, 2e6],
             ["record", 30e6, 4e6], ["record", 40e6, 3e6]]
    ctx["window"] = (0, 100e6)
    assert RUN._load_reader("restack_ms")(ctx) == pytest.approx(10)
    assert RUN._load_reader("epoch_record_ms")(ctx) == pytest.approx(3)
    assert RUN._load_reader("gc_pause_ms")(ctx) == 0
    assert RUN._load_reader("writeback_ms")(ctx) is None


@pytest.fixture(scope="module")
def recorded():
    with open(DATA / "layers_extract.json") as f:
        return json.load(f)


def test_readers_on_recorded_extract(recorded):
    """Host spans over the traced fit, device scopes over the slice of
    device ops the extract keeps (the end of one epoch and the start of
    the next), read as one epoch."""
    host = {"trace": recorded, "window": tuple(recorded["window"])}
    dev = {"trace": recorded, "window": tuple(recorded["device_window"]),
           "spec": {"epoch_module": "jit_epoch"}, "epochs": 1}
    got = {n: RUN._load_reader(n)(host) for n in (
        "restack_ms", "writeback_ms", "results_ms", "epoch_record_ms",
        "gc_pause_ms")}
    got.update({n: RUN._load_reader(n)(dev) for n in (
        "policy_round_ms", "eq7_score_ms", "train_step_ms")})
    assert got == pytest.approx(recorded["readings"], rel=1e-12)
    epoch = RUN._load_reader("epoch_device_ms")(dev)
    assert got["eq7_score_ms"] <= got["policy_round_ms"] <= epoch
    scoped = L.scope_ms_per_epoch(dev, L.TOP_SCOPES)
    assert 0.9 * epoch <= scoped <= epoch


def test_recorded_breakdowns(recorded):
    lo, hi = recorded["device_window"]
    dev = next(iter(recorded["devices"].values()))
    by = L.by_scope_ms(dev, recorded["op_scopes"], "jit_epoch", lo, hi, 1)
    assert set(by) == set(L.SCOPES) | {"unscoped"}
    assert by["eq7_score"] <= by["policy_round"]
    busy = T.busy_ns(dev["ops"], lo, hi) * 1e-6
    assert sum(by[s] for s in L.TOP_SCOPES) + by["unscoped"] \
        == pytest.approx(busy)
    idle = L.idle_by_span(dev["ops"], recorded["host"], lo, hi, k=100)
    assert {n for n, _ in idle} <= {h[0] for h in recorded["host"]}
    assert sum(v for _, v in idle) == pytest.approx((hi - lo - busy * 1e6)
                                                    * 1e-9)


def test_extract_keeps_program_spans(tmp_path):
    """On the CPU (no device plane): every program span of a profiled fit
    is kept; op scopes need a device's module events."""
    import jax
    from repro.core.telemetry import TelemetryPlan
    fed = RUN.build(small_cell(), 2**31 + 11, True, jax.devices())[0]
    fed.fit(epochs=1)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.fit"):
        fed.fit(epochs=2)
    jax.profiler.stop_trace()
    assert fed.telemetry == TelemetryPlan(rounds=False, spans=True,
                                          profile=True)
    assert "jit_epoch" in fed._recorder.programs
    ex = L.extract(str(tmp_path), fed._recorder.programs)
    names = [h[0] for h in ex["host"]]
    for span in ("bench.fit", "fit", "restack", "writeback", "results",
                 "test_pass"):
        assert names.count(span) == 1, span
    for span in ("dispatch", "readback", "record"):
        assert names.count(span) == 2, span
    assert ex["op_scopes"] == {}


def test_existing_readers_unchanged_on_recorded_extract():
    """The accepted readers read the same numbers from the recorded
    extract of ``test_devtrace.py`` as at the commit that added it."""
    with open(DATA / "trace_extract.json") as f:
        ex = json.load(f)
    lo, hi = ex["cut"]
    ctx = {"trace": ex, "window": (lo, hi), "epochs": 1, "fits": 1,
           "spec": {"epoch_module": "jit_epoch"}, "chips": 1,
           "peak": {"flops_per_s": 1.97e14}, "work": {"flops": 1e9},
           "fit_sync_s": [8.0, 9.0],
           "epoch_intervals_s": [k * 1e-3 for k in range(20, 0, -1)]}
    got = {n: RUN._load_reader(n)(ctx) for n in (
        "fit_sync_ms", "epoch_interval_p95_ms", "epoch_interval_median_ms",
        "epoch_device_ms", "mfu", "device_idle_share")}
    pin = json.loads((DATA / "readers_pin.json").read_text())
    dev = next(iter(ex["devices"].values()))
    assert T.top_ops(dev["ops"], lo, hi) == pin.pop("device_ops")
    assert got == pytest.approx(pin, rel=1e-12)
