"""Per-layer time from a profiler trace: device time by named scope and
host time by program span.

The fused epoch programs carry the named scopes ``policy_round`` (with
``eq7_score`` inside), ``train_step`` and ``eval_best`` in their ops'
``op_name`` metadata, and the fit driver records the host spans in
``PROGRAM_SPANS`` (``core/telemetry.py`` documents both).  A TPU v5e
trace under JAX 0.9 names its op events by bare HLO instruction (its op
events' stats hold only ``device_offset_ps``, ``device_duration_ps`` and
``Time Scale Multiplier``, and ``ProfileData`` shows no HLO in the
``/host:metadata`` plane), and instruction names repeat across modules.
So scopes resolve per module: each op is put in the "XLA Modules" event
that contains it, and its scope is looked up in that module's
``{instruction: op_name}`` map, which the program's flight recorder
keeps under ``TelemetryPlan(profile=True)`` (``FlightRecorder.programs``,
from the compiled program's text, fetched before the first dispatch).

``extract`` is ``devtrace.extract`` with two additions: ``host`` keeps
every span of ``PROGRAM_SPANS``, and ``op_scopes[module event][op]`` is
the op's ``op_name`` (``""`` where it has none).  The ``[name, start,
duration]`` entries are those of ``devtrace.extract``, so its readers
read the same data.  The rest are pure functions over such an extract,
checked on a recorded one in ``tests/``.
"""
from __future__ import annotations

import bisect
import glob
import statistics

import devtrace as T

PROGRAM_SPANS = ("fit", "restack", "dispatch", "readback", "record",
                 "writeback", "results", "test_pass", "gc", "exchange")
SCOPES = ("policy_round", "eq7_score", "train_step", "eval_best")
# the scopes whose union is the epoch's scoped time (eq7_score nests in
# policy_round)
TOP_SCOPES = ("policy_round", "train_step", "eval_best")


def module_index(modules):
    """A lookup from a time to the module event running then."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]

    def find(t):
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and t <= mods[k][1] + mods[k][2]:
            return mods[k][0]
        return None
    return find


def extract(trace_dir: str, programs: dict) -> dict:
    """``devtrace.extract`` plus every program span and ``op_scopes``
    from ``programs`` ({module name: {instruction: op_name}}, as
    ``FlightRecorder.programs`` holds it)."""
    from jax.profiler import ProfileData
    ex = T.extract(trace_dir)
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    pd = ProfileData.from_file(paths[-1])
    ex["host"] = [h for h in ex["host"] if h[0].startswith("bench.")]
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                ex["host"].extend([e.name, e.start_ns, e.duration_ns]
                                  for e in line.events
                                  if e.name in PROGRAM_SPANS)
    ex["op_scopes"] = {
        m[0]: programs[m[0].split("(")[0]]
        for dev in ex["devices"].values() for m in dev["modules"]
        if m[0].split("(")[0] in programs}
    return ex


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def scoped_ops(dev, op_scopes, module: str, match):
    """The ops of every module event whose base name is ``module`` whose
    scope path ``match`` accepts."""
    find = module_index(dev["modules"])
    out = []
    for e in dev["ops"]:
        mod = find(e[1])
        if mod is None or mod.split("(")[0] != module:
            continue
        path = op_scopes.get(mod, {}).get(e[0])
        if path is not None and match(path):
            out.append(e)
    return out


def scope_ns(dev, op_scopes, module, lo, hi, scopes) -> float:
    """Device time in [lo, hi] of the union of the module's ops under any
    of ``scopes``."""
    ops = scoped_ops(dev, op_scopes, module,
                     lambda p: any(in_scope(p, s) for s in scopes))
    return T.busy_ns(ops, lo, hi)


def scope_ms_per_epoch(ctx, scopes):
    """Milliseconds per traced epoch of the cell's epoch module under any
    of ``scopes``, averaged over the chips; None without scope maps."""
    ex, module = ctx["trace"], ctx["spec"].get("epoch_module")
    if not ex.get("op_scopes") or not module or not ctx["epochs"]:
        return None
    lo, hi = ctx["window"]
    ns = T.mean_over_devices(ex, lambda d: scope_ns(
        d, ex["op_scopes"], module, lo, hi, scopes))
    return ns * 1e-6 / ctx["epochs"] if ns else None


def spans_in(host, name, lo, hi):
    """Durations (ns) of the host spans named ``name`` inside [lo, hi]."""
    return [d for n, t, d in host if n == name and lo <= t and t + d <= hi]


def span_reading(ctx, name, reduce):
    """``reduce`` over the durations (ms) of the ``name`` spans in the
    window; None when the program records no fit-driver spans."""
    lo, hi = ctx["window"]
    host = ctx["trace"]["host"]
    if not spans_in(host, "restack", lo, hi):
        return None
    return reduce([d * 1e-6 for d in spans_in(host, name, lo, hi)])


def mean_or_none(v):
    return sum(v) / len(v) if v else None


def median_or_none(v):
    return statistics.median(v) if v else None


def idle_by_span(ops, host, lo, hi, k: int = 10):
    """[span, seconds] of the device's idle time in [lo, hi], each piece
    of every gap put down to the innermost (shortest) host span covering
    it, "no span" where none does; the k largest."""
    tot = {}
    for g0, g1 in T.idle_gaps(ops, lo, hi):
        cover = [(d, n, t, t + d) for n, t, d in host
                 if t < g1 and t + d > g0]
        cuts = sorted({g0, g1} | {x for _d, _n, s, e in cover
                                  for x in (s, e) if g0 < x < g1})
        for a, b in zip(cuts[:-1], cuts[1:]):
            mid = (a + b) / 2
            inner = min(((d, n) for d, n, s, e in cover if s <= mid <= e),
                        default=(0, "no span"))
            tot[inner[1]] = tot.get(inner[1], 0.0) + (b - a) * 1e-9
    return sorted(([n, v] for n, v in tot.items()),
                  key=lambda kv: -kv[1])[:k]


def by_scope_ms(dev, op_scopes, module, lo, hi, epochs: int) -> dict:
    """Device ms per epoch of the module's ops under each scope (inclusive:
    ``eq7_score`` is part of ``policy_round``), and ``unscoped``: busy
    time in which no scoped op runs."""
    out = {s: scope_ns(dev, op_scopes, module, lo, hi, (s,)) * 1e-6 / epochs
           for s in SCOPES}
    busy = T.busy_ns(dev["ops"], lo, hi)
    scoped = scope_ns(dev, op_scopes, module, lo, hi, TOP_SCOPES)
    out["unscoped"] = (busy - scoped) * 1e-6 / epochs
    return out
