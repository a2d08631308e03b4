#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator JAX finds.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the cell's population and weights from ``--seed``, wraps
them in a batched ``Federation`` and warms it up with a 2-epoch ``fit()``,
which compiles every program the window uses; those first epochs are also
what ``correct`` judges against the plain reference (``reference.py``).
The window then runs back-to-back ``fit(epochs=E)`` calls on the same
``Federation`` and stops starting fits once ``--seconds`` have passed.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
one ``fit(epochs=E)`` with the profiler and reports the per-layer metrics,
each read by ``metrics/<name>.py``.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``compared``,
each compared number beside its limit.  Without a TPU, or with fewer
chips than the cell asks for, the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import warnings  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import manifest as M  # noqa: E402

# population lengths are multiples of R, but keep the program's warning
# about dropped tails out of the output all the same
warnings.filterwarnings("ignore", message=r"RoundSchedule\(R=")
CACHE_DIR = ROOT / ".jax_cache"
# the warm-up fit's epochs: the second is the first that carries Adam's
# state, the pool and save-best over from an earlier epoch
WARMUP_EPOCHS = 2


class NoChip(RuntimeError):
    pass


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else a fixed directory in the checkout; every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int):
    """The first ``n`` TPU devices, or NoChip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs


def load_peaks(kind: str) -> dict:
    with open(HERE / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


class CompileTally:
    """Backend compiles (persistent-cache reads included) and jaxpr
    traces, counted through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.n = collections.Counter()

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n["compiles"] += 1
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.n["traces"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


class GcTally:
    """Python's garbage collections while on: count and longest pause per
    generation."""

    def __init__(self):
        self.n = collections.Counter()
        self.longest = collections.defaultdict(float)
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            g = info["generation"]
            self.n[g] += 1
            self.longest[g] = max(self.longest[g],
                                  time.perf_counter() - self._t)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def _epoch_marks():
    from repro.core.federation import Callback

    class EpochMarks(Callback):
        """Host time of every epoch end; overrides only ``on_epoch_end``,
        so the fused one-dispatch-per-epoch path stays on."""

        def __init__(self):
            self.times = []

        def on_epoch_end(self, fed, epoch, val, active):
            self.times.append(time.perf_counter())

    return EpochMarks()


def build(cell: dict, seed: int, traced: bool, devices):
    """The population, its weights and the warm ``Federation``."""
    import jax
    import population as P
    from repro.core.federation import Federation, RoundSchedule
    from repro.core.hfl import FederatedClient, HFLConfig
    from repro.core.telemetry import TelemetryPlan

    conf, traffic = cell["config"], cell["traffic"]
    choices = conf["nf_choices"]
    nfs = [choices[h % len(choices)] for h in range(conf["sites"])]
    sites = P.make_sites(seed, nfs, conf["patients_per_site"],
                         conf["events_per_patient"], conf["w"],
                         conf["split_lengths"])
    weights, params0 = P.init_weights(seed, nfs, conf)
    cfg = HFLConfig(w=conf["w"], R=conf["R"], alpha=conf["alpha"],
                    lr=conf["lr"], epochs=conf["epochs"],
                    mode=traffic["mode"], seed=seed & 0x7FFFFFFF)
    clients = []
    for h, (s, p) in enumerate(zip(sites, weights)):
        c = FederatedClient(f"h{h:03d}", nfs[h], cfg, s["train"],
                            s["valid"], s["test"], jax.random.PRNGKey(h))
        c.params, c.best_params = p, p
        c.opt_state = c.opt.init(p)
        clients.append(c)
    mesh = None
    if cell["chips"] > 1:
        from repro.core.mesh_federation import make_mesh
        mesh = make_mesh(devices=devices[:cell["chips"]])
    marks = _epoch_marks()
    tele = (TelemetryPlan(rounds=False, spans=True, profile=True)
            if traced else None)
    fed = Federation(clients, cfg, engine="batched",
                     schedule=RoundSchedule(conf["epochs"], conf["R"],
                                            traffic["exchange_every"]),
                     callbacks=[marks], mesh=mesh, telemetry=tele)
    return fed, marks, sites, params0, nfs


def snapshot(fed, hist) -> dict:
    """What the warm-up fit produced, as the clients and results hold it."""
    names = [c.name for c in fed.clients]
    return {"params": [c.params for c in fed.clients],
            "m": [c.opt_state["m"] for c in fed.clients],
            "val": [hist[n]["val"] for n in names],
            "test": [hist[n]["test"] for n in names],
            "selections": [hist[n]["selections"] for n in names]}


def work_counts(cell: dict, nfs, epochs: int, fits: int) -> dict:
    import counts as K
    import population as P
    conf, traffic = cell["config"], cell["traffic"]
    R = conf["R"]
    lengths = conf["split_lengths"]
    n_sub = lengths["train"] // R
    exchange = traffic["mode"] != "no"
    n_exch = n_sub // traffic["exchange_every"] if exchange else 0
    nets_of = lambda nf: P.mlp_dims(conf, nf)
    return {
        "flops": epochs * K.epoch_flops(nets_of, nfs, R, n_sub, n_exch,
                                        lengths["valid"])
        + fits * K.test_flops(nets_of, nfs, lengths["test"]),
        "client_rounds_per_epoch": len(nfs) * n_sub,
    }


def _load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def p95(values) -> float:
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, devices,
             *, t_proc: float = T_PROC, log=print) -> dict:
    """Set-up, window, reference; returns the result line's object."""
    import jax
    import numpy as np
    import compare
    import devtrace
    import reference

    kind = devices[0].device_kind
    peak = load_peaks(kind) if devices[0].platform == "tpu" else None
    conf, traffic = cell["config"], cell["traffic"]
    E = conf["epochs"]
    exchange = traffic["mode"] != "no"
    tally = CompileTally()
    fed, marks, sites, params0, nfs = build(cell, seed, traced, devices)
    hist = fed.fit(epochs=WARMUP_EPOCHS)
    first = snapshot(fed, hist)
    stats = fed.dispatch_stats
    if stats["dispatches_per_epoch"] != 1.0:
        log(f"warning: {stats['dispatches_per_epoch']} dispatches per "
            f"epoch, not one fused dispatch")
    marks.times.clear()
    before = dict(tally.n)
    setup_s = time.perf_counter() - t_proc

    fit_ends = []
    tdir = None
    with GcTally() as gct:
        t0 = time.perf_counter()
        if traced:
            tdir = tempfile.mkdtemp(prefix="perfbench-trace-")
            jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation("bench.fit"):
                fed.fit(epochs=E)
            fit_ends.append(time.perf_counter())
            jax.profiler.stop_trace()
        else:
            while True:
                fed.fit(epochs=E)
                fit_ends.append(time.perf_counter())
                if fit_ends[-1] - t0 >= seconds:
                    break
        t1 = fit_ends[-1]
    in_window = {k: tally.n[k] - before.get(k, 0) for k in
                 ("compiles", "traces")}
    fits, epochs = len(fit_ends), len(marks.times)
    ends = list(marks.times)
    pts = [t0] + ends
    pts[-1] = t1
    intervals = [b - a for a, b in zip(pts[:-1], pts[1:])]
    fit_sync = [fit_ends[k] - ends[(k + 1) * E - 1] for k in range(fits)]
    vals = np.asarray([c.val_history[WARMUP_EPOCHS:] for c in fed.clients])
    finite_epochs = int(np.isfinite(vals).all(axis=0).sum())
    work = work_counts(cell, nfs, epochs, fits)
    log(f"window: {fits} fits of {E} epochs, {epochs} epochs, "
        f"{len(intervals)} epoch intervals (median "
        f"{1e3 * statistics.median(intervals):.3f} ms, p95 "
        f"{1e3 * p95(intervals):.3f} ms), {t1 - t0:.3f} s")
    top = sorted(range(len(intervals)), key=intervals.__getitem__)[::-1]
    log("largest epoch intervals (index: ms; a fit's first is index "
        f"k*{E}): " + ", ".join(f"{i}: {1e3 * intervals[i]:.3f}"
                                for i in top[:12]))
    log("garbage collections in the window (generation: count, longest "
        "ms): " + ", ".join(f"{g}: {gct.n[g]}, {1e3 * gct.longest[g]:.3f}"
                            for g in sorted(gct.n)))
    log(f"compiles in the window: {in_window['compiles']} backend "
        f"compiles, {in_window['traces']} traces (expected 0)")
    log("dispatch_stats: " + json.dumps(fed.dispatch_stats, default=str))
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices[:cell["chips"]]]
    log(f"peak HBM bytes per chip: {mem}")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": max(mem)}

    metrics, breakdown = {}, None
    if not traced:
        rate = work["client_rounds_per_epoch"] * epochs / (t1 - t0)
        values = {"client_rounds_per_s": rate, "setup_s": setup_s}
        for m in cell["e2e"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ex = devtrace.extract(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = devtrace.window(ex)
        ctx = {"trace": ex, "window": (lo, hi), "fit_sync_s": fit_sync,
               "epoch_intervals_s": intervals,
               "spec": cell["spec"], "epochs": epochs, "fits": fits,
               "chips": cell["chips"], "peak": peak, "work": work}
        for m in cell["per_layer"]:
            v = _load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        devs = list(ex["devices"].values())
        busy = devtrace.mean_over_devices(
            ex, lambda d: devtrace.busy_ns(d["ops"], lo, hi))
        device.update(busy_s=busy * 1e-9, window_s=(hi - lo) * 1e-9)
        gaps = devtrace.idle_gaps(devs[0]["ops"], lo, hi)[:10] if devs \
            else []
        breakdown = {
            "device_ops": devtrace.top_ops(devs[0]["ops"], lo, hi)
            if devs else [],
            "idle_gaps": [[devtrace.label(g, ex["host"]), (g[1] - g[0]) * 1e-9]
                          for g in gaps]}
        del ex, ctx

    # the reference, once the window has closed and the program's state is
    # freed: the warm-up epochs from the same weights and data
    prog = jax.device_get({k: first[k] for k in ("params", "m")})
    prog.update({k: first[k] for k in ("val", "test")})
    forced = first["selections"] if exchange else None
    del fed, first, hist
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.run_epochs(sites, params0, conf, exchange, WARMUP_EPOCHS,
                               forced=forced)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    nums = compare.numbers(prog, ref, params0, exchange)
    log("worst client, leaf or choice (not judged): "
        + json.dumps(compare.worst(prog, ref, params0, exchange)))
    limits = cell["spec"]["limits"]
    out = {"correct": compare.judge(nums, limits),
           "attempted": epochs, "failed": epochs - finite_epochs,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compare.report(nums, limits)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = M.cell(M.load(), args.workload)
    cache = enable_compile_cache()
    try:
        devices = require_chips(cell["chips"])
    except NoChip as e:
        print(f"{e}; nothing was run", file=sys.stderr)
        return 1
    print(f"cell {cell['name']} seed {args.seed} on {devices[0].device_kind}"
          f" x{len(devices)}, compile cache {cache}", flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   log=lambda s: print(s, flush=True))
    for k, v in out["compared"].items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
