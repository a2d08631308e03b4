#!/usr/bin/env python3
"""Bring-up smoke: the federated trainer on a TPU, through its public API.

  python chip_smoke.py              # phases a-c on one chip
  python chip_smoke.py --chips 4    # only the client-sharded mesh path

Phases (one process; no child processes):

  a. batched engine: a cross-silo population of 32 synthetic hospitals at
     Table-4 widths (``HFLConfig`` defaults: w=3, R=50, heads
     16-256-64-16-1) under ``mode="always"`` — one fused dispatch per
     epoch, federated rounds counted, every val/test MSE finite.
  b. reference: the sequential oracle and the batched engine on 4
     hospitals — identical selections and round counts, val MSEs close.
  c. compiled pool kernel: ``pool_mlp_errors_features`` against
     ``hfl.pool_errors`` on chip-resident Table-4 pools, the lowered
     program holding a ``tpu_custom_call`` (compiled, not interpreted);
     then phase a's fit with ``use_pool_kernel=True`` — round counts equal
     to phase a's, val MSEs close, at most 1% of selections differing.

``--chips 4`` runs only the mesh phase: ``Federation(..., mesh=make_mesh())``
over four chips at C=32 and ``exchange_every`` 1 and 2 against the
single-device engine on the same population — identical selections, the
comms counters equal to their analytic values, the client state
partitioned over all four chips, and live bytes equal on every chip.

Each phase prints one line.  Without a TPU the script exits non-zero
before any phase.  The last line of standard output is one JSON object,
printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
import traceback
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

# population lengths are not multiples of R; the engine warns about the
# dropped tail every fit, which is expected here
warnings.filterwarnings("ignore", message=r"RoundSchedule\(R=")

# the bring-up size: a cross-silo population at Table-4 widths, with few
# enough events that host-side data generation stays a few seconds
CLIENTS, EPOCHS, PATIENTS, EVENTS, SEED = 32, 3, 20, 300, 0

# val MSEs of two engines agree to float32 re-association, as in the CPU
# parity tests (tests/test_fused_epoch.py); selections must be identical
VAL_RTOL, VAL_ATOL = 1e-5, 1e-6
# the compiled kernel vs the vmap oracle on the same chip, as in
# tests/test_kernels.py
ERR_RTOL, ERR_ATOL = 1e-5, 1e-6
# The kernel and the vmap path score within ~4e-7 of each other, which is
# enough to flip an Eq.-7 near-tie in a long fit (2 of 288 selection
# events on a v5e at the sizes above).  A flipped near-tie moved val MSEs
# by 1.2e-6 relative, so the kernel fit is held to phase a's val MSEs at
# this tolerance, with at most this share of selection events differing.
FIT_RTOL = 1e-5
FIT_MAX_FLIPS = 0.01
# during a mesh fit every chip holds the same share of the partitioned
# state and a full copy of the replicated pool; live bytes per chip may
# differ by at most this share of the largest
PLACEMENT_SPREAD = 0.01


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def all_finite(hist):
    import numpy as np
    vals = [v for h in hist.values() for v in h["val"]]
    tests = [h["test"] for h in hist.values()]
    return bool(np.all(np.isfinite(vals)) and np.all(np.isfinite(tests)))


def same_selections(h1, h2):
    return all(h1[n]["selections"] == h2[n]["selections"]
               and h1[n]["rounds"] == h2[n]["rounds"] for n in h1)


def max_val_gap(h1, h2, rtol=VAL_RTOL, atol=VAL_ATOL):
    """Largest |a - b| / (atol + rtol |b|) over every val MSE — <= 1 means
    ``np.allclose(a, b, rtol, atol)`` holds."""
    import numpy as np
    a = np.concatenate([np.asarray(h1[n]["val"]) for n in h1])
    b = np.concatenate([np.asarray(h2[n]["val"]) for n in h1])
    return float(np.max(np.abs(a - b) / (atol + rtol * np.abs(b))))


def selection_diffs(h1, h2):
    """(selection events that differ, events) between two histories."""
    pairs = [(a, b) for n in h1
             for a, b in zip(h1[n]["selections"], h2[n]["selections"])]
    return sum(a != b for a, b in pairs), len(pairs)


def total_rounds(hist):
    return sum(h["rounds"] for h in hist.values())


def population(n, cfg):
    from repro.core.experiment import population_clients
    clients, _ = population_clients(n, cfg, seed=SEED, n_patients=PATIENTS,
                                    n_events=EVENTS)
    return clients


def timed_fit(fed, epochs):
    """fit() with the first epoch (compile + run) timed apart from the
    rest; returns (history, first-epoch s, mean steady epoch s)."""
    t0 = time.perf_counter()
    fed.fit(epochs=1)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    hist = fed.fit(epochs=epochs - 1)
    steady = (time.perf_counter() - t0) / max(epochs - 1, 1)
    return hist, first, steady


def fused_fit(cfg):
    """Phases a and c: the batched engine on the cross-silo population."""
    from repro.core.federation import Federation
    fed = Federation(population(CLIENTS, cfg), cfg, engine="batched")
    hist, first, steady = timed_fit(fed, EPOCHS)
    st = fed.dispatch_stats
    check(st["path"] == "fused" and st["dispatches_per_epoch"] == 1.0,
          f"expected one fused dispatch per epoch, got {st}")
    check(total_rounds(hist) > 0, "no federated round was counted")
    check(all_finite(hist), "a val or test MSE is not finite")
    return hist, (f"C={CLIENTS} nf={fed.clients[0].nf} "
                  f"epochs={EPOCHS} rounds={total_rounds(hist)} "
                  f"dispatches/epoch={st['dispatches_per_epoch']} "
                  f"first-epoch {first:.2f}s steady-epoch {steady:.3f}s "
                  f"(information only)")


def phase_a(state):
    from repro.core.hfl import HFLConfig
    cfg = HFLConfig(epochs=EPOCHS, mode="always")
    state["hist_a"], line = fused_fit(cfg)
    return line


def phase_b(state):
    from repro.core.federation import Federation
    from repro.core.hfl import HFLConfig
    cfg = HFLConfig(epochs=2, mode="always")
    h_seq = Federation(population(4, cfg), cfg, engine="sequential").fit()
    h_bat = Federation(population(4, cfg), cfg, engine="batched").fit()
    check(total_rounds(h_seq) > 0, "the oracle ran no federated round")
    check(same_selections(h_seq, h_bat),
          "batched selections differ from the sequential oracle's")
    gap = max_val_gap(h_bat, h_seq)
    check(gap <= 1.0, f"val MSEs differ beyond rtol={VAL_RTOL} "
                      f"atol={VAL_ATOL} (gap {gap:.3g})")
    return (f"C=4 epochs=2 rounds={total_rounds(h_seq)} selections "
            f"identical, val gap {gap:.3g} of (rtol={VAL_RTOL}, "
            f"atol={VAL_ATOL})")


def phase_c(state):
    import jax
    import numpy as np
    from repro.core import networks as N
    from repro.core.hfl import HFLConfig, pool_errors
    from repro.kernels.pool_mlp.ops import pool_mlp_errors_features
    from repro.sharding import spec as S

    # the sweep itself, on chip-resident pools at Table-4 widths: a full
    # pool (ns = C * nf) and a ragged one
    cfg = HFLConfig(epochs=EPOCHS, mode="always", use_pool_kernel=True)
    nf = 4
    key = jax.random.PRNGKey(SEED)
    worst = 0.0
    for ns in (CLIENTS * nf, CLIENTS * nf - 3):
        keys = jax.random.split(key, ns + 2)
        pool = jax.vmap(lambda k: S.materialize(N.head_schema(cfg.w), k))(
            keys[:ns])
        xd = jax.random.normal(keys[ns], (nf, cfg.R, cfg.w))
        y = jax.random.normal(keys[ns + 1], (cfg.R,))
        lowered = pool_mlp_errors_features.lower(pool, xd, y)
        check("tpu_custom_call" in lowered.as_text(),
              "pool_mlp_errors_features lowered without a tpu_custom_call "
              "(the interpreter would run)")
        got = np.asarray(pool_mlp_errors_features(pool, xd, y))
        ref = np.asarray(jax.vmap(lambda x: pool_errors(pool, x, y))(xd))
        check(got.shape == (nf, ns), f"kernel output {got.shape}")
        np.testing.assert_allclose(got, ref, rtol=ERR_RTOL, atol=ERR_ATOL)
        check((got.argmin(1) == ref.argmin(1)).all(),
              "kernel argmin differs from the vmap oracle's")
        worst = max(worst, float(np.max(np.abs(got - ref))))

    hist, line = fused_fit(cfg)
    hist_a = state["hist_a"]
    check(all(hist[n]["rounds"] == hist_a[n]["rounds"] for n in hist),
          "kernel-path round counts differ from phase a's")
    gap = max_val_gap(hist, hist_a, rtol=FIT_RTOL)
    check(gap <= 1.0, f"kernel-path val MSEs differ from phase a's beyond "
                      f"rtol={FIT_RTOL} (gap {gap:.3g})")
    diff, events = selection_diffs(hist, hist_a)
    check(diff <= FIT_MAX_FLIPS * events,
          f"{diff}/{events} kernel-path selection events differ from phase "
          f"a's, more than {FIT_MAX_FLIPS:.0%}")
    return (f"kernel compiled (tpu_custom_call), max |kernel - vmap| "
            f"{worst:.3g} at ns={CLIENTS * nf} and {CLIENTS * nf - 3}; "
            f"fit {line}; vs phase a: {diff}/{events} selection events "
            f"differ (near-ties, at most {FIT_MAX_FLIPS:.0%}), val gap "
            f"{gap:.3g} of (rtol={FIT_RTOL}, atol={VAL_ATOL})")


def _placement_probe(n_dev):
    """An ``on_epoch_end`` callback checking, while the fit's state is
    live, that every client-partitioned array is split over all chips (one
    shard per chip, each holding 1/n_dev of the client axis) and that no
    chip holds more live bytes than another beyond ``PLACEMENT_SPREAD`` of
    the largest: an array left whole on one chip fails the second check."""
    import jax
    from jax.sharding import NamedSharding
    from repro.core.federation import Callback

    class Probe(Callback):
        def __init__(self):
            self.seen = 0
            self.live_bytes = []
            self.bytes_in_use = []

        def on_epoch_end(self, fed, epoch, val, active):
            per_dev = collections.Counter()
            for a in jax.live_arrays():
                for s in a.addressable_shards:
                    per_dev[s.device] += s.data.nbytes
                sh = a.sharding
                if not (isinstance(sh, NamedSharding)
                        and "clients" in tuple(sh.spec)):
                    continue
                ax = tuple(sh.spec).index("clients")
                shards = a.addressable_shards
                devs = {s.device for s in shards}
                check(len(devs) == n_dev and all(
                    s.data.shape[ax] * n_dev == a.shape[ax]
                    for s in shards),
                      f"a client-partitioned {a.shape} array is not split "
                      f"over {n_dev} chips: {sorted(map(str, devs))}")
                self.seen += 1
            self.live_bytes = [per_dev[d] for d in jax.devices()]
            hi, lo = max(self.live_bytes), min(self.live_bytes)
            check(hi - lo <= PLACEMENT_SPREAD * hi,
                  f"live bytes per chip {self.live_bytes} differ by more "
                  f"than {PLACEMENT_SPREAD:.0%}: an array sits whole on "
                  f"one chip")
            self.bytes_in_use = [(d.memory_stats() or {}).get(
                "bytes_in_use", 0) for d in jax.devices()]

    return Probe()


def phase_mesh(state):
    import gc
    import jax
    from repro.core import networks as N
    from repro.core.federation import Federation, RoundSchedule
    from repro.core.hfl import HFLConfig
    from repro.core.mesh_federation import make_mesh
    from repro.sharding import spec as S

    cfg = HFLConfig(epochs=EPOCHS, mode="always")
    D, C = len(jax.devices()), CLIENTS
    parts = []
    for k in (1, 2):
        sched = RoundSchedule(cfg.epochs, cfg.R, exchange_every=k)
        h1 = Federation(population(C, cfg), cfg, engine="batched",
                        schedule=sched).fit()
        # the reference's arrays are garbage now (a fit leaves a cycle
        # through its sync hook); free them before the probe counts bytes
        gc.collect()
        probe = _placement_probe(D)
        fed = Federation(population(C, cfg), cfg, engine="batched",
                         schedule=sched, mesh=make_mesh(), callbacks=[probe])
        t0 = time.perf_counter()
        hD = fed.fit()
        wall = time.perf_counter() - t0
        st = fed.dispatch_stats
        check(st["devices"] == D, f"mesh fit ran on {st['devices']} devices")
        check(probe.seen > 0, "no client-partitioned array was live")
        check(same_selections(h1, hD),
              f"mesh selections differ from the single-device engine's "
              f"at exchange_every={k}")
        gap = max_val_gap(hD, h1)
        check(gap <= 1.0, f"mesh val MSEs differ at exchange_every={k} "
                          f"(gap {gap:.3g})")
        # the analytic comms: every epoch federates (mode="always") and
        # runs n_sub // k exchange rounds; each moves the replicated pool
        # of C*nf heads, the (C, R) probe batches, and the sharded argmin's
        # two (D, nf) pairs per client
        nf = fed.clients[0].nf
        n_sub = len(fed.clients[0].train[2]) // cfg.R
        rounds = cfg.epochs * (n_sub // k)
        head = S.count_params(N.head_schema(cfg.w))
        per_round = (C * nf * head * 4 + C * cfg.R * (nf * cfg.w + 1) * 4
                     + C * D * nf * 8)
        check(st["exchange_rounds"] == rounds,
              f"exchange_rounds {st['exchange_rounds']} != {rounds}")
        check(st["pool_bytes_gathered"] == rounds * per_round,
              f"pool_bytes_gathered {st['pool_bytes_gathered']} != "
              f"{rounds * per_round}")
        parts.append(f"k={k}: exchange_rounds={rounds} "
                     f"pool_bytes_gathered={rounds * per_round} "
                     f"rounds={total_rounds(hD)} val gap {gap:.3g} "
                     f"fit {wall:.2f}s, {probe.seen} client-split arrays "
                     f"checked, live bytes per chip {probe.live_bytes}, "
                     f"HBM bytes in use per chip {probe.bytes_in_use}")
        del fed
        gc.collect()
    return (f"C={C} on {D} chips, selections identical to 1 device; "
            + "; ".join(parts))


def compile_counter():
    """Count backend compilations (their seconds include persistent-cache
    reads) and persistent-cache hits and misses, through jax.monitoring."""
    import jax
    tally = collections.Counter()

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tally["programs"] += 1
            tally["seconds"] += duration

    def on_event(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            tally[event.rsplit("_", 1)[-1]] += 1     # hits / misses

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return tally


def run(phases):
    state, failed = {}, []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            line = fn(state)
        except Exception as e:   # report, go on, fail at the end
            traceback.print_exc()
            print(f"[{name}] FAIL {type(e).__name__}: {e}", flush=True)
            failed.append(name)
        else:
            print(f"[{name}] PASS {line} "
                  f"[{time.perf_counter() - t0:.1f}s]", flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-c on one chip; 4: only the "
                         "client-sharded mesh path over four chips")
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU found: JAX runs on {dev.platform} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}"
          f", compile cache {cache}", flush=True)

    phases = ([("mesh", phase_mesh)] if args.chips == 4 else
              [("a", phase_a), ("b", phase_b), ("c", phase_c)])
    tally = compile_counter()
    t0 = time.perf_counter()
    failed = run(phases)
    print(f"total {time.perf_counter() - t0:.1f}s; compile "
          f"{tally['seconds']:.1f}s over {tally['programs']} programs, "
          f"persistent cache {tally['hits']} hits / {tally['misses']} "
          f"misses", flush=True)
    if failed:
        print(f"failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
