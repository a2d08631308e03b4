#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python perfbench/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --first-seed <n>

For each program seed: the cell's set-up as a run makes it (population,
weights, batched ``Federation``, the warm-up epochs) and the same
comparison with the reference.  For each control seed: the control, the
reference computed in bfloat16 at the default precision (the next
precision below the configuration's), put in the program's place, making
its own Eq.-7 choices, and compared in the same way.  With
``--fault-seeds n``, first each fault of ``faults.py`` planted in the
program, on n seeds below the first.  Prints one JSON line
per seed and, last, the largest program reading and the smallest control
reading of each number.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as RUN


def program_reading(cell: dict, seed: int, devices) -> dict:
    import jax
    import compare
    import reference
    exchange = cell["traffic"]["mode"] != "no"
    fed, _marks, sites, params0, _nfs = RUN.build(cell, seed, False,
                                                  devices)
    hist = fed.fit(epochs=RUN.WARMUP_EPOCHS)
    first = RUN.snapshot(fed, hist)
    prog = jax.device_get({k: first[k] for k in ("params", "m")})
    prog.update({k: first[k] for k in ("val", "test")})
    forced = first["selections"] if exchange else None
    del fed, first, hist
    gc.collect()
    ref = reference.run_epochs(sites, params0, cell["config"], exchange,
                               RUN.WARMUP_EPOCHS, forced=forced)
    return {**compare.numbers(prog, ref, params0, exchange),
            "worst": compare.worst(prog, ref, params0, exchange)}


def control_reading(cell: dict, seed: int) -> dict:
    import compare
    import population as P
    import reference
    conf = cell["config"]
    exchange = cell["traffic"]["mode"] != "no"
    choices = conf["nf_choices"]
    nfs = [choices[h % len(choices)] for h in range(conf["sites"])]
    sites = P.make_sites(seed, nfs, conf["patients_per_site"],
                         conf["events_per_patient"], conf["w"],
                         conf["split_lengths"])
    _, params0 = P.init_weights(seed, nfs, conf)
    n = RUN.WARMUP_EPOCHS
    ctl = reference.run_epochs(sites, params0, conf, exchange, n,
                               mode="control")
    ref = reference.run_epochs(sites, params0, conf, exchange, n,
                               forced=ctl["choices"] if exchange else None)
    return {**compare.numbers(ctl, ref, params0, exchange),
            "worst": compare.worst(ctl, ref, params0, exchange)}


def readings(cell: dict, seeds, control_seeds, devices, log=print,
             fault_seeds=(), fault_names=None) -> dict:
    import faults
    for name in (fault_names or faults.NAMES) if fault_seeds else ():
        with faults.planted(name):
            for s in fault_seeds:
                t = time.perf_counter()
                r = program_reading(cell, s, devices)
                log(json.dumps({"fault": name, "seed": s,
                                "s": time.perf_counter() - t, **r}))
    prog, ctl = [], []
    for s in seeds:
        t = time.perf_counter()
        prog.append(program_reading(cell, s, devices))
        log(json.dumps({"program": s, "s": time.perf_counter() - t,
                        **prog[-1]}))
    for s in control_seeds:
        t = time.perf_counter()
        ctl.append(control_reading(cell, s))
        log(json.dumps({"control": s, "s": time.perf_counter() - t,
                        **ctl[-1]}))
    if not prog and not ctl:
        return {}
    names = [k for k in (prog[0] if prog else ctl[0]) if k != "worst"]
    return {k: {"program_max": max((p[k] for p in prog), default=None),
                "control_min": min((c[k] for c in ctl), default=None)}
            for k in names}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=0,
                    help="also read each planted fault (faults.py) on "
                         "this many seeds")
    ap.add_argument("--faults", default="",
                    help="comma-separated names of faults.py's faults to "
                         "read (default: all)")
    ap.add_argument("--first-seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = RUN.M.cell(RUN.M.load(), args.workload)
    RUN.enable_compile_cache()
    try:
        devices = RUN.require_chips(cell["chips"])
    except RUN.NoChip as e:
        print(f"{e}; nothing was run", file=sys.stderr)
        return 1
    s0 = args.first_seed
    out = readings(cell, range(s0, s0 + args.seeds),
                   range(s0 + args.seeds, s0 + args.seeds
                         + args.control_seeds), devices,
                   log=lambda s: print(s, flush=True),
                   fault_seeds=range(s0 - args.fault_seeds, s0),
                   fault_names=[f for f in args.faults.split(",") if f])
    print(json.dumps({"workload": args.workload, "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
