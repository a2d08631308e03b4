"""Paper Tables 5 / 6 / 7 on the simulated MIMIC-III (see DESIGN.md §7).

Absolute MSEs are not comparable to the paper (different data — the real
MIMIC-III sits behind a PhysioNet DUA); the CLAIMS under validation are the
paper's orderings:
  T5: HFL ranks best on (most of) the small target domain's tasks,
  T6: HFL stays competitive when the domains swap,
  T7: ablation ordering — selection beats random, switch beats always-on.

Protocol mirrors §5.2 (Adam lr 0.01, batch = R periods, save-best) with a
reduced default budget for the CPU container; REPRO_BENCH_FULL=1 restores
50 epochs / full patient counts / 5 seeds.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path

import numpy as np

from repro.core.experiment import run_task, train_hfl
from repro.core.hfl import HFLConfig

OUT = Path(__file__).resolve().parent.parent / "experiments" / "paper"

FULL = bool(int(os.environ.get("REPRO_BENCH_FULL", "0")))
# 50 epochs is NOT negotiable: the Table-4 heads pass through two sigmoid
# layers and only become load-bearing late in training — below ~30 epochs the
# blend provably cannot influence the final prediction (see EXPERIMENTS.md
# §Repro "Budget sensitivity").  FULL additionally restores paper-scaled
# patient counts and 5 seeds.
EPOCHS = 50
N_PATIENTS = None if FULL else 24      # None -> paper-scaled counts
N_EVENTS = 400 if FULL else 220
SEEDS = (0, 1, 2, 3, 4) if FULL else (0,)
LABELS = (0, 1, 2, 3, 4)


def _cfg(mode="hfl"):
    return HFLConfig(epochs=EPOCHS, mode=mode)


def _avg(runs, key):
    return float(np.mean([r[key] for r in runs]))


def table5_prediction(labels=LABELS):
    """Target = metavision (smaller domain), systems DNN/BIBE/BIBEP/HFL."""
    rows = []
    for lbl in labels:
        per_sys = {}
        for system in ("dnn", "bibe", "bibep", "hfl"):
            runs = [run_task("metavision", lbl, [system], _cfg(), seed=s,
                             n_patients=N_PATIENTS, n_events=N_EVENTS)[system]
                    for s in SEEDS]
            per_sys[system] = {"valid": _avg(runs, "valid"),
                               "test": _avg(runs, "test")}
        ranks = sorted(per_sys, key=lambda s: per_sys[s]["test"])
        rows.append({"label": f"MF{lbl + 1}", **{
            s: per_sys[s] for s in per_sys}, "best": ranks[0]})
    return {"table": "5_prediction", "target": "metavision", "rows": rows,
            "protocol": {"epochs": EPOCHS, "seeds": len(SEEDS), "full": FULL}}


def table6_robustness(labels=LABELS):
    """Domains swapped: target = carevue."""
    rows = []
    for lbl in labels:
        per_sys = {}
        for system in ("dnn", "bibe", "bibep", "hfl"):
            runs = [run_task("carevue", lbl, [system], _cfg(), seed=s,
                             n_patients=N_PATIENTS, n_events=N_EVENTS)[system]
                    for s in SEEDS]
            per_sys[system] = {"valid": _avg(runs, "valid"),
                               "test": _avg(runs, "test")}
        ranks = sorted(per_sys, key=lambda s: per_sys[s]["test"])
        rows.append({"label": f"CF{lbl + 1}", **per_sys, "best": ranks[0]})
    return {"table": "6_robustness", "target": "carevue", "rows": rows,
            "protocol": {"epochs": EPOCHS, "seeds": len(SEEDS), "full": FULL}}


def table7_ablation(labels=LABELS):
    """HFL-No / HFL-Random / HFL-Always / HFL on both hospitals."""
    rows = []
    for target in ("carevue", "metavision"):
        for lbl in labels:
            per_mode = {}
            for mode in ("no", "random", "always", "hfl"):
                runs = [train_hfl(target, lbl, _cfg(mode), seed=s,
                                  n_patients=N_PATIENTS, n_events=N_EVENTS)
                        for s in SEEDS]
                per_mode[mode] = {"test": _avg(runs, "test"),
                                  "rounds": _avg(runs, "rounds")}
            prefix = "CF" if target == "carevue" else "MF"
            rows.append({"label": f"{prefix}{lbl + 1}", "target": target,
                         **per_mode,
                         "best": min(per_mode, key=lambda m:
                                     per_mode[m]["test"])})
    return {"table": "7_ablation", "rows": rows,
            "protocol": {"epochs": EPOCHS, "seeds": len(SEEDS), "full": FULL}}


def run_all(labels=LABELS, tables=("5", "6", "7")):
    OUT.mkdir(parents=True, exist_ok=True)
    results = {}
    fns = {"5": table5_prediction, "6": table6_robustness,
           "7": table7_ablation}
    for t in tables:
        t0 = time.time()
        res = fns[t](labels)
        res["elapsed_s"] = round(time.time() - t0, 1)
        (OUT / f"table{t}.json").write_text(json.dumps(res, indent=1))
        results[t] = res
        print(f"[paper] table{t} done in {res['elapsed_s']}s", flush=True)
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import sys
    labels = LABELS if len(sys.argv) < 2 else tuple(
        int(x) for x in sys.argv[1].split(","))
    out = run_all(labels)
    for t, res in out.items():
        print(f"== table {t} ==")
        for row in res["rows"]:
            print(json.dumps(row))
