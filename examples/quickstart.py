"""Quickstart: the paper's HFL system end-to-end on the simulated two-hospital
sparse clinical data (5 minutes on CPU).

  PYTHONPATH=src python examples/quickstart.py [--epochs 12]

Trains the target hospital (metavision, small) and the source hospital
(carevue, large) as decentralized federated clients: each packs dense/sparse
feature tensors (paper §3), trains the H/E/P network (Table 4), publishes
head weights to the asynchronous pool, and — whenever its validation loss
plateaus (the switching mechanism) — selects the best-matching heterogeneous
head by Eq. 7 and blends it in by Eq. 8.
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import dataclasses

from repro.core.experiment import train_hfl
from repro.core.hfl import HFLConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--label", type=int, default=4,
                    help="which channel to predict (0..4), paper: MF5")
    ap.add_argument("--patients", type=int, default=24)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    cfg = HFLConfig(epochs=args.epochs)
    print(f"== HFL (selection + switch), target=metavision MF{args.label+1} ==")
    res = train_hfl("metavision", args.label, cfg, n_patients=args.patients,
                    verbose=args.verbose)
    print(f"HFL      test MSE {res['test']:10.2f}  (federated rounds: "
          f"{res['rounds']})")

    res_no = train_hfl("metavision", args.label,
                       dataclasses.replace(cfg, mode="no"),
                       n_patients=args.patients)
    print(f"HFL-No   test MSE {res_no['test']:10.2f}  (no transfer)")
    delta = 100 * (1 - res["test"] / res_no["test"])
    print(f"=> heterogeneous transfer changed test MSE by {delta:+.1f}% "
          f"on the small target domain")
    if args.epochs < 30:
        print("   (note: below ~30 epochs the Table-4 heads are not yet "
              "load-bearing and transfer provably cannot move the final "
              "prediction — run with --epochs 50 for the paper protocol; "
              "see EXPERIMENTS.md §Repro 'Budget sensitivity')")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
