"""Benchmark entrypoint (deliverable d): ``PYTHONPATH=src python -m benchmarks.run``.

One benchmark per paper table (5/6/7) + kernel micro-benches + the roofline
summary (the roofline lowers on a 512-device host mesh, so it runs as a
subprocess — jax locks the device count at first init).

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, then
the paper-table summaries.  Env:
  REPRO_BENCH_FULL=1     full 50-epoch / 5-seed paper protocol
  REPRO_BENCH_LABELS=4   restrict paper tables to one label task
  REPRO_BENCH_SKIP_ROOFLINE=1 / REPRO_BENCH_SKIP_TABLES=1
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    t0 = time.time()
    print("name,us_per_call,derived", flush=True)

    # --- kernel micro-benches ---------------------------------------------
    from benchmarks import kernel_bench
    for name, us, derived in kernel_bench.run():
        print(f"{name},{us:.1f},{derived}", flush=True)

    # --- paper tables (5/6/7) ----------------------------------------------
    if not int(os.environ.get("REPRO_BENCH_SKIP_TABLES", "0")):
        from benchmarks import paper_tables
        labels_env = os.environ.get("REPRO_BENCH_LABELS")
        labels = (tuple(int(x) for x in labels_env.split(","))
                  if labels_env else paper_tables.LABELS)
        results = paper_tables.run_all(labels)
        for t, res in results.items():
            for row in res["rows"]:
                sysnames = [k for k in row
                            if isinstance(row[k], dict) and "test" in row[k]]
                tests = {s: round(row[s]["test"], 2) for s in sysnames}
                tgt = row.get("target", res.get("target", ""))
                print(f"table{t}_{tgt}_{row['label']},"
                      f"{res['elapsed_s'] * 1e6 / max(1, len(res['rows'])):.0f},"
                      f"best={row['best']}|{tests}", flush=True)

    # --- roofline (subprocess: needs 512 forced host devices) --------------
    if not int(os.environ.get("REPRO_BENCH_SKIP_ROOFLINE", "0")):
        # the child only lowers, on forced CPU host devices: it must stay
        # off the chip this process already holds
        env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.roofline", "--skip-existing"],
            cwd=ROOT, env=env, capture_output=True, text=True)
        sys.stdout.write(proc.stdout)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-4000:])
            raise SystemExit("roofline failed")

    print(f"benchmarks_total,{(time.time() - t0) * 1e6:.0f},wall", flush=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
