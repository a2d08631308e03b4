"""Membership-inference benchmark for the DP trust layer.

  PYTHONPATH=src python -m benchmarks.privacy_bench [--sigmas 0.3,1,2]

Runs a federation of deterministic random-tensor hospitals whose labels are
PURE noise (``tensor_population`` draws y independent of x), so the only way
any head lowers its training error is by memorizing individual examples —
the worst case for release privacy and the cleanest target for a membership
attack.  The geometry is deliberately overfit-friendly (tiny train split,
many epochs, lr above the paper default) so the no-DP attack has signal.

The attacker is strong: they observe the public head pool AND are granted
the victim's local body (embedding + prediction nets) and seed-deterministic
init heads.  Granting the body is what isolates the RELEASE pathway — body
memorization appears identically in both terms of the score and cancels:

  score(example) = prelim_err(init_heads, example)
                 - prelim_err(published_heads, example)

i.e. how much the published (Eq. 7 preliminary-task) error on that example
improved over init.  Member examples shaped the head trajectory, so their
error improves more; every bit of that signal flows through the published
heads, which is exactly the object ``repro.core.trust.DPNoise`` clips and
noises.  Per client, member scores (train split) are ranked against
non-member scores (a held-out split the client never trained on) with the
Mann-Whitney AUC; the benchmark row reports the mean over clients.

Expected shape of the curve (pinned loosely by tests/CI): the no-DP row
sits meaningfully above 0.5 (~0.73 at the default geometry) and every
DP-on row collapses to ~0.5 while ``epsilon_spent`` composes analytically
across the run's releases.  ``--smoke`` shrinks epochs for CI, where the
DP-on rows keep their near-0.5 AUC (privacy holds at any training length)
even though the no-DP signal is weaker.

Writes ``BENCH_privacy.json`` at the repo root (``--out`` to redirect,
``--out ""`` to disable); :func:`validate_payload` pins its schema and
tests/test_bench_schema.py re-validates the committed file.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO_ROOT / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import networks as N
from repro.core import trust as TR
from repro.core.experiment import tensor_population
from repro.core.federation import Federation
from repro.core.hfl import HFLConfig


def mann_whitney_auc(pos, neg) -> float:
    """P(pos > neg) + 0.5 P(pos == neg) over all pairs — the rank-sum AUC
    of the membership classifier ``score > t`` swept over thresholds."""
    pos, neg = np.asarray(pos, np.float64), np.asarray(neg, np.float64)
    gt = (pos[:, None] > neg[None, :]).mean()
    eq = (pos[:, None] == neg[None, :]).mean()
    return float(gt + 0.5 * eq)


def prelim_errors(heads, split) -> np.ndarray:
    """Per-example preliminary-task error sum_f (y - H_f(xd_f))^2 — the
    head-only prediction pathway (Eq. 7), no body involved."""
    _, xd, y = split
    y_prelim = jax.vmap(N.head_apply, in_axes=(0, 1), out_axes=1)(
        heads, jnp.asarray(xd))
    return np.asarray(((jnp.asarray(y)[:, None] - y_prelim) ** 2).sum(-1))


def attack_federation(fed: Federation, init_heads: dict) -> float:
    """Mean per-client membership AUC against the post-fit public pool."""
    aucs = []
    for cl in fed.clients:
        rows = [fed.pool.entries[(cl.name, f)] for f in range(cl.nf)]
        pub = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)
        h0 = jax.tree_util.tree_map(jnp.asarray, init_heads[cl.name])
        member = prelim_errors(h0, cl.train) - prelim_errors(pub, cl.train)
        non = prelim_errors(h0, cl.test) - prelim_errors(pub, cl.test)
        aucs.append(mann_whitney_auc(member, non))
    return float(np.mean(aucs))


def run_point(args, dp: "TR.DPNoise | None") -> dict:
    cfg = HFLConfig(epochs=args.epochs, R=args.R, mode="always",
                    seed=args.seed, lr=args.lr)
    pop = tensor_population(args.clients, cfg, seed=args.seed,
                            nf_choices=(args.nf,), n_train=args.n_train,
                            n_eval=args.n_eval).build(range(args.clients))
    trust = TR.TrustPlan(dp=dp) if dp is not None else None
    fed = Federation(pop, cfg, engine=args.engine, trust=trust)
    init_heads = {cl.name: jax.tree_util.tree_map(np.array,
                                                  cl.params["heads"])
                  for cl in fed.clients}
    hist = fed.fit()
    stats = fed.dispatch_stats
    releases = sum(fed._dp_counts.values()) if dp is not None else 0
    return {
        "dp": dp is not None,
        "sigma": float(dp.sigma) if dp is not None else 0.0,
        "clip": float(dp.clip) if dp is not None else None,
        "epsilon": float(stats.get("epsilon_spent", 0.0)),
        "releases": int(releases),
        "clip_events": int(stats.get("clip_events", 0)),
        "attack_auc": attack_federation(fed, init_heads),
        "mean_val": float(np.mean([hist[n]["val"][-1] for n in hist])),
    }


def validate_payload(payload: dict) -> None:
    """Structural schema check for BENCH_privacy.json — mirrored by
    tests/test_bench_schema.py so the schema can't drift silently."""
    def need(obj, key, types, where):
        if key not in obj:
            raise ValueError(f"{where}: missing key {key!r}")
        if not isinstance(obj[key], types):
            raise ValueError(f"{where}[{key!r}]: expected {types}, "
                             f"got {type(obj[key]).__name__}")

    need(payload, "benchmark", str, "payload")
    if payload["benchmark"] != "privacy":
        raise ValueError(f"payload[benchmark]: {payload['benchmark']!r}")
    need(payload, "unix_time", int, "payload")
    need(payload, "backend", str, "payload")
    need(payload, "device_count", int, "payload")
    need(payload, "platform", str, "payload")
    need(payload, "config", dict, "payload")
    need(payload, "results", list, "payload")
    cfg = payload["config"]
    for k in ("clients", "epochs", "R", "nf", "n_train", "n_eval", "seed"):
        need(cfg, k, int, "config")
    need(cfg, "lr", (int, float), "config")
    need(cfg, "clip", (int, float), "config")
    need(cfg, "delta", (int, float), "config")
    need(cfg, "engine", str, "config")
    need(cfg, "sigmas", list, "config")
    if not all(isinstance(s, (int, float)) and s > 0
               for s in cfg["sigmas"]):
        raise ValueError("config[sigmas]: expected positive numbers")
    if not payload["results"]:
        raise ValueError("results: empty")
    for i, r in enumerate(payload["results"]):
        where = f"results[{i}]"
        need(r, "dp", bool, where)
        need(r, "sigma", (int, float), where)
        need(r, "clip", (int, float, type(None)), where)
        need(r, "epsilon", (int, float), where)
        need(r, "releases", int, where)
        need(r, "clip_events", int, where)
        need(r, "attack_auc", (int, float), where)
        need(r, "mean_val", (int, float), where)
        if not 0.0 <= r["attack_auc"] <= 1.0:
            raise ValueError(f"{where}[attack_auc]: must be in [0, 1], "
                             f"got {r['attack_auc']}")
        if r["releases"] < 0 or r["clip_events"] < 0:
            raise ValueError(f"{where}: DP counters must be >= 0")
        if r["dp"]:
            if r["epsilon"] <= 0 or r["releases"] <= 0:
                raise ValueError(f"{where}: DP-on rows must spend epsilon")
            if r["sigma"] <= 0 or not r["clip"]:
                raise ValueError(f"{where}: DP-on rows need sigma/clip > 0")
        else:
            if r["epsilon"] != 0 or r["sigma"] != 0:
                raise ValueError(f"{where}: DP-off rows must not spend "
                                 f"epsilon")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=40)
    ap.add_argument("--R", type=int, default=8)
    ap.add_argument("--nf", type=int, default=3)
    ap.add_argument("--n-train", type=int, default=8)
    ap.add_argument("--n-eval", type=int, default=40)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="batched",
                    choices=("sequential", "batched"))
    ap.add_argument("--clip", type=float, default=5.0)
    ap.add_argument("--delta", type=float, default=1e-5)
    ap.add_argument("--sigmas", default="0.3,1.0,2.0",
                    help="comma-separated DP noise multipliers; a no-DP "
                    "row is always emitted first")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: 12 epochs, one DP point")
    ap.add_argument("--out", default=str(_REPO_ROOT / "BENCH_privacy.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.epochs, args.n_eval, args.sigmas = 12, 20, "1.0"
    sigmas = [float(s) for s in args.sigmas.split(",") if s]

    print("dp,sigma,epsilon,releases,clip_events,attack_auc,mean_val",
          flush=True)
    records = []
    for dp in [None] + [TR.DPNoise(clip=args.clip, sigma=s,
                                   delta=args.delta, seed=args.seed)
                        for s in sigmas]:
        r = run_point(args, dp)
        records.append(r)
        print(f"{int(r['dp'])},{r['sigma']:g},{r['epsilon']:.3f},"
              f"{r['releases']},{r['clip_events']},{r['attack_auc']:.4f},"
              f"{r['mean_val']:.4f}", flush=True)

    if args.out:
        payload = {
            "benchmark": "privacy",
            "unix_time": int(time.time()),
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "platform": platform.platform(),
            "config": {"clients": args.clients, "epochs": args.epochs,
                       "R": args.R, "nf": args.nf,
                       "n_train": args.n_train, "n_eval": args.n_eval,
                       "lr": args.lr, "seed": args.seed,
                       "engine": args.engine, "clip": args.clip,
                       "delta": args.delta, "sigmas": sigmas},
            "results": records,
        }
        validate_payload(payload)
        Path(args.out).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
