"""Roofline analysis of the pool-scoring kernels (and the legacy LLM zoo).

Default mode ``pool_mlp`` profiles the CURRENT hot path of the HFL system:
the fused Eq.-7 pool sweep in ``repro.kernels.pool_mlp.ops`` — the kernel
every engine (batched, cohorted, client-sharded) dispatches once per
exchange round per scoring client.  For each entry point

    pool_mlp_errors           (R, w) probe vs (ns,) pool      -> (ns,)
    pool_mlp_errors_features  (nf, R, w) multi-feature sweep  -> (nf, ns)
    pool_mlp_errors_shard     one device's ns/D pool chunk    -> (nf, chunk)

we lower the jitted op at a sweep of pool sizes and report FLOPs, bytes
accessed and arithmetic intensity from XLA's ``cost_analysis``, falling
back to ANALYTIC counts from the Table-4 head geometry
(w -> 16 -> 256 -> 64 -> 16 -> 1) whenever the compiled module reports no
flops — interpret-mode Pallas lowerings on CPU typically don't.  A timed
execution adds achieved FLOP/s, and ``--peak-flops`` / ``--hbm-bw`` place
each op against a roofline (defaults: TPU v5e, 197 TFLOP/s bf16 and
819 GB/s HBM — the kernel's tuned target; the ridge point tells you which
side of the roof each pool size sits on regardless of the host that ran
the lowering).

Results go to stdout as CSV and, with ``--out``, to a JSON file under
``experiments/roofline/``.  CI smoke-runs ``--smoke`` (tiny pool sweep,
analytic + lowering paths both exercised).

``--mode llm`` keeps the seed repo's LLM-zoo roofline (depth-variant
extrapolation over the production mesh) runnable; only that mode forces
the 512-virtual-device host split, and it does so BEFORE jax initializes,
which is why the mode flag is read straight from argv.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO_ROOT / "src"))


def _mode_from_argv() -> str:
    """``--mode`` must be known before jax first initializes (the llm mode
    lowers on a 512-virtual-device host split, locked at first init), so it
    is read straight from argv; argparse re-parses it later."""
    for i, arg in enumerate(sys.argv):
        if arg == "--mode" and i + 1 < len(sys.argv):
            return sys.argv[i + 1]
        if arg.startswith("--mode="):
            return arg.split("=", 1)[1]
    return "pool_mlp"


if _mode_from_argv() == "llm":
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import jax
import numpy as np

OUT_DIR = _REPO_ROOT / "experiments" / "roofline"

PEAK_FLOPS = 197e12          # bf16 / chip (TPU v5e)
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes")

# Table-4 global-head MLP: dense (w,) feature vector -> scalar preliminary
# prediction (repro.core.networks.head_schema)
_HEAD_DIMS = (16, 256, 64, 16, 1)


def _head_dims(w: int):
    return (w,) + _HEAD_DIMS


def _compiled_cost(compiled) -> dict:
    """cost_analysis across jax versions: dict, list-of-dict, or absent."""
    try:
        c = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backends without an analysis
        return {}
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return dict(c or {})


def analytic_flops(ns: int, nf: int, R: int, w: int) -> float:
    """Eq.-7 sweep FLOPs: every (feature, pool row, probe sample) triple
    runs the head MLP forward (2ab per dense layer) plus the squared-error
    reduction — the count the kernel's grid walks by construction."""
    dims = _head_dims(w)
    mlp = sum(2 * a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return float(nf) * ns * (R * (mlp + 3))     # +3: err, square, accumulate


def analytic_bytes(ns: int, nf: int, R: int, w: int) -> float:
    """Unique-traffic floor: pool weights + probes read once, errors
    written once (f32)."""
    dims = _head_dims(w)
    weights = ns * sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    return 4.0 * (weights + nf * R * w + R + nf * ns)


def _pool(ns: int, w: int, rng) -> dict:
    dims = _head_dims(w)
    out = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        out[f"w{i}"] = rng.normal(size=(ns, a, b)).astype(np.float32)
        out[f"b{i}"] = rng.normal(size=(ns, b)).astype(np.float32)
    return out


def measure_pool_op(op: str, ns: int, nf: int, R: int, w: int,
                    repeats: int = 10) -> dict:
    """Lower + time one pool_mlp entry point at one pool size.  Returns
    cost-analysis FLOPs/bytes (``source: xla``) or the analytic model
    (``source: analytic``) when the lowering reports no flops, plus
    arithmetic intensity, achieved FLOP/s, and the lowered memory
    footprint."""
    from repro.kernels.pool_mlp import ops

    rng = np.random.default_rng(0)
    pool = _pool(ns, w, rng)
    y = rng.normal(size=R).astype(np.float32)
    xd = rng.normal(size=(R, w)).astype(np.float32)
    xdf = rng.normal(size=(nf, R, w)).astype(np.float32)
    if op == "pool_mlp_errors":
        fn, args, nf_eff = ops.pool_mlp_errors, (pool, xd, y), 1
    elif op == "pool_mlp_errors_features":
        fn, args, nf_eff = ops.pool_mlp_errors_features, (pool, xdf, y), nf
    elif op == "pool_mlp_errors_shard":
        # one device's chunk of a larger flattened pool, with a validity
        # mask as the cohort/mesh engines pass it
        valid = np.ones(ns, bool)
        fn = jax.jit(lambda p, x, yy, v: ops.pool_mlp_errors_shard(
            p, x, yy, v))
        args, nf_eff = (pool, xdf, y, valid), nf
    else:
        raise SystemExit(f"unknown pool op {op!r}")

    compiled = jax.jit(fn).lower(*args).compile() \
        if op != "pool_mlp_errors_shard" else fn.lower(*args).compile()
    cost = _compiled_cost(compiled)
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    source = "xla"
    if flops <= 0:
        flops, source = analytic_flops(ns, nf_eff, R, w), "analytic"
    if bytes_ <= 0:
        bytes_ = analytic_bytes(ns, nf_eff, R, w)
    jax.block_until_ready(compiled(*args))      # warm
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = compiled(*args)
    jax.block_until_ready(out)
    wall = (time.perf_counter() - t0) / repeats
    mem = compiled.memory_analysis()
    return {
        "op": op, "ns": ns, "nf": nf_eff, "R": R, "w": w,
        "flops": flops, "bytes": bytes_, "source": source,
        "intensity": flops / bytes_ if bytes_ else 0.0,
        "wall_s": wall,
        "achieved_flops": flops / wall if wall else 0.0,
        "memory_analysis": {f: int(getattr(mem, f, 0) or 0)
                            for f in _MEM_FIELDS},
    }


def main_pool_mlp(args) -> int:
    sizes = [int(x) for x in args.ns.split(",")]
    ops_list = args.ops.split(",")
    ridge = args.peak_flops / args.hbm_bw
    rows = []
    print("op,ns,nf,R,w,source,flops,bytes,intensity,achieved_gflops,"
          "bound", flush=True)
    for op in ops_list:
        for ns in sizes:
            r = measure_pool_op(op, ns, args.nf, args.R, args.w,
                                repeats=args.repeats)
            # which side of the ridge point this sweep sits on, for the
            # TARGET accelerator (the host that lowered it is irrelevant)
            r["bound"] = ("compute" if r["intensity"] >= ridge
                          else "memory")
            r["roof_s"] = max(r["flops"] / args.peak_flops,
                              r["bytes"] / args.hbm_bw)
            rows.append(r)
            print(f"{op},{ns},{r['nf']},{r['R']},{r['w']},{r['source']},"
                  f"{r['flops']:.3e},{r['bytes']:.3e},"
                  f"{r['intensity']:.2f},{r['achieved_flops'] / 1e9:.2f},"
                  f"{r['bound']}", flush=True)
    if args.out:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        out = OUT_DIR / args.out
        out.write_text(json.dumps({
            "mode": "pool_mlp", "backend": jax.default_backend(),
            "peak_flops": args.peak_flops, "hbm_bw": args.hbm_bw,
            "ridge_intensity": ridge, "rows": rows}, indent=1) + "\n")
        print(f"wrote {out}", file=sys.stderr)
    return 0


# ---------------------------------------------------------------------------
# Legacy mode: the seed repo's LLM-zoo roofline (depth-variant
# extrapolation on the 256-chip production mesh).  Unchanged method — see
# EXPERIMENTS.md §Roofline/Method; imports stay inside the functions so the
# default pool_mlp mode never touches the zoo (or its 512-device forcing).
# ---------------------------------------------------------------------------

def _depth_variants(cfg):
    import dataclasses
    base = dataclasses.replace(
        cfg, segments=tuple(dataclasses.replace(s, repeats=1)
                            for s in cfg.segments))
    variants = []
    for i in range(len(cfg.segments)):
        segs = [dataclasses.replace(s, repeats=2 if j == i else 1)
                for j, s in enumerate(cfg.segments)]
        variants.append(dataclasses.replace(cfg, segments=tuple(segs)))
    return base, variants


def _measure(cfg, shape_name: str, mesh, moe_a2a: bool = False):
    """Lower one config x shape on `mesh`; return dict of raw costs."""
    import jax.numpy as jnp  # noqa: F401
    from jax.sharding import PartitionSpec as P

    from repro.configs import INPUT_SHAPES
    from repro.launch import steps
    from repro.launch.dryrun import _first_cost, collective_bytes, named
    from repro.sharding import spec as S

    shape = INPUT_SHAPES[shape_name]
    opt = steps.default_optimizer()
    needs_mesh = ((moe_a2a and cfg.moe is not None) or
                  (cfg.attn is not None and cfg.attn.n_heads_padded))
    moe_mesh = mesh if needs_mesh else None
    with mesh:
        if shape.kind == "train":
            fn = steps.make_train_step(cfg, opt, unroll=True,
                                       moe_mesh=moe_mesh)
            state = steps.abstract_state(cfg, opt)
            st_specs = named(steps.state_pspecs(cfg, opt, mesh), mesh)
            batch = steps.batch_spec(cfg, shape)
            b_specs = named(steps.batch_pspecs(cfg, shape, mesh), mesh)
            lowered = jax.jit(fn, in_shardings=(st_specs, b_specs),
                              out_shardings=(st_specs, None)).lower(state,
                                                                    batch)
        elif shape.kind == "prefill":
            fn = steps.make_prefill_step(cfg, unroll=True, moe_mesh=moe_mesh)
            p_specs, schema = steps.param_pspecs(cfg, mesh)
            lowered = jax.jit(
                fn, in_shardings=(named(p_specs, mesh),
                                  named(steps.batch_pspecs(cfg, shape, mesh),
                                        mesh)),
                out_shardings=None).lower(S.abstract(schema),
                                          steps.batch_spec(cfg, shape))
        else:
            fn = steps.make_serve_step(cfg, shape.seq_len, unroll=True)
            p_specs, schema = steps.param_pspecs(cfg, mesh)
            kvq = bool(int(os.environ.get("REPRO_KV_QUANT", "0")))
            cache, tokens, pos = steps.decode_inputs_spec(cfg, shape,
                                                          kv_quant=kvq)
            c_specs = named(steps.cache_pspecs(cfg, shape, mesh,
                                               kv_quant=kvq), mesh)
            scalar = jax.NamedSharding(mesh, P())
            lowered = jax.jit(
                fn, in_shardings=(named(p_specs, mesh), c_specs, scalar,
                                  scalar),
                out_shardings=(None, c_specs)).lower(
                    S.abstract(schema), cache, tokens, pos)
        compiled = lowered.compile()
    cost = _first_cost(compiled)
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll.get("total", 0)),
        "mem": {f: int(getattr(mem, f, 0) or 0) for f in _MEM_FIELDS},
    }


def model_flops(cfg, shape_name: str) -> float:
    """MODEL_FLOPS = 6 N D (training) with N = active params (MoE: routed
    top-k active only); decode: 2 N_active per token x batch."""
    from repro.configs import INPUT_SHAPES
    from repro.models.model import model_schema
    from repro.sharding import spec as S

    flat, _ = jax.tree_util.tree_flatten_with_path(model_schema(cfg),
                                                   is_leaf=S.is_spec)
    total = active = 0
    for path, sp in flat:
        n = sp.size
        total += n
        if sp.logical and "experts" in sp.logical:
            n = n * cfg.moe.top_k // cfg.moe.n_experts
        active += n
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        return 6.0 * active * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * active * shape.seq_len * shape.global_batch
    return 2.0 * active * shape.global_batch           # one token


def roofline_pair(arch: str, shape_name: str, mesh,
                  moe_a2a: bool = False) -> dict:
    from repro.configs import INPUT_SHAPES, get_config
    from repro.launch import steps

    cfg = steps.effective_config(get_config(arch), INPUT_SHAPES[shape_name])
    base_cfg, variants = _depth_variants(cfg)
    t0 = time.time()
    base = _measure(base_cfg, shape_name, mesh, moe_a2a)
    totals = dict(flops=base["flops"], bytes=base["bytes"],
                  coll=base["coll"])
    units = []
    for seg, vcfg in zip(cfg.segments, variants):
        v = _measure(vcfg, shape_name, mesh, moe_a2a)
        unit = {k: max(0.0, v[k] - base[k])
                for k in ("flops", "bytes", "coll")}
        units.append(unit)
        for k in totals:
            totals[k] += (seg.repeats - 1) * unit[k]
    n_chips = mesh.devices.size
    compute_s = totals["flops"] / PEAK_FLOPS          # per-device program
    memory_s = totals["bytes"] / HBM_BW
    coll_s = totals["coll"] / ICI_BW
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", coll_s), key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape_name)
    hlo_global = totals["flops"] * n_chips
    return {
        "arch": arch, "shape": shape_name, "mesh": "16x16",
        "chips": n_chips, "moe_a2a": moe_a2a,
        "per_device": totals,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "memory_analysis_base": base["mem"],
        "elapsed_s": round(time.time() - t0, 1),
    }


def main_llm(args) -> int:
    from repro.configs import INPUT_SHAPES, list_archs
    from repro.launch.mesh import make_production_mesh

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    mesh = make_production_mesh(multi_pod=False)
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    suffix = "__a2a" if args.moe_a2a else ""
    print("arch,shape,compute_s,memory_s,collective_s,dominant,"
          "model_flops,useful_ratio", flush=True)
    fails = []
    for arch in archs:
        for shape in shapes:
            out = OUT_DIR / f"{arch}__{shape}{suffix}.json"
            if args.skip_existing and out.exists():
                r = json.loads(out.read_text())
            else:
                try:
                    r = roofline_pair(arch, shape, mesh, args.moe_a2a)
                    out.write_text(json.dumps(r, indent=1))
                except Exception as e:  # noqa: BLE001
                    print(f"FAIL,{arch},{shape},{e}", flush=True)
                    import traceback
                    traceback.print_exc()
                    fails.append((arch, shape))
                    continue
            print(f"{arch},{shape},{r['compute_s']:.3e},"
                  f"{r['memory_s']:.3e},{r['collective_s']:.3e},"
                  f"{r['dominant']},{r['model_flops']:.3e},"
                  f"{r['useful_ratio']:.3f}", flush=True)
    return 1 if fails else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="pool_mlp",
                    choices=("pool_mlp", "llm"),
                    help="pool_mlp: roofline of the Eq.-7 pool-scoring "
                         "kernels (the HFL hot path); llm: the seed "
                         "LLM-zoo roofline on the production mesh")
    # pool_mlp mode
    ap.add_argument("--ops", default="pool_mlp_errors,"
                                     "pool_mlp_errors_features,"
                                     "pool_mlp_errors_shard")
    ap.add_argument("--ns", default="8,64,512",
                    help="comma list of pool sizes to sweep")
    ap.add_argument("--nf", type=int, default=4)
    ap.add_argument("--R", type=int, default=20)
    ap.add_argument("--w", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--peak-flops", type=float, default=PEAK_FLOPS,
                    help="target accelerator peak FLOP/s for the roofline "
                         "(default: TPU v5e bf16)")
    ap.add_argument("--hbm-bw", type=float, default=HBM_BW,
                    help="target accelerator HBM bandwidth, bytes/s")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI (one op, ns=8,16, 2 repeats)")
    ap.add_argument("--out", default=None,
                    help="JSON filename under experiments/roofline/ "
                         "(pool_mlp mode)")
    # llm mode
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--moe-a2a", action="store_true",
                    help="use the explicit all-to-all MoE dispatch "
                         "(optimized variant; writes *__a2a.json)")
    args = ap.parse_args()
    if args.smoke:
        args.ops = "pool_mlp_errors,pool_mlp_errors_features"
        args.ns, args.repeats = "8,16", 2
    if args.mode == "llm":
        sys.exit(main_llm(args))
    sys.exit(main_pool_mlp(args))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
