import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
combination on the production mesh, print memory/cost analysis, and emit the
roofline terms (EXPERIMENTS.md §Dry-run / §Roofline read from this output).

The two lines above MUST stay the first executable statements: jax locks the
device count at first backend init (see the brief).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
        --shape train_4k [--multi-pod] [--solver-iters 2] [--json out.json]
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
"""
import argparse
import json
import sys
import time

import jax

from repro.api import ExperimentSpec
from repro.configs import ARCHS, INPUT_SHAPES, VARIANTS, get_config
from repro.launch.hlo import Roofline, analyze_hlo, model_flops
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_problem


def active_param_ratio(cfg):
    """fraction of params active per token (MoE top-k routing)."""
    if cfg.num_experts and cfg.top_k:
        # expert params scale with E; active with top_k (+ shared)
        total_e = cfg.num_experts
        active_e = cfg.top_k
        # rough: expert FFN dominates the ratio; attention shared
        return None  # handled via n_active computation in run_one
    return None


def count_params(problem):
    params_shape = problem.args[0]
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params_shape))


def count_active_params(cfg, n_total):
    """N_active for MoE: swap routed-expert count for top_k."""
    if not cfg.num_experts:
        return n_total
    f = cfg.expert_d_ff or cfg.d_ff
    per_expert = 3 * cfg.d_model * f  # gate/up/down
    routed_total = cfg.num_layers * cfg.num_experts * per_expert
    routed_active = cfg.num_layers * cfg.top_k * per_expert
    return n_total - routed_total + routed_active


def run_one(arch: str, shape_name: str, multi_pod: bool,
            solver_iters: int = 2, two_round: bool = False,
            worker_groups: int = 1, compressor: str | None = None,
            error_feedback: str = "none", aggregator: str | None = None,
            verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    # config built (and validated) through the experiment facade
    newton = ExperimentSpec(
        problem="external", runtime="mesh",
        solver_iters=solver_iters, exact_gradient=two_round,
        compressor=compressor, error_feedback=error_feedback,
        aggregator=aggregator if aggregator is not None
        else "norm_trim:0.125",
    ).to_distributed_config()

    problem = make_problem(cfg, shape, mesh, newton, worker_groups=worker_groups)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": int(chips),
        "worker_groups": worker_groups,
    }
    if problem.skipped:
        rec["status"] = "skipped"
        rec["reason"] = problem.skipped
        if verbose:
            print(f"[dryrun] SKIP {problem.label} ({rec['mesh']}): {problem.skipped}")
        return rec

    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            problem.step_fn, in_shardings=problem.in_shardings
        ).lower(*problem.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    # loop-aware HLO analysis (cost_analysis counts while bodies once —
    # useless for scanned layer stacks; see repro.launch.hlo)
    hc = analyze_hlo(hlo)
    flops_dev = float(hc["flops"])
    bytes_dev = float(hc["bytes"])
    coll_dev = float(hc["collective_total"])

    roof = Roofline(flops_dev, bytes_dev, coll_dev, int(chips))
    n_total = count_params(problem)
    n_active = count_active_params(cfg, n_total)
    bp_eq = (1.0 + 2.0 * solver_iters) if shape.kind == "train" else 1.0
    mflops = model_flops(cfg, shape, n_params_active=n_active,
                         backprop_equivalents=bp_eq)
    useful = mflops / (flops_dev * chips) if flops_dev else 0.0

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "params": n_total,
        "params_active": n_active,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collectives": hc["collectives"],
        "collective_counts": hc["collective_counts"],
        "unknown_loops": hc["unknown_loops"],
        "cost_analysis_raw": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "roofline": roof.as_dict(),
        "model_flops": mflops,
        "useful_flops_ratio": useful,
        "memory": {
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
    })
    if verbose:
        print(f"[dryrun] OK {problem.label} mesh={rec['mesh']} "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  memory_analysis: args={rec['memory']['argument_bytes']} "
              f"temp={rec['memory']['temp_bytes']} out={rec['memory']['output_bytes']}")
        print(f"  cost: flops/dev={flops_dev:.3e} bytes/dev={bytes_dev:.3e} "
              f"coll/dev={coll_dev:.3e}")
        print(f"  roofline: compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms "
              f"dominant={roof.dominant} useful_ratio={useful:.3f}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, variant id, or 'all'")
    ap.add_argument("--shape", default="all", choices=list(INPUT_SHAPES) + ["all"])
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2×16×16 multi-pod mesh (default single-pod 16×16)")
    ap.add_argument("--solver-iters", type=int, default=2)
    ap.add_argument("--two-round", action="store_true",
                    help="Remark-5 exact-gradient variant")
    ap.add_argument("--worker-groups", type=int, default=1,
                    help="coalesce N data rows per worker (memory knob)")
    ap.add_argument("--compressor", default=None,
                    help="uplink channel spec (e.g. topk:0.1)")
    ap.add_argument("--error-feedback", default="none",
                    choices=["none", "ef", "ef21"],
                    help="thread mesh-scale EF channel state (stateful step; "
                         "requires --compressor)")
    ap.add_argument("--aggregator", default=None,
                    help="center aggregation spec (norm_trim:<beta>/krum:<n>/"
                         "trimmed_mean:<f>/coordinate_median/mean)")
    ap.add_argument("--json", default=None, help="append JSONL records here")
    args = ap.parse_args(argv)

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]

    records, failures = [], []
    for a in archs:
        for s in shapes:
            try:
                rec = run_one(a, s, args.multi_pod,
                              solver_iters=args.solver_iters,
                              two_round=args.two_round,
                              worker_groups=args.worker_groups,
                              compressor=args.compressor,
                              error_feedback=args.error_feedback,
                              aggregator=args.aggregator)
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                rec = {"arch": a, "shape": s, "status": "error",
                       "error": f"{type(e).__name__}: {e}"}
                failures.append(rec)
                print(f"[dryrun] FAIL {a}×{s}: {rec['error']}")
            records.append(rec)
            if args.json:
                with open(args.json, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {len(failures)} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
