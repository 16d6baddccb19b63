"""CLI: ``python -m repro.sweep {plan,run,merge,report}``.

    # one host
    python -m repro.sweep run --preset fig12 --store results/sweep/fig12.jsonl

    # two hosts, disjoint shards, then a deterministic union
    python -m repro.sweep run --preset fig12 --shard 0/2 --store s0.jsonl
    python -m repro.sweep run --preset fig12 --shard 1/2 --store s1.jsonl
    python -m repro.sweep merge s0.jsonl s1.jsonl --out fig12.jsonl
    python -m repro.sweep report fig12.jsonl

Re-invoking ``run`` over a finished store performs zero experiment
builds (every cell hash hits the store).  ``--grid file.json`` takes a
``{"axes": {...}, "base": {...}}`` dict instead of a preset.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from . import grids, runner
from . import store as store_mod
from .grid import plan_grid
from .report import report as report_store
from .report import telemetry_report


def _build_plan(args):
    if args.grid:
        ignored = [flag for flag, v in (
            ("--steps", args.steps), ("--datasets", args.datasets),
            ("--alphas", args.alphas), ("--seed", args.seed),
        ) if v is not None]
        if ignored:
            raise SystemExit(
                f"{', '.join(ignored)} only override a --preset; with "
                f"--grid, edit the grid file's axes/base instead"
            )
        with open(args.grid) as f:
            g = json.load(f)
        axes, base = g.get("axes", {}), g.get("base", {})
    else:
        kw = {}
        if args.steps is not None:
            kw["n_steps"] = args.steps
        if getattr(args, "datasets", None):
            kw["datasets"] = tuple(args.datasets.split(","))
        if getattr(args, "alphas", None):
            kw["alphas"] = tuple(float(a) for a in args.alphas.split(","))
        if getattr(args, "seed", None) is not None:
            kw["seed"] = args.seed
        try:
            axes, base = grids.PRESETS[args.preset](**kw)
        except TypeError as e:
            raise SystemExit(
                f"preset {args.preset!r} does not take one of the "
                f"supplied overrides: {e}"
            ) from None
    return plan_grid(axes, base)


def _add_grid_args(p, with_run=False):
    src = p.add_mutually_exclusive_group()
    src.add_argument("--preset", choices=sorted(grids.PRESETS),
                     default="smoke")
    src.add_argument("--grid", help="JSON file with {'axes': …, 'base': …}")
    p.add_argument("--steps", type=int, default=None,
                   help="override the preset's per-cell round budget")
    p.add_argument("--datasets", help="comma list, e.g. a9a,w8a")
    p.add_argument("--alphas", help="comma list, e.g. 0.1,0.2")
    p.add_argument("--seed", type=int, default=None)
    if with_run:
        p.add_argument("--shard", default="0/1", metavar="I/N",
                       help="run shard I of N (hash-partitioned, disjoint)")
        p.add_argument("--store", default=None,
                       help="JSONL store path (default "
                            "results/sweep/<preset>.jsonl)")
        p.add_argument("--budget-s", type=float, default=None,
                       help="per-cell wall-time budget (cooperative)")
        p.add_argument("--limit", type=int, default=None,
                       help="build at most this many cells this invocation")
        p.add_argument("--jobs", type=int, default=1, metavar="N",
                       help="run cells on an N-worker process pool "
                            "(spawn-context; merged stores stay byte-"
                            "identical to --jobs 1); N > 1 needs "
                            "JAX_PLATFORMS=cpu, since a chip serves one "
                            "process")
        p.add_argument("--retry-failed", action="store_true")
        p.add_argument("--retry-truncated", action="store_true",
                       help="re-run cells a previous --budget-s cut short")
        p.add_argument("--telemetry-dir", default=None,
                       help="enable repro.telemetry: per-cell/shard spans "
                            "+ wire/compile events into DIR (events.jsonl "
                            "+ trace.json; use one DIR per shard)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.sweep")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_plan = sub.add_parser("plan", help="expand + validate a grid")
    _add_grid_args(p_plan)
    p_plan.add_argument("--out", help="write the plan (hashes + specs) here")

    p_run = sub.add_parser("run", help="run (a shard of) a grid into a store")
    _add_grid_args(p_run, with_run=True)

    p_merge = sub.add_parser("merge", help="union shard stores (canonical)")
    p_merge.add_argument("stores", nargs="+")
    p_merge.add_argument("--out", required=True)

    p_rep = sub.add_parser("report", help="pivot a store into the tables")
    p_rep.add_argument("store", nargs="?", default=None)
    p_rep.add_argument("--eps", default="0.3,0.1,0.05",
                       help="comma list of ε thresholds")
    p_rep.add_argument("--telemetry", metavar="EVENTS_JSONL", default=None,
                       help="summarize a telemetry events.jsonl stream "
                            "(span timings, cell outcomes, wire/compile "
                            "totals) — the live sweep progress view")
    p_rep.add_argument("--plots", metavar="DIR", default=None,
                       help="also render the Fig. 1-3 panels as PNGs into "
                            "DIR (requires matplotlib; skipped with a "
                            "hint when it is missing)")

    args = ap.parse_args(argv)

    if args.cmd == "plan":
        plan = _build_plan(args)
        print(plan.summary())
        for e in plan.entries:
            print(f"  {e.hash}  n_steps={e.n_steps}  "
                  f"{store_mod.canonical_json(e.spec.to_dict())}")
        for s in plan.skipped:
            print(f"  SKIP {s['reason']}")
        if args.out:
            with open(args.out, "w") as f:
                json.dump([{"hash": e.hash, "n_steps": e.n_steps,
                            "spec": e.spec.to_dict()}
                           for e in plan.entries], f, indent=1)
            print(f"plan written to {args.out}")
        return 0

    if args.cmd == "run":
        from ..launch.cache import use_compile_cache

        use_compile_cache()
        plan = _build_plan(args)
        try:
            idx, num = (int(x) for x in args.shard.split("/"))
        except ValueError:
            raise SystemExit(f"--shard must look like 0/2, got {args.shard!r}")
        if args.store:
            path = args.store
        else:
            stem = (args.preset if not args.grid else
                    os.path.splitext(os.path.basename(args.grid))[0])
            path = f"results/sweep/{stem}.jsonl"
        if args.telemetry_dir:
            from ..telemetry import get_telemetry

            get_telemetry().enable(args.telemetry_dir)
        st = store_mod.ResultStore(path)
        print(plan.summary() + f"; shard {idx}/{num} → {path}")
        summary = runner.run_plan(
            plan, st, shard_index=idx, num_shards=num,
            time_budget_s=args.budget_s, limit=args.limit,
            retry_failed=args.retry_failed,
            retry_truncated=args.retry_truncated, jobs=args.jobs,
            log=print,
        )
        print(f"[sweep] done: built={summary['built']} "
              f"cached={summary['cached']} failed={summary['failed']} "
              f"(shard total {summary['total']})")
        if args.telemetry_dir:
            from ..telemetry import get_telemetry

            get_telemetry().flush()
            print(f"[sweep] telemetry → {args.telemetry_dir}")
        return 1 if summary["failed"] else 0

    if args.cmd == "merge":
        n = store_mod.merge(args.stores, args.out)
        print(f"merged {len(args.stores)} stores → {args.out} ({n} cells)")
        return 0

    if args.cmd == "report":
        if args.store is None and args.telemetry is None:
            raise SystemExit("report needs a store path and/or --telemetry")
        if args.telemetry is not None:
            telemetry_report(args.telemetry)
        if args.store is not None:
            if args.telemetry is not None:
                print()
            eps = tuple(float(e) for e in args.eps.split(","))
            st = store_mod.ResultStore(args.store)
            report_store(st, eps_grid=eps)
            if args.plots is not None:
                from .report import plots as plot_store

                plot_store(st, args.plots)
        elif args.plots is not None:
            raise SystemExit("--plots needs a store path")
        return 0

    return 2


if __name__ == "__main__":
    sys.exit(main())
