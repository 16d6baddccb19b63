"""Benchmark harness — one entry per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of one
algorithm round / kernel call on this host; derived = the headline derived
metric for that artifact: final accuracy, loss, round-speedup, exact wire
bits, or dominant roofline term).  Full-protocol runs: pass --full; CI
smoke: ``--dryrun`` (seconds-scale budgets, every entry still executed).

    PYTHONPATH=src python -m benchmarks.run [--full|--dryrun] [--skip-roofline]
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale rounds")
    ap.add_argument("--dryrun", action="store_true",
                    help="seconds-scale smoke: tiny budgets, exercises every "
                         "benchmark entry (CI runs this so they can't rot)")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--out", default="results/benchmarks.json")
    ap.add_argument("--sweep-store-dir", default=None,
                    help="persist the fig3/fig12 sweeps as resumable "
                         "stores under this dir (re-runs skip stored "
                         "cells); default: in-memory")
    ap.add_argument("--trace-dir", default=None,
                    help="enable repro.telemetry: one span per benchmark "
                         "section (plus per-round/wire/compile events from "
                         "the runs underneath) into DIR/events.jsonl and a "
                         "Perfetto-loadable DIR/trace.json")
    ap.add_argument("--bench-ledger", default="results/bench",
                    help="append one fingerprinted BENCH_<name>.json "
                         "record per entry under this dir (compare with "
                         "`python -m repro.obsv bench-compare`); pass an "
                         "empty string to disable")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    from repro.obsv import append_ledger, extract_scalars, fingerprint

    use_compile_cache()
    from repro.telemetry import get_telemetry

    tel = get_telemetry()
    if args.trace_dir is not None:
        tel.enable(args.trace_dir)

    meta = fingerprint()

    def _store(name):
        if args.sweep_store_dir is None:
            return None
        return os.path.join(args.sweep_store_dir, f"{name}.jsonl")

    from . import (
        async_staleness,
        fig3_convergence,
        fig12_byzantine,
        headtohead,
        saddle_escape,
        table1_communication,
        roofline,
    )

    T = 15 if args.full else (2 if args.dryrun else 6)
    datasets = ("a9a", "w8a") if args.full else ("a9a",)
    if args.dryrun:
        args.skip_roofline = True
    all_results = {}
    print("name,us_per_call,derived")

    # ---- Fig. 3: non-Byzantine convergence (sweep-engine backed) ---------
    t0 = time.time()
    with tel.span("bench.fig3"):
        r3 = fig3_convergence.run(T=T, datasets=datasets,
                                  Ms=(10.0, 15.0, 20.0) if args.full
                                  else (10.0,),
                                  store_path=_store("fig3"))
    n_rounds = sum(len(v.get("loss", [])) for v in r3.values())
    for k, v in r3.items():
        derived = (f"final_acc={v['accuracy'][-1]:.4f}" if "accuracy" in v
                   else f"final_loss={v['loss'][-1]:.4f}")
        _emit(f"fig3/{k}", (time.time() - t0) / max(n_rounds, 1) * 1e6, derived)
    all_results["fig3"] = r3

    # ---- Figs. 1 & 2: Byzantine attacks (sweep-engine backed) ------------
    t0 = time.time()
    with tel.span("bench.fig12"):
        r12 = fig12_byzantine.run(
            T=T, datasets=datasets,
            attacks=("flipped_label", "negative", "gaussian", "random_label")
            if args.full else (("gaussian",) if args.dryrun
                               else ("flipped_label", "gaussian")),
            alphas=(0.10, 0.15, 0.20) if args.full else (0.20,),
            store_path=_store("fig12"),
        )
    n_rounds = sum(len(v.get("loss", v.get("accuracy", []))) for v in r12.values())
    for k, v in r12.items():
        derived = (f"final_acc={v['accuracy'][-1]:.4f}" if "accuracy" in v
                   else f"final_loss={v['loss'][-1]:.4f}")
        _emit(k, (time.time() - t0) / max(n_rounds, 1) * 1e6, derived)
    all_results["fig12"] = r12

    # ---- Table 1: communication rounds vs ByzantinePGD --------------------
    t0 = time.time()
    with tel.span("bench.table1"):
        t1 = table1_communication.run(
            dataset="a9a" if args.dryrun else "w8a",
            attacks=("gaussian", "flipped_label", "negative", "random_label")
            if args.full else ("gaussian",),
            alphas=(0.10, 0.15, 0.20) if args.full else (0.15,),
            max_rounds=400 if args.full else (40 if args.dryrun else 250),
            newton_budget=60 if not args.dryrun else 4,
        )
    dt = time.time() - t0
    for row in t1:
        _emit(
            f"table1/{row['attack']}/alpha={row['alpha']:g}",
            dt / max(len(t1), 1) * 1e6 / 100,
            f"newton={row['newton_rounds']}r pgd={row['pgd_rounds']}r "
            f"speedup={row['speedup']:.1f}x "
            f"up_bits={row['newton_uplink_bits']} "
            f"down_bits={row['newton_downlink_bits']}",
        )
    all_results["table1"] = t1

    # ---- Head-to-head: solver axis (second- vs first-order) ---------------
    # one sweep grid, all three solvers through the same channel stack;
    # every bits@ε below is an exact WireLedger int
    t0 = time.time()
    with tel.span("bench.headtohead"):
        h2h = headtohead.run(
            T=60 if args.full else (2 if args.dryrun else 20),
            datasets=("a9a",) if args.dryrun else ("w8a",),
            eps=0.3 if args.dryrun else 0.05,
            store_path=_store("headtohead"),
        )
    dt = time.time() - t0
    for row in h2h:
        eps_cols = " ".join(
            f"{c}={'miss' if v is None else v}" for c, v in row.items()
            if "_rounds@" in c or "_bits@" in c
        )
        _emit(
            f"headtohead/{row['attack']}/{row['aggregator']}"
            f"/alpha={row['alpha']:g}",
            dt / max(len(h2h), 1) * 1e6 / 100,
            eps_cols,
        )
    all_results["headtohead"] = h2h

    # ---- Table 1 (compression axis): exact bits on the wire ---------------
    t0 = time.time()
    with tel.span("bench.table1_compression"):
        tc = table1_communication.run_compression(
            dataset="w8a" if args.full else "a9a",
            newton_budget=60 if not args.dryrun else 4,
        )
    dt = time.time() - t0
    for row in tc:
        _emit(
            f"table1_compression/{row['compressor']}",
            dt / max(len(tc), 1) * 1e6 / 100,
            f"rounds={row['rounds']} "
            f"up/round={row['uplink_bits_per_round']} "
            f"down/round={row['downlink_bits_per_round']} "
            f"up_total={row['uplink_bits']} down_total={row['downlink_bits']} "
            f"overhead={row['round_overhead']:.2f}x "
            f"bits_saving={row['bits_saving']:.1f}x",
        )
    all_results["table1_compression"] = tc

    # ---- bits-to-ε curve (total wire, uplink+downlink) --------------------
    t0 = time.time()
    with tel.span("bench.bits_to_eps"):
        te = table1_communication.run_bits_to_eps(
            dataset="w8a" if args.full else "a9a",
            newton_budget=25 if not args.dryrun else 4,
            eps_grid=(0.3, 0.1, 0.05, 0.02) if not args.dryrun else (0.3,),
        )
    dt = time.time() - t0
    for row in te:
        eps_str = " ".join(
            f"eps{eps:g}={bits if bits is not None else 'miss'}"
            for eps, bits in row["bits_to_eps"].items()
        )
        _emit(
            f"bits_to_eps/{row['compressor']}",
            dt / max(len(te), 1) * 1e6 / 100,
            eps_str,
        )
    all_results["bits_to_eps"] = te

    # ---- top-k kernel vs XLA at model scale -------------------------------
    # d sweep scales with the budget: dryrun proves the gridded launch at
    # CI speed; --full covers the ISSUE's 1.4k → 1M ladder (interpret mode
    # off-TPU, so the derived column carries the mode flag)
    kd = ((1408, 4096) if args.dryrun
          else table1_communication.KERNEL_TIMING_DS if args.full
          else (1408, 16_384, 131_072))
    with tel.span("bench.topk_kernel"):
        kt = table1_communication.run_kernel_timing(ds=kd)
    for row in kt:
        _emit(
            f"topk_kernel/d={row['d']}",
            row["kernel_us"],
            f"plan={row['plan']} k={row['k']} "
            f"xla_us={row['xla_topk_us']:.1f} "
            f"interpret={row['interpret_mode']}",
        )
    all_results["topk_kernel_timing"] = kt

    # ---- aggregation roofline: fused center kernels vs XLA dense ----------
    # same budget scaling as the top-k ladder; every row asserts parity
    # before it times, so bench-smoke exercises the kernels' semantics
    agg_ms = (4, 8) if args.dryrun else table1_communication.AGG_ROOFLINE_MS
    agg_ds = ((1408, 4096) if args.dryrun
              else table1_communication.KERNEL_TIMING_DS if args.full
              else (1408, 16_384, 131_072))
    with tel.span("bench.agg_roofline"):
        ar = table1_communication.run_agg_roofline(ms=agg_ms, ds=agg_ds)
    for row in ar:
        extra = (f"xla_us={row['xla_dense_us']:.1f}"
                 if "xla_dense_us" in row else "baseline=skipped")
        bytes_str = (f" center_bytes={row['center_bytes_sparse']}"
                     f"/{row['center_bytes_dense']}"
                     if "center_bytes_sparse" in row else "")
        _emit(
            f"agg_roofline/{row['rule']}/m={row['m']}/d={row['d']}",
            row["kernel_us"],
            f"plan={row['plan']} {extra}{bytes_str} "
            f"interpret={row['interpret_mode']}",
        )
    all_results["agg_roofline"] = ar

    # ---- Saddle escape (beyond-paper; Theorems 1-2 exercised directly) ----
    t0 = time.time()
    with tel.span("bench.saddle_escape"):
        se = saddle_escape.run(
            T=25 if args.full else (5 if args.dryrun else 15))
    dt = (time.time() - t0) * 1e6 / 45
    sv = se["newton"]["saddle_value"]
    _emit("saddle/newton", dt, f"final={se['newton']['loss'][-1]:.4f} "
          f"(saddle_value={sv:.2f})")
    _emit("saddle/first_order_gd", dt, f"final={se['gd']['loss'][-1]:.4f}")
    _emit("saddle/newton_under_saddle_attack", dt,
          f"final={se['newton_saddle_attack']['loss'][-1]:.4f}")
    all_results["saddle_escape"] = se

    # ---- Resilience vs staleness (async runtime; beyond-paper) ------------
    t0 = time.time()
    with tel.span("bench.async_staleness"):
        ast = async_staleness.run(
            T=8 if args.full else (2 if args.dryrun else 6),
            stalenesses=(0, 1, 4) if not args.dryrun else (0, 1),
            participations=(1.0, 0.5),
            alphas=(0.0, 0.2) if not args.dryrun else (0.2,),
        )
    dt = (time.time() - t0) * 1e6 / max(len(ast["cells"]), 1)
    for cell in ast["cells"]:
        esc = cell["saddle_escape_step"]
        _emit(
            f"async/stale={cell['staleness']}/p={cell['participation']:g}"
            f"/alpha={cell['alpha']:g}",
            dt,
            f"final={cell['loss'][-1]:.4f} "
            f"escape={'miss' if esc is None else esc} "
            f"up_bits={cell['uplink_bits']}",
        )
    if "degenerate_bit_exact" in ast:
        _emit("async/degenerate_bit_exact", 0.0,
              f"bit_exact={ast['degenerate_bit_exact']}")
        assert ast["degenerate_bit_exact"], \
            "degenerate async cell must be bit-exact with runtime='paper'"
    all_results["async_staleness"] = ast

    # ---- Roofline: dry-run aggregation + kernel micro-bench ---------------
    if not args.skip_roofline:
        with tel.span("bench.roofline"):
            rows = roofline.roofline_table()
        for r in rows:
            if r["status"] == "ok":
                _emit(
                    f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
                    max(r["compute_ms"], r["memory_ms"], r["collective_ms"]) * 1e3,
                    f"dominant={r['dominant']} useful={r['useful_flops_ratio']:.3f}",
                )
            else:
                _emit(
                    f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
                    0.0,
                    f"{r['status']}:{r.get('reason','')[:60]}",
                )
        all_results["roofline"] = rows
        for name, us, derived_us in roofline.kernel_microbench():
            _emit(f"kernel/{name}", us, f"tpu_roofline_us={derived_us:.2f}")

    all_results["meta"] = meta
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(all_results, f, indent=1, default=str)
    if args.bench_ledger:
        n_led = 0
        for name, entry in all_results.items():
            if name == "meta":
                continue
            scalars = extract_scalars(name, entry)
            if scalars:
                append_ledger(args.bench_ledger, name, scalars, meta)
                n_led += 1
        print(f"# bench ledger -> {args.bench_ledger} "
              f"({n_led} BENCH_<name>.json files)")
    if args.trace_dir is not None:
        tel.flush()
        print(f"# telemetry -> {args.trace_dir}")


if __name__ == "__main__":
    main()
