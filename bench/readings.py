#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/readings.py --workload <name> --seeds 1,2,... \\
        --control-seeds 7,8,9 [--faults half_batch,...] [--fault-seeds 4,5,6] \\
        [--seconds 3] [--out FILE]

In one process (the set-up is paid once): for each of ``--seeds`` a short
window of the program at the cell's own size and load, compared with the
plain float32 reference exactly as a benchmark run compares it (the lower
readings); then for each of ``--control-seeds`` the control, the same
reference computed in bfloat16 and put in the program's place, solving
the same keys to ε and compared with the float32 reference the same way
(the upper readings); then for each of ``--faults`` (``bench/faults.py``)
the program with that fault planted under the timed path, a short window
for each of ``--fault-seeds``, compared the same way.  One JSON line per
reading; the benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time

from run import ROOT, load, load_cell, start_jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    jax = start_jax()
    import jax.numpy as jnp

    cell = load_cell(args.workload)
    rt = load(f"runtimes/{cell.config['runtime']}")
    ref_mod = load(f"reference/{cell.config['runtime']}")
    ref32 = rt.reference_for(cell, ref_mod)
    rows = []

    def program_readings(kind, seeds):
        exp = rt.build(cell)
        rt.compile_round(exp)
        for seed in seeds:
            t0 = time.perf_counter()
            with rt.cache_writes_off():
                if seed == seeds[0]:
                    rt.warm_up(exp, cell, seed)
                win = rt.window(exp, cell, seed, args.seconds)
            gaps = rt.compare(cell, seed, win["solves"], ref_mod, ref32)
            rows.append({"workload": cell.name, "kind": kind, "seed": seed,
                         "device": jax.devices()[0].device_kind, "gaps": gaps,
                         "solves": len(win["solves"]),
                         "rounds": sorted({s.rounds for s in win["solves"]}),
                         "seconds": time.perf_counter() - t0})
            print(json.dumps(rows[-1]), flush=True)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    if seeds:
        program_readings("program", seeds)
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    if control_seeds:
        ref16 = rt.reference_for(cell, ref_mod, dtype=jnp.bfloat16)
        bpr = ref_mod.bits_per_round(ref32.m, ref32.d, cell.traffic["reference"].get("topk"))
        for seed in control_seeds:
            solves = rt.control_solves(cell, ref16, seed, bpr)
            gaps = rt.compare(cell, seed, solves, ref_mod, ref32)
            rows.append({"workload": cell.name, "kind": "control_bf16", "seed": seed,
                         "device": jax.devices()[0].device_kind, "gaps": gaps,
                         "rounds": [s.rounds for s in solves]})
            print(json.dumps(rows[-1]), flush=True)
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]
    for fault in (f for f in args.faults.split(",") if f):
        with load("faults").planted(fault):
            program_readings(f"fault:{fault}", fault_seeds)
    if args.out:
        with open(args.out, "a") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
