#!/usr/bin/env python3
"""Smoke run of Algorithm 1 on one TPU chip, through the normal entry points.

    python chip_smoke.py             # one chip: device check, main path,
                                     # kernels on the main path, gridded top-k
    python chip_smoke.py --chips 4   # four chips: the host-mesh training
                                     # step against the same steps on device 0

Every phase checks its own result and raises on a failure; nothing runs on
past a failed phase, and nothing falls back to the CPU.  The last line of
standard output is ``{"ok": true, "device": {...}}`` and is printed only
when every phase passed.  All work stays in this one process: a TPU chip
serves one process at a time.

Times printed here are smoke numbers (host clock, windows ended with
``block_until_ready``, compile seconds reported apart), not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# the paper's cross-silo deployment (the fig12 job): w8a twin, 49,749 x 300
# training rows over 20 workers, norm-trim against a Gaussian attack
MAIN_SPEC = dict(problem="w8a-logistic", m_workers=20,
                 aggregator="norm_trim:0.3", attack="gaussian", alpha=0.2)
MAIN_ROUNDS = 5
KERNEL_ROUNDS = 3
# each *_kernel spec beside its XLA twin on the main-path problem
KERNEL_PAIRS = (
    (dict(compressor="topk_kernel:0.1", aggregator="krum_kernel:4"),
     dict(compressor="topk:0.1", aggregator="krum:4")),
    (dict(aggregator="trimmed_mean_kernel:0.25"),
     dict(aggregator="trimmed_mean:0.25")),
)
# the d=65536 facade path on the mesh runtime: the gridded top-k launch
GRIDDED_PAIR = (
    dict(problem="quadratic:65535", runtime="mesh",
         compressor="topk_kernel:0.01"),
    dict(problem="quadratic:65535", runtime="mesh", compressor="topk:0.01"),
)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def device_check(jax, want_count):
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log("device", f"platform={info['platform']} kind={info['kind']} "
                  f"count={info['count']}")
    check(info["platform"] == "tpu",
          f"JAX found no TPU (platform {info['platform']!r}); this smoke "
          f"runs on the chip only")
    check(want_count == 1 or info["count"] == want_count,
          f"--chips {want_count} needs {want_count} devices, JAX sees "
          f"{info['count']}")
    return info


def assert_kernel_lowered(lowered, what):
    """A kernel that ran as a Mosaic kernel shows up as a
    ``tpu_custom_call``; interpret mode would have inlined plain HLO."""
    check("tpu_custom_call" in lowered.as_text(),
          f"{what}: no tpu_custom_call in the lowered program — the Pallas "
          f"kernel did not lower through Mosaic")


def paper_step_lowering(jax, exp):
    """The paper runtime's jitted round, lowered at the problem's shapes."""
    algo, prob = exp.algo, exp.problem
    algo._ensure_channels(prob.dim, prob.m_workers)
    return algo._step.lower(prob.w0, jax.numpy.zeros_like(prob.w0),
                            algo.init_comm_state(), prob.X_workers,
                            prob.y_workers, jax.random.PRNGKey(0))


def same_run(a, b):
    """Iterates, losses and ledger ints of two runs, exactly equal."""
    import jax
    import numpy as np

    (wa, ha), (wb, hb) = a, b
    leaves_a, leaves_b = jax.tree.leaves(wa), jax.tree.leaves(wb)
    iter_eq = len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(leaves_a, leaves_b))
    bits_eq = all(ha[k] == hb[k]
                  for k in ("uplink_bits", "downlink_bits", "total_bits"))
    return iter_eq, ha["loss"] == hb["loss"], bits_eq


def phase_main(jax, ExperimentSpec):
    from repro.telemetry import ANY, CompileCounter

    spec = ExperimentSpec(**MAIN_SPEC)
    t0 = time.perf_counter()
    exp = spec.build()
    prob = exp.problem
    jax.block_until_ready(prob.X_workers)
    log("main", f"{spec.problem}: {prob.X_full.shape[0]} x {prob.dim} "
                f"training rows, worker shards {prob.X_workers.shape} "
                f"(data made on the device in "
                f"{time.perf_counter() - t0:.3f}s)")
    check(prob.X_full.shape == (49_749, 300)
          and prob.X_workers.shape == (20, 2487, 300),
          f"w8a twin has the wrong size: {prob.X_full.shape}, "
          f"{prob.X_workers.shape}")

    with CompileCounter() as cc:
        t0 = time.perf_counter()
        w, hist = exp.run(n_steps=MAIN_ROUNDS)
        jax.block_until_ready(w)
        first_s = time.perf_counter() - t0
    log("main", f"first run ({MAIN_ROUNDS} rounds): {first_s:.3f}s wall, of "
                f"which {cc.compile_seconds(ANY):.3f}s backend compile "
                f"({cc.backend_compiles(ANY)} compiles)")
    for scope, row in cc.snapshot().items():
        log("main", f"  compile scope {scope}: {row}")
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        w2, _ = exp.run(n_steps=MAIN_ROUNDS)
        jax.block_until_ready(w2)
        warm_s = time.perf_counter() - t0
    log("main", f"warm run ({MAIN_ROUNDS} rounds): {warm_s:.3f}s wall "
                f"({MAIN_ROUNDS / warm_s:.2f} rounds/s), "
                f"{cc.compile_seconds(ANY):.3f}s compile inside the window "
                f"({cc.backend_compiles(ANY)} compiles)")

    losses = hist["loss"]
    check(len(losses) == MAIN_ROUNDS and all(map(math.isfinite, losses)),
          f"losses not finite: {losses}")
    check(losses[-1] < math.log(2),
          f"loss {losses[-1]} is not below f(w0) = ln 2")
    bps = exp.bits_per_step()
    want = MAIN_ROUNDS * (bps["uplink"] + bps["downlink"])
    check(hist["total_bits"] == want,
          f"ledger total {hist['total_bits']} != {MAIN_ROUNDS} x "
          f"bits_per_step {bps}")
    log("main", f"loss {losses[0]:.6f} -> {losses[-1]:.6f} (< ln 2), "
                f"test accuracy {hist['eval'][-1]:.4f}, ledger "
                f"{hist['total_bits']} bits = {MAIN_ROUNDS} x "
                f"{bps['uplink'] + bps['downlink']}")


def phase_kernels(jax, ExperimentSpec):
    import jax.numpy as jnp

    from repro.kernels import krum_scores_fused, sort_workers_fused, topk_compress

    # each kernel on its own, at the main path's shapes (m=20, d=300)
    x = jax.ShapeDtypeStruct((20, 300), jnp.float32)
    assert_kernel_lowered(
        jax.jit(jax.vmap(lambda v: topk_compress(v, 30))).lower(x),
        "topk_compress (vmapped, single tile)")
    assert_kernel_lowered(
        jax.jit(lambda f: krum_scores_fused(f, 4)).lower(x), "krum_scores_fused")
    assert_kernel_lowered(jax.jit(sort_workers_fused).lower(x),
                          "sort_workers_fused")

    for kern, xla in KERNEL_PAIRS:
        runs = []
        for overrides in (kern, xla):
            spec = ExperimentSpec(**{**MAIN_SPEC, **overrides})
            exp = spec.build()
            if overrides is kern:
                assert_kernel_lowered(paper_step_lowering(jax, exp),
                                      f"paper round with {overrides}")
            t0 = time.perf_counter()
            w, hist = exp.run(n_steps=KERNEL_ROUNDS)
            jax.block_until_ready(w)
            log("kernels", f"{overrides}: loss {hist['loss'][-1]:.6f}, "
                           f"{hist['total_bits']} bits "
                           f"({time.perf_counter() - t0:.3f}s incl. compile)")
            runs.append((w, hist))
        iter_eq, loss_eq, bits_eq = same_run(*runs)
        check(iter_eq and loss_eq and bits_eq,
              f"{kern} vs {xla}: iterates equal={iter_eq}, losses "
              f"equal={loss_eq}, wire bits equal={bits_eq}")
        log("kernels", f"{kern} == {xla}: identical iterates, losses and "
                       f"wire bits over {KERNEL_ROUNDS} rounds")


def phase_gridded(jax, ExperimentSpec):
    runs = []
    for overrides in GRIDDED_PAIR:
        exp = ExperimentSpec(**overrides).build()
        if overrides is GRIDDED_PAIR[0]:
            prob = exp.problem
            lowered = exp.step.lower(prob.w0, prob.batch,
                                     jax.random.PRNGKey(0))
            assert_kernel_lowered(lowered, f"mesh step with {overrides}")
        t0 = time.perf_counter()
        params, hist = exp.run(n_steps=KERNEL_ROUNDS)
        jax.block_until_ready(params)
        log("gridded", f"{overrides['compressor']}: loss "
                       f"{hist['loss'][-1]:.6f}, {hist['total_bits']} bits "
                       f"({time.perf_counter() - t0:.3f}s incl. compile)")
        runs.append((params, hist))
    iter_eq, loss_eq, bits_eq = same_run(*runs)
    check(iter_eq and loss_eq and bits_eq,
          f"gridded top-k vs XLA top-k at d=65536: iterates equal={iter_eq}, "
          f"losses equal={loss_eq}, wire bits equal={bits_eq}")
    log("gridded", f"topk_kernel:0.01 == topk:0.01 at d=65536: identical "
                   f"iterates, losses and wire bits over {KERNEL_ROUNDS} "
                   f"steps")


def phase_four_chips(jax):
    """The mesh training step with workers on the data axis of a (4, 1)
    host mesh, against the same steps with every array on device 0."""
    import numpy as np

    from repro.launch.train import run_training

    kw = dict(arch="mamba2-780m", preset="100m", m_workers=4, steps=3,
              log_every=1)
    runs = {}
    for sharded in (True, False):
        t0 = time.perf_counter()
        params, losses, kept = run_training(sharded=sharded, **kw)
        jax.block_until_ready(params)
        leaf = jax.tree.leaves(params)[0]
        n_dev = len(leaf.sharding.device_set)
        log("4chip", f"sharded={sharded}: losses {losses}, params on "
                     f"{n_dev} device(s), {time.perf_counter() - t0:.3f}s "
                     f"incl. compile")
        check(n_dev == (4 if sharded else 1),
              f"sharded={sharded} left params on {n_dev} devices")
        runs[sharded] = (losses, kept)
        del params
        # peak_bytes_in_use never resets: after the mesh run it is the
        # mesh step's peak, after the device-0 run device 0 shows the
        # larger of the two
        for d in jax.devices():
            stats = d.memory_stats() or {}
            log("4chip", f"after sharded={sharded}: device {d.id} "
                         f"peak_bytes_in_use="
                         f"{stats.get('peak_bytes_in_use', 'not reported')}")
    (l4, k4), (l1, k1) = runs[True], runs[False]
    check(np.allclose(l4, l1, rtol=1e-4),
          f"mesh losses {l4} differ from device-0 losses {l1} (rtol 1e-4)")
    check(len(k4) == len(k1) and all(np.array_equal(a, b)
                                     for a, b in zip(k4, k1)),
          f"keep masks differ: mesh {k4} vs device 0 {k1}")
    log("4chip", f"losses agree (rtol 1e-4), keep masks equal: "
                 f"{[m.tolist() for m in k4]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the host-mesh "
                         "training phase and its device-0 comparison")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the program is not next to this script "
              f"({SRC / 'repro'} is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    from repro.launch.cache import use_compile_cache

    log("setup", f"compile cache: {use_compile_cache()}")
    import jax

    device = device_check(jax, args.chips)
    if args.chips == 4:
        phase_four_chips(jax)
    else:
        from repro.api import ExperimentSpec

        phase_main(jax, ExperimentSpec)
        phase_kernels(jax, ExperimentSpec)
        phase_gridded(jax, ExperimentSpec)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
