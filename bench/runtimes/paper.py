"""Cells on the paper runtime: seeded solves of Algorithm 1 to ε.

Set-up builds the experiment the way every entry point does
(``ExperimentSpec(...).build()``: the data is made on the device from the
configuration's data seed), runs one round, which compiles the round or
loads it from the persistent cache, and warms up every program the window
runs with one short solve.  From the warm-up on nothing is written to the
cache (``cache_writes_off``), so every solve compiles what ``run()`` jits
anew in each call, as it does at JAX's default threshold.  The window then
calls ``Experiment.run`` back to back, one solve from w = 0 to ε per
call, with keys drawn from ``--seed``: the sweep path, which runs many
seeded solves in one process.  A solve that reaches the round cap before ε
has failed; the solve that the window's end cuts short is not counted, but
its rounds are.

Once the window has closed, a sample of the solves (the longest, and
others drawn from the seed) is repeated by the plain reference
(``bench/reference/paper.py``) over the same rounds and keys, and the
gaps are held to the cell's limits (``bench/limits/<cell>.json``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import random
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

TRACE_SECONDS = 2.0      # the traced part of a --trace 1 window


@dataclasses.dataclass
class Solve:
    index: int
    seconds: float
    rounds: int
    bits: int
    reached: bool
    w: object
    grad_norm: list
    loss: list


def solve_key(seed: int, index: int):
    """The key of the window's ``index``-th solve under ``--seed``."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), index)


def build(cell):
    from repro.api import ExperimentSpec

    cfg, tr = cell.config, cell.traffic
    spec = ExperimentSpec(**cfg["spec"], **cfg["solver"], **tr["spec"],
                          seed=cfg["data"]["seed"])
    exp = spec.build()
    jax.block_until_ready(exp.problem.X_workers)
    return exp


MIN_COMPILE_KEY = "jax_persistent_cache_min_compile_time_secs"


@contextlib.contextmanager
def cache_writes_off():
    """Nothing compiled inside the block is written to the persistent
    cache; what is in it is still read.

    ``run()`` jits the pooled loss and gradient anew in every call.  They
    compile well under JAX's one-second threshold, so the program never
    caches them; but about one compile in a few thousand takes longer, is
    written, and from then on every run in that checkout loads them
    instead of compiling, and the runs split in two.  Inside the block no
    compile, however slow, changes what a later run finds."""
    old = getattr(jax.config, MIN_COMPILE_KEY)
    jax.config.update(MIN_COMPILE_KEY, float("inf"))
    try:
        yield
    finally:
        jax.config.update(MIN_COMPILE_KEY, old)


def compile_round(exp):
    """One round from w0 through the program's ``step``: compiles the
    jitted round, written to the persistent cache when that takes a
    second or more, or loads it from there."""
    prob = exp.problem
    w, *_ = exp.algo.step(prob.w0, prob.X_workers, prob.y_workers,
                          jax.random.PRNGKey(0))
    jax.block_until_ready(w)


def warm_up(exp, cell, seed: int):
    """One short solve: compiles, or loads from the persistent cache, the
    round and every operation of the run loop at the window's shapes."""
    exp.run(n_steps=2, grad_tol=cell.traffic["grad_tol"],
            key=solve_key(seed, 2**31 - 1))


def step_module_name(exp) -> str:
    """The name XLA gives the jitted round, from its lowering."""
    algo, prob = exp.algo, exp.problem
    algo._ensure_channels(prob.dim, prob.m_workers)
    lowered = algo._step.lower(prob.w0, jnp.zeros_like(prob.w0),
                               algo.init_comm_state(), prob.X_workers,
                               prob.y_workers, jax.random.PRNGKey(0))
    head = lowered.as_text().split("\n", 1)[0]     # "module @<name> ..."
    return head.split("@", 1)[1].split()[0]


def window(exp, cell, seed: int, seconds: float, trace_dir=None):
    """Solves back to back for ``seconds``; returns them with the window's
    length, its rounds, its compile seconds and the solves started."""
    from repro.telemetry import ANY, CompileCounter

    tr = cell.traffic
    eps, cap = tr["grad_tol"], tr["round_cap"]
    solves, rounds, started = [], 0, 0
    tracing = trace_dir is not None
    if tracing:
        import jax.profiler as prof

        opts = prof.ProfileOptions()
        opts.python_tracer_level = 0
        prof.start_trace(trace_dir, profiler_options=opts)
        annotation = prof.TraceAnnotation("bench.traced_window")
        annotation.__enter__()
    cc = CompileCounter().activate()
    t_start = time.perf_counter()
    deadline = time.monotonic() + seconds
    while True:
        t0 = time.perf_counter()
        w, hist = exp.run(n_steps=cap, grad_tol=eps, key=solve_key(seed, started),
                          deadline=deadline)
        jax.block_until_ready(w)
        t1 = time.perf_counter()
        started += 1
        rounds += hist["rounds"]
        if not hist["truncated"]:
            solves.append(Solve(started - 1, t1 - t0, hist["rounds"],
                                hist["total_bits"], hist["grad_norm"][-1] <= eps,
                                w, hist["grad_norm"], hist["loss"]))
        if tracing and t1 - t_start >= min(TRACE_SECONDS, seconds):
            annotation.__exit__(None, None, None)
            prof.stop_trace()
            tracing = False
        if hist["truncated"] or time.monotonic() >= deadline:
            break
    t_end = time.perf_counter()
    cc.deactivate()
    return {"solves": solves, "started": started, "rounds": rounds,
            "seconds": t_end - t_start, "compile_s": cc.compile_seconds(ANY)}


def end_to_end(win, cell) -> dict:
    done = [s for s in win["solves"] if s.reached]
    out = {"rounds_per_s": win["rounds"] / win["seconds"]}
    if done:
        times = [s.seconds for s in done]
        out["time_to_eps_s"] = sum(times) / len(times)
        out["time_to_eps_p90_s"] = (statistics.quantiles(times, n=10, method="inclusive")[8]
                                    if len(times) > 1 else times[0])
        first = done[: cell.traffic["bits_solves"]]
        out["bits_to_eps"] = sum(s.bits for s in first) / len(first)
    return out


# ------------------------------------------------------------ correctness
def sample(solves, seed: int, k: int):
    """The longest solve and ``k - 1`` others drawn from the seed."""
    if not solves:
        return []
    longest = max(solves, key=lambda s: (s.rounds, -s.index))
    rest = [s for s in solves if s is not longest]
    return [longest] + random.Random(seed).sample(rest, min(k - 1, len(rest)))


def reference_for(cell, ref_mod, dtype=jnp.float32):
    cfg, tr = cell.config, cell.traffic
    X, y = ref_mod.make_data(cfg["data"], cfg["data"]["seed"])
    return ref_mod.Reference(cfg["data"], cfg["solver"], tr["reference"], X, y,
                             dtype=dtype)


def gaps(solve, ref_out, bits_per_round) -> dict:
    """The numbers compared for one solve against the reference over the
    same rounds and key."""
    wp = np.asarray(solve.w, np.float64)
    wr = np.asarray(ref_out["w"], np.float64)
    gp, gr = np.asarray(solve.grad_norm), np.asarray(ref_out["grad_norm"])
    lp, lr_ = np.asarray(solve.loss), np.asarray(ref_out["loss"])
    return {
        "iterate_gap": float(np.linalg.norm(wp - wr) / np.linalg.norm(wr)),
        "grad_norm_gap": float(np.max(np.abs(gp - gr) / gr)),
        "loss_gap": float(np.max(np.abs(lp - lr_) / np.abs(lr_))),
        "bits_gap": abs(solve.bits - solve.rounds * bits_per_round),
    }


def compare(cell, seed: int, solves, ref_mod, reference=None) -> dict:
    """Worst gaps over the sampled solves (``{}`` when none finished)."""
    picked = sample(solves, seed, cell.traffic["check_solves"])
    if not picked:
        return {}
    ref = reference or reference_for(cell, ref_mod)
    bpr = ref_mod.bits_per_round(ref.m, ref.d, cell.traffic["reference"].get("topk"))
    worst = {}
    with jax.default_matmul_precision("highest"):
        for s in picked:
            out = ref.solve(solve_key(seed, s.index), rounds=s.rounds)
            for k, v in gaps(s, out, bpr).items():
                worst[k] = max(worst.get(k, 0), v)
    return worst


def control_solves(cell, ref16, seed: int, bits_per_round: int):
    """The control in the program's place: the reference in bfloat16
    solves the window's first keys to ε, recorded as the program's solves
    are."""
    tr = cell.traffic
    out = []
    for i in range(tr["check_solves"]):
        r = ref16.solve(solve_key(seed, i), eps=tr["grad_tol"], cap=tr["round_cap"])
        out.append(Solve(i, 0.0, r["rounds"], r["rounds"] * bits_per_round,
                         r["grad_norm"][-1] <= tr["grad_tol"], r["w"],
                         r["grad_norm"], r["loss"]))
    return out


# ------------------------------------------------------------------- run
def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             load) -> dict:
    """One benchmark run of a paper-runtime cell; the harness's result."""
    phases = {"start": time.perf_counter() - t_start}
    exp = build(cell)
    phases["build"] = time.perf_counter() - t_start
    compile_round(exp)
    phases["round"] = time.perf_counter() - t_start
    with cache_writes_off():
        warm_up(exp, cell, seed)
        module = step_module_name(exp) if trace else None
        setup_s = time.perf_counter() - t_start
        phases["warm_up"] = setup_s

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        win = window(exp, cell, seed, seconds, trace_dir)
    memory = jax.devices()[0].memory_stats() or {}
    record = {"window": win, "setup_s": setup_s, "setup_phases": phases,
              "memory_peak_bytes": memory.get("peak_bytes_in_use")}
    if trace:
        tr_mod = load("trace")
        try:
            record["trace"] = tr_mod.reduce_trace(tr_mod.find_trace(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        record["step_module"] = module
        data = cell.config["data"]
        n = data["n_train"] // data["m_workers"]
        record["flops_per_round"] = load("flops").paper_round_flops(
            data["m_workers"], n, n * data["m_workers"], data["dim"])
    else:
        record["metrics"] = end_to_end(win, cell)
        record["metrics"]["setup_s"] = setup_s

    del exp
    record["compared"] = compare(cell, seed, win["solves"], load("reference/paper"))
    record["attempted"] = len(win["solves"])
    record["failed"] = sum(not s.reached for s in win["solves"])
    return record
