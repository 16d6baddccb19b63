"""Deterministic sharder + executor for a :class:`~repro.sweep.SweepPlan`.

Sharding is **by canonical cell hash**, not plan position:
``shard_entries(entries, i, N)`` keeps the cells whose
``int(hash, 16) % N == i``.  For any N the shards are provably disjoint
(each hash has exactly one residue) and covering (every hash has one),
and — because the hash is host-independent — two hosts planning the same
grid independently agree on who owns which cell without coordination.

Execution is failure-isolated and resumable: a cell whose hash is
already in the store is skipped (zero builds on a re-run), a cell that
raises is recorded as ``status="failed"`` with the error and the sweep
moves on, and a per-cell wall-time budget cooperatively truncates a
diverging run at the next round boundary (recorded in the metrics as
``truncated``).

**Executor pool** (``jobs > 1``): cells fan out over a spawn-context
:class:`~concurrent.futures.ProcessPoolExecutor` — spawn, not fork,
because a forked JAX runtime inherits locked XLA state.  Each worker
rebuilds its cell from the JSON spec dict (the same serde the store
uses) and runs the identical :func:`execute_cell`, so per-cell
deadline/failure-isolation semantics are unchanged; per-cell wall time
and the executor worker id are recorded as *volatile* store fields
(stripped on merge), so a pool run's **merged store is byte-identical
to the serial run's** — the CI ``async-smoke`` job asserts it with
``cmp``.  A worker process that dies outright (OOM, segfault) fails
only its own cell: the parent records a ``status="failed"`` line and
the pool keeps draining.  The pool is for CPU fan-out only: an
accelerator chip serves one process at a time, so ``jobs > 1`` is
refused unless ``JAX_PLATFORMS=cpu`` is set in the environment.
"""
from __future__ import annotations

import os
import time
import traceback
from typing import Callable, FrozenSet, Optional

import jax
import jax.numpy as jnp

from .grid import PlanEntry, SweepPlan
from .store import ResultStore
from ..telemetry import get_telemetry


# ---------------------------------------------------------------- shard
def shard_entries(entries, shard_index: int, num_shards: int):
    """The sub-list of cells shard ``shard_index`` of ``num_shards`` owns."""
    if not 0 <= shard_index < num_shards:
        raise ValueError(
            f"shard_index must be in [0, {num_shards}), got {shard_index}"
        )
    return [e for e in entries
            if int(e.hash, 16) % num_shards == shard_index]


# -------------------------------------------------------------- execute
def _build_and_run(entry: PlanEntry, deadline: Optional[float]) -> dict:
    """Build one cell and run it; returns the JSON-ready metrics dict.

    Split out so tests can inject failures, and so alternative executors
    can replace just this function.
    """
    tel = get_telemetry()
    with tel.span("sweep.cell.build", hash=entry.hash):
        exp = entry.spec.build()
    with tel.span("sweep.cell.run", hash=entry.hash):
        w, hist = exp.run(entry.n_steps, deadline=deadline)
    metrics = {k: v for k, v in hist.items()}
    w_star = getattr(exp.problem, "w_star", None)
    if w_star is not None and isinstance(w, jax.Array) and w.ndim == 1 \
            and w.shape == w_star.shape:
        metrics["w_err"] = float(
            jnp.linalg.norm(w - w_star) / jnp.linalg.norm(w_star)
        )
    if exp.problem.saddle_value is not None:
        metrics["saddle_value"] = exp.problem.saddle_value
    return metrics


def execute_cell(entry: PlanEntry, *,
                 time_budget_s: Optional[float] = None,
                 inject_fail: FrozenSet[str] = frozenset(),
                 log: Optional[Callable[[str], None]] = None) -> dict:
    """Run ONE cell to a complete store record — the unit both the
    serial loop and the pool workers execute, so their semantics cannot
    drift.  Never raises: failures are isolated into
    ``status="failed"`` records.  ``inject_fail`` (a set of cell hashes
    to fail deliberately) is the cross-process test seam — monkeypatches
    don't survive a spawn, a plain argument does.

    The record's ``wall_time_s`` and ``worker_id`` are *volatile* store
    fields: per-run diagnostics stripped on merge, keeping pool and
    serial merged stores byte-identical.
    """
    log = log or (lambda s: None)
    tel = get_telemetry()
    h = entry.hash
    deadline = (time.monotonic() + time_budget_s
                if time_budget_s is not None else None)
    t0 = time.monotonic()
    record = {"hash": h, "spec": entry.spec.to_dict(),
              "n_steps": entry.n_steps}
    with tel.span("sweep.cell", hash=h,
                  problem=entry.spec.problem,
                  aggregator=entry.spec.aggregator,
                  attack=entry.spec.attack):
        try:
            if h in inject_fail:
                raise RuntimeError(f"injected failure for cell {h}")
            record["status"] = "ok"
            record["metrics"] = _build_and_run(entry, deadline)
        except Exception as e:   # noqa: BLE001 — failure isolation is the point
            record["status"] = "failed"
            record.pop("metrics", None)
            record["error"] = f"{type(e).__name__}: {e}"
            log(f"[sweep] FAILED {h} {entry.spec.aggregator}/"
                f"{entry.spec.attack}: {record['error']}")
            log(traceback.format_exc(limit=3))
            if tel.enabled:
                tel.event("sweep.cell.failed", hash=h,
                          error=record["error"])
        else:
            if tel.enabled and record["metrics"].get("truncated"):
                tel.event("sweep.cell.truncated", hash=h)
    record["wall_time_s"] = round(time.monotonic() - t0, 3)
    record["worker_id"] = os.getpid()
    return record


def _pool_cell(spec_dict: dict, n_steps: int,
               time_budget_s: Optional[float],
               inject_fail: FrozenSet[str]) -> dict:
    """Pool-worker entry point: rebuild the cell from its JSON spec dict
    (the store's own serde — nothing unpicklable crosses the process
    boundary) and execute it.  Runs in a spawn-context child."""
    from ..api import ExperimentSpec

    entry = PlanEntry(ExperimentSpec.from_dict(spec_dict), int(n_steps))
    return execute_cell(entry, time_budget_s=time_budget_s,
                        inject_fail=inject_fail)


def _cell_log_line(record: dict, entry: PlanEntry) -> str:
    return (f"[sweep] {record['status']} {record['hash']} "
            f"problem={entry.spec.problem} agg={entry.spec.aggregator} "
            f"attack={entry.spec.attack} comp={entry.spec.compressor} "
            f"({record['wall_time_s']:.1f}s)")


def run_plan(
    plan: SweepPlan,
    store: ResultStore,
    *,
    shard_index: int = 0,
    num_shards: int = 1,
    time_budget_s: Optional[float] = None,
    limit: Optional[int] = None,
    retry_failed: bool = False,
    retry_truncated: bool = False,
    jobs: int = 1,
    log: Optional[Callable[[str], None]] = None,
    _inject_fail: FrozenSet[str] = frozenset(),
) -> dict:
    """Run this shard of the plan into ``store``; returns the summary
    ``{"built": …, "cached": …, "failed": …, "shard": …, "total": …}``.

    ``limit`` caps the number of cells *built* this invocation (the CI
    smoke lever, and how tests simulate a killed run); ``retry_failed``
    re-runs cells whose stored status is ``"failed"``, and
    ``retry_truncated`` re-runs cells a previous wall-time budget cut
    short, instead of treating either as done.

    ``jobs > 1`` runs the shard's cells on a spawn-context process pool
    (see module doc).  Per-cell semantics are identical to serial; the
    one behavioural difference is ``limit``, which caps *submissions*
    in pool mode (cells in flight when the cap is reached still finish)
    rather than successful builds — resumability makes the distinction
    harmless (the next invocation skips whatever completed).
    """
    if jobs > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        # read from the environment: asking JAX for its backend here
        # would make this parent take the accelerator before any child
        # starts, and a chip serves one process at a time
        raise RuntimeError(
            f"jobs={jobs} starts {jobs} JAX processes, but an accelerator "
            f"chip serves one process at a time — run with jobs=1, or set "
            f"JAX_PLATFORMS=cpu to fan cells out over CPU processes"
        )
    log = log or (lambda s: None)
    tel = get_telemetry()
    entries = shard_entries(plan.entries, shard_index, num_shards)
    built = cached = failed = 0
    with tel.span("sweep.shard", shard=shard_index, num_shards=num_shards,
                  cells=len(entries), jobs=jobs):
        todo = []
        for entry in entries:
            prior = store.get(entry.hash)
            done = prior is not None
            if done and retry_failed and prior.get("status") == "failed":
                done = False
            if done and retry_truncated \
                    and prior.get("metrics", {}).get("truncated"):
                done = False
            if done:
                cached += 1
            else:
                todo.append(entry)

        if jobs <= 1:
            for entry in todo:
                if limit is not None and built >= limit:
                    break
                record = execute_cell(
                    entry, time_budget_s=time_budget_s,
                    inject_fail=_inject_fail, log=log,
                )
                if record["status"] == "ok":
                    built += 1
                else:
                    failed += 1
                with tel.span("sweep.cell.store", hash=record["hash"]):
                    store.append(record)
                log(_cell_log_line(record, entry))
        else:
            built, failed = _run_pool(
                todo if limit is None else todo[:limit],
                store, jobs=jobs, time_budget_s=time_budget_s,
                inject_fail=_inject_fail, log=log,
            )
    return {"built": built, "cached": cached, "failed": failed,
            "shard": (shard_index, num_shards), "total": len(entries)}


def _run_pool(todo, store: ResultStore, *, jobs: int,
              time_budget_s: Optional[float],
              inject_fail: FrozenSet[str],
              log: Callable[[str], None]) -> tuple:
    """Drain ``todo`` through a spawn-context process pool; append each
    record as it completes (the store is hash-keyed and merge-sorted, so
    completion order never shows in merged bytes).  A worker that dies
    outright fails only its own cell."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor, as_completed

    tel = get_telemetry()
    built = failed = 0
    if not todo:
        return built, failed
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=min(jobs, len(todo)),
                             mp_context=ctx) as pool:
        futures = {
            pool.submit(_pool_cell, entry.spec.to_dict(), entry.n_steps,
                        time_budget_s, inject_fail): entry
            for entry in todo
        }
        for fut in as_completed(futures):
            entry = futures[fut]
            try:
                record = fut.result()
            except Exception as e:   # a worker process died outright
                record = {"hash": entry.hash,
                          "spec": entry.spec.to_dict(),
                          "n_steps": entry.n_steps, "status": "failed",
                          "error": f"{type(e).__name__}: {e}",
                          "wall_time_s": 0.0, "worker_id": None}
                log(f"[sweep] POOL-FAILED {entry.hash}: "
                    f"{record['error']}")
                if tel.enabled:
                    tel.event("sweep.cell.failed", hash=entry.hash,
                              error=record["error"])
            if record["status"] == "ok":
                built += 1
            else:
                failed += 1
            with tel.span("sweep.cell.store", hash=record["hash"]):
                store.append(record)
            log(_cell_log_line(record, entry))
    return built, failed
