"""Roofline benchmark: aggregates the dry-run sweep (results/dryrun/*.jsonl)
into the per-(arch × shape × mesh) three-term table EXPERIMENTS.md §Roofline
publishes, plus micro-benchmarks of the Pallas kernels (interpret mode —
CPU wall time is NOT TPU time; the derived column is the roofline estimate).
"""
from __future__ import annotations

import glob
import json
import os
import time

import jax
import jax.numpy as jnp

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")


def load_records(results_dir=RESULTS_DIR):
    recs = []
    for f in sorted(glob.glob(os.path.join(results_dir, "dryrun*", "*.jsonl"))):
        with open(f) as fh:
            for ln in fh:
                try:
                    recs.append(json.loads(ln))
                except json.JSONDecodeError:
                    pass
    # newest record per (arch, shape, mesh) wins
    dedup = {}
    for r in recs:
        dedup[(r["arch"], r["shape"], r.get("mesh", "?"))] = r
    return list(dedup.values())


def roofline_table(records=None):
    records = records if records is not None else load_records()
    rows = []
    for r in sorted(records, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        if r["status"] != "ok":
            rows.append({
                "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
                "status": r["status"], "reason": r.get("reason", r.get("error", "")),
            })
            continue
        roof = r["roofline"]
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "status": "ok",
            "compute_ms": roof["compute_s"] * 1e3,
            "memory_ms": roof["memory_s"] * 1e3,
            "collective_ms": roof["collective_s"] * 1e3,
            "dominant": roof["dominant"],
            "useful_flops_ratio": r["useful_flops_ratio"],
            "bytes_per_device_GB": r["bytes_per_device"] / 1e9,
        })
    return rows


def kernel_microbench(n_iter=3):
    """CPU interpret-mode wall time (correctness-path cost only) + the
    TPU-roofline-derived time for each kernel's benchmark shape."""
    from repro.kernels import flash_attention, rmsnorm
    from repro.launch.hlo import HBM_BW, PEAK_FLOPS

    out = []

    B, H, S, Dh = 1, 4, 512, 64
    q = jnp.ones((B, H, S, Dh), jnp.float32)
    f = lambda: flash_attention(q, q, q, causal=True).block_until_ready()
    f()
    t0 = time.time()
    for _ in range(n_iter):
        f()
    flops = 4 * B * H * S * S * Dh / 2  # causal
    out.append(("flash_attention_512", (time.time() - t0) / n_iter * 1e6,
                flops / PEAK_FLOPS * 1e6))

    x = jnp.ones((512, 1024), jnp.float32)
    w = jnp.ones((1024,), jnp.float32)
    f = lambda: rmsnorm(x, w).block_until_ready()
    f()
    t0 = time.time()
    for _ in range(n_iter):
        f()
    out.append(("rmsnorm_512x1024", (time.time() - t0) / n_iter * 1e6,
                (512 * 1024 * 8) / HBM_BW * 1e6))
    return out
