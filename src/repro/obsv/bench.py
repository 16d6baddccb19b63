"""Benchmark regression ledger: fingerprinted records, threshold diffs.

Every ``benchmarks/run.py`` invocation appends one record per benchmark
entry to ``BENCH_<name>.json`` (a JSON array — the ledger), carrying a
host **fingerprint** (git sha, jax/jaxlib versions, platform, UTC
timestamp) plus the entry's **scalars**: the deterministic quantities a
regression in is a bug (exact wire bits, rounds-to-ε, center bytes) and
the informational ones (kernel vs XLA wall-clock) that only gate when
asked.

``compare_ledgers`` diffs the newest record of each current ledger
against the newest committed baseline record, classifying every scalar
key by name:

* ``bits`` / ``bytes``  — exact static ints; regression when
  ``current > baseline × bits_ratio`` (default 1.5×, so an accidental
  2× wire blow-up always trips);
* ``rounds``            — convergence counts; lenient
  ``rounds_ratio`` (default 2×) plus a small absolute slack, since a
  platform's float drift can move an ε-crossing by a round;
* ``us`` / ``time``     — wall-clock; **skipped by default** (CI
  machines are not comparable), opt in with ``check_times``;
* anything else         — informational, never gates.

Missing keys or missing current ledgers are warnings (errors under
``strict``) — a benchmark that silently stops reporting a number is a
different failure mode from one that regresses it.
"""
from __future__ import annotations

import json
import os
import subprocess
from datetime import datetime, timezone
from typing import Optional


def fingerprint() -> dict:
    """Who/where/when of one benchmark run (everything best-effort —
    a missing git binary must not fail the benchmark)."""
    import platform as _platform

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    import jax
    import jaxlib

    return {
        "git_sha": sha,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": _platform.platform(),
        "python": _platform.python_version(),
        "timestamp_utc": datetime.now(timezone.utc).isoformat(),
    }


def _num(v) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return v


def extract_scalars(name: str, entry) -> dict:
    """Flatten one ``all_results`` entry to the ledger's scalar dict
    (dotted keys).  Unknown entries return {} — no ledger file."""
    out = {}

    def put(key, v):
        v = _num(v)
        if v is not None:
            out[key] = v

    if name in ("fig3", "fig12") and isinstance(entry, dict):
        for cell, hist in entry.items():
            if isinstance(hist, dict) and hist.get("loss"):
                put(f"{cell}.final_loss", hist["loss"][-1])
                put(f"{cell}.n_rounds", len(hist["loss"]))
    elif name == "table1" and isinstance(entry, list):
        for row in entry:
            key = f"{row.get('attack')}.alpha={row.get('alpha')}"
            put(f"{key}.newton_rounds", row.get("newton_rounds"))
            put(f"{key}.pgd_rounds", row.get("pgd_rounds"))
            put(f"{key}.newton_uplink_bits", row.get("newton_uplink_bits"))
            put(f"{key}.newton_downlink_bits",
                row.get("newton_downlink_bits"))
    elif name == "table1_compression" and isinstance(entry, list):
        for row in entry:
            key = str(row.get("compressor"))
            put(f"{key}.rounds", row.get("rounds"))
            put(f"{key}.uplink_bits_per_round",
                row.get("uplink_bits_per_round"))
            put(f"{key}.downlink_bits_per_round",
                row.get("downlink_bits_per_round"))
            put(f"{key}.uplink_bits", row.get("uplink_bits"))
            put(f"{key}.downlink_bits", row.get("downlink_bits"))
    elif name == "bits_to_eps" and isinstance(entry, list):
        for row in entry:
            key = str(row.get("compressor"))
            for eps, bits in (row.get("bits_to_eps") or {}).items():
                put(f"{key}.bits@eps={eps}", bits)
    elif name == "headtohead" and isinstance(entry, list):
        for row in entry:
            key = (f"{row.get('attack')}.{row.get('aggregator')}"
                   f".alpha={row.get('alpha')}")
            for col, v in row.items():
                if "_rounds@" in col or "_bits@" in col:
                    put(f"{key}.{col}", v)
    elif name == "topk_kernel_timing" and isinstance(entry, list):
        for row in entry:
            key = f"d={row.get('d')}"
            put(f"{key}.kernel_us", row.get("kernel_us"))
            put(f"{key}.xla_topk_us", row.get("xla_topk_us"))
    elif name == "agg_roofline" and isinstance(entry, list):
        for row in entry:
            key = f"{row.get('rule')}.m={row.get('m')}.d={row.get('d')}"
            put(f"{key}.kernel_us", row.get("kernel_us"))
            put(f"{key}.xla_dense_us", row.get("xla_dense_us"))
            put(f"{key}.center_bytes_sparse", row.get("center_bytes_sparse"))
            put(f"{key}.center_bytes_dense", row.get("center_bytes_dense"))
    elif name == "saddle_escape" and isinstance(entry, dict):
        for variant, hist in entry.items():
            if isinstance(hist, dict) and hist.get("loss"):
                put(f"{variant}.final_loss", hist["loss"][-1])
    elif name == "async_staleness" and isinstance(entry, dict):
        for cell in entry.get("cells", ()):
            key = (f"stale={cell.get('staleness')}"
                   f".p={cell.get('participation')}"
                   f".alpha={cell.get('alpha')}")
            put(f"{key}.uplink_bits", cell.get("uplink_bits"))
            put(f"{key}.saddle_escape_step", cell.get("saddle_escape_step"))
    return out


def append_ledger(ledger_dir: str, name: str, scalars: dict,
                  meta: dict) -> str:
    """Append one fingerprinted record to ``BENCH_<name>.json`` (created
    on first use).  Returns the ledger path."""
    os.makedirs(ledger_dir, exist_ok=True)
    path = os.path.join(ledger_dir, f"BENCH_{name}.json")
    records = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                records = json.load(f)
        except (OSError, json.JSONDecodeError):
            records = []
        if not isinstance(records, list):
            records = []
    records.append({"meta": meta, "scalars": scalars})
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(records, f, indent=1)
    os.replace(tmp, path)
    return path


def _classify(key: str) -> str:
    low = key.lower()
    if low.endswith("_us") or "time" in low:
        return "time"
    if "bits" in low or "bytes" in low:
        return "bits"
    if "rounds" in low:
        return "rounds"
    return "info"


def _latest(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            records = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(records, list) or not records:
        return None
    return records[-1]


def compare_ledgers(current_dir: str, baseline_dir: str, *,
                    bits_ratio: float = 1.5, rounds_ratio: float = 2.0,
                    rounds_slack: int = 2, times_ratio: float = 5.0,
                    check_times: bool = False, strict: bool = False):
    """Diff current ledgers against committed baselines.

    Returns ``(problems, warnings, n_compared)`` — nonempty problems
    mean CI should fail; warnings are missing entries/keys (promoted to
    problems under ``strict``)."""
    problems, warnings = [], []
    n_compared = 0
    names = sorted(
        fn[len("BENCH_"):-len(".json")]
        for fn in os.listdir(baseline_dir)
        if fn.startswith("BENCH_") and fn.endswith(".json")
    )
    if not names:
        problems.append(f"{baseline_dir}: no BENCH_*.json baselines")
    for name in names:
        base = _latest(os.path.join(baseline_dir, f"BENCH_{name}.json"))
        cur_path = os.path.join(current_dir, f"BENCH_{name}.json")
        cur = _latest(cur_path)
        if base is None:
            warnings.append(f"{name}: unreadable baseline ledger")
            continue
        if cur is None:
            warnings.append(f"{name}: no current ledger at {cur_path}")
            continue
        base_s, cur_s = base.get("scalars", {}), cur.get("scalars", {})
        for key, bval in sorted(base_s.items()):
            cls = _classify(key)
            if cls == "info":
                continue
            if cls == "time" and not check_times:
                continue
            cval = cur_s.get(key)
            if cval is None:
                warnings.append(f"{name}/{key}: present in baseline, "
                                f"missing from current run")
                continue
            n_compared += 1
            if cls == "bits":
                limit = bval * bits_ratio
            elif cls == "rounds":
                limit = bval * rounds_ratio + rounds_slack
            else:
                limit = bval * times_ratio
            if cval > limit:
                problems.append(
                    f"{name}/{key}: {cval:g} vs baseline {bval:g} "
                    f"(limit {limit:g}, class {cls}) — REGRESSION"
                )
    if strict:
        problems += warnings
        warnings = []
    return problems, warnings, n_compared
