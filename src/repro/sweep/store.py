"""Resumable result store: append-only JSONL keyed by a canonical spec hash.

One line = one completed (or failed) sweep cell:

    {"hash": "…", "spec": {…}, "n_steps": T, "status": "ok"|"failed",
     "metrics": {…full history incl. exact WireLedger ints…},
     "wall_time_s": 1.23, "worker_id": 4242, "error": "…"}

The **hash** is the identity of a cell: SHA-256 over the canonical JSON
of ``{"n_steps": T, "spec": spec.to_dict()}`` (sorted keys, no
whitespace), truncated to 16 hex chars.  It covers everything that
determines the numbers — the full :class:`~repro.api.ExperimentSpec`
(problem, seed, channels, aggregator, attack, …) *and* the round budget
— and nothing that doesn't, so the same cell planned on any host at any
time hashes identically.  ``tests/test_sweep.py`` pins a golden value;
changing the canonicalization is a store-format break and must bump
:data:`STORE_VERSION`.

Resumability: a :class:`ResultStore` opened on an existing file loads
its hashes, and the runner skips any cell whose hash is present —
re-running a finished sweep performs **zero** experiment builds.
``merge`` unions shard files from multiple hosts into one canonical
store: records are deduplicated by hash, **volatile** per-host fields
(wall time) are stripped, and lines are sorted by hash — so merging the
same set of cells always produces byte-identical output regardless of
which host ran which shard, or where a killed run was resumed.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Iterable, Optional

from ..api import ExperimentSpec

STORE_VERSION = 1

#: per-host / per-run diagnostics that must not affect merged-store bytes
#: (wall time and the executor-pool worker pid vary run to run; stripping
#: them is what keeps a ``--jobs N`` pool merge byte-identical to serial)
VOLATILE_KEYS = ("wall_time_s", "worker_id")


# ------------------------------------------------------------------ hash
def canonical_json(obj) -> str:
    """Deterministic JSON: sorted keys, minimal separators."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def spec_hash(spec, n_steps: int) -> str:
    """Canonical identity of one sweep cell (spec + round budget)."""
    if isinstance(spec, ExperimentSpec):
        spec = spec.to_dict()
    payload = canonical_json({"n_steps": int(n_steps), "spec": spec})
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def canonical_record(record: dict) -> dict:
    """A record with volatile per-host fields stripped (merge form)."""
    return {k: v for k, v in record.items() if k not in VOLATILE_KEYS}


# ------------------------------------------------------------------ store
class ResultStore:
    """Append-only JSONL result store; ``path=None`` keeps it in memory
    (benchmark thin-views that don't need resume across processes)."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._records: list[dict] = []
        self._by_hash: dict[str, dict] = {}
        if path is not None and os.path.exists(path):
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        self._index(json.loads(line))

    def _index(self, rec: dict) -> None:
        h = rec["hash"]
        self._records.append(rec)
        # last-write-wins in-process (a retried failure overwrites), but
        # append-only on disk — merge dedups by first occurrence
        self._by_hash[h] = rec

    # -- writing ---------------------------------------------------------
    def append(self, record: dict) -> None:
        if "hash" not in record:
            raise ValueError("store records need a 'hash' key")
        if self.path is not None:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            with open(self.path, "a") as f:
                f.write(canonical_json(record) + "\n")
        self._index(record)

    # -- reading ---------------------------------------------------------
    def __contains__(self, h: str) -> bool:
        return h in self._by_hash

    def __len__(self) -> int:
        return len(self._by_hash)

    def get(self, h: str) -> Optional[dict]:
        return self._by_hash.get(h)

    def hashes(self) -> set:
        return set(self._by_hash)

    def records(self) -> list[dict]:
        """Deduplicated records (latest per hash), insertion order."""
        seen = set()
        out = []
        for rec in self._records:
            if rec["hash"] in seen:
                continue
            seen.add(rec["hash"])
            out.append(self._by_hash[rec["hash"]])
        return out

    def ok_records(self) -> list[dict]:
        return [r for r in self.records() if r.get("status") == "ok"]


# ------------------------------------------------------------------ merge
def merge(paths: Iterable[str], out_path: str) -> int:
    """Union shard stores into one canonical store (see module doc).

    Duplicate hashes keep the first occurrence in sorted-``paths`` order;
    the output is volatile-stripped, hash-sorted, canonical JSONL —
    byte-identical for the same set of cells however they were produced.
    Returns the number of merged records.  Every input path must exist —
    a typo'd shard file must not silently produce a half-empty store.
    """
    paths = sorted(paths)
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        raise FileNotFoundError(f"shard store(s) not found: {missing}")
    by_hash: dict[str, dict] = {}
    for path in paths:
        for rec in ResultStore(path).records():
            by_hash.setdefault(rec["hash"], rec)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        for h in sorted(by_hash):
            f.write(canonical_json(canonical_record(by_hash[h])) + "\n")
    return len(by_hash)
