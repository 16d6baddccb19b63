"""Event schema for the telemetry JSONL stream (schema-versioned).

Every line of ``events.jsonl`` is one JSON object.  The stream is
append-only and mergeable like the sweep store: concatenating two
streams yields a valid stream (events carry their own wall-clock
timestamps; no line references another line by position).

Base keys (every event):

* ``v``    — schema version (int, one of :data:`ACCEPTED_VERSIONS`;
  writers stamp :data:`SCHEMA_VERSION`)
* ``kind`` — one of :data:`KINDS`
* ``name`` — dotted event name (``"sweep.cell"``, ``"newton.round"``)
* ``ts``   — seconds since the process enabled telemetry (monotonic)
* ``wall`` — wall-clock unix seconds (for cross-process merge ordering)

Per-kind required keys (on top of the base):

* ``span``    — ``dur_s`` (float ≥ 0); optional ``args`` dict and
  ``parent`` (the name of the enclosing span on the same thread)
* ``counter`` — ``value`` (number), the post-increment running total
* ``gauge``   — ``value`` (number)
* ``hist``    — ``value`` (number), one observation
* ``round``   — ``step`` (int ≥ 0); the flattened
  :class:`~repro.telemetry.RoundRecord` fields ride as optional keys
  (v2 adds ``center_bytes``, int ≥ 0, the center aggregation-path bytes,
  and ``agg_kernel``, one of ``"sparse"``/``"fused"``/``"dense"``; v3
  adds the async-runtime fields ``cohort_size``/``n_arrivals``/
  ``queue_depth`` (ints ≥ 0), ``participation`` (number), and
  ``arrival_staleness``, a list of ints ≥ 0 — per-arrival ages; v4 adds
  the per-worker forensic fields, every list indexed by worker id
  ``0 … m−1``: ``worker_bits`` (ints ≥ 0, exact uplink bits each worker
  paid this round), ``worker_delta`` (number-or-null, each worker's
  measured δ̂), ``worker_keep`` (number-or-null, the aggregator's keep
  weight — null when the worker did not participate/arrive),
  ``worker_norms`` (number-or-null, update norms), ``worker_staleness``
  (int ≥ 0 or null, arrival age), ``suspicion`` (numbers in [0, 1], the
  EWMA suspicion score), and ``byzantine_true`` (ints ≥ 0, the planted
  Byzantine worker ids the attack hook knows))
* ``wire``    — ``ledger_id`` (int), ``uplink`` (int ≥ 0),
  ``downlink`` (int ≥ 0), ``rounds`` (int ≥ 0): ONE ledger-record call,
  exact integer bits; v3 adds ``seq`` (int ≥ 0, the ledger generation's
  per-record sequence id) and ``pid`` (int ≥ 0, the emitting process)
* ``ledger``  — ``ledger_id``, ``uplink_bits``, ``downlink_bits``,
  ``total_bits``, ``rounds``: a ledger snapshot (end-of-run totals);
  the wire events from the same ledger generation — grouped
  ``(pid, ledger_id)`` — must sum to it exactly, and when the snapshot
  carries ``n_records`` (v3) their ``seq`` ids must cover exactly
  ``0 … n_records−1`` in ANY order (checked by
  ``python -m repro.telemetry validate --check-wire``)
* ``compile`` — ``event`` (the JAX monitoring event tail, e.g.
  ``backend_compile``), ``dur_s``; optional ``scope`` (the
  :func:`~repro.telemetry.compile_scope` label active during the
  compile) and ``trigger``/``shape_key`` on explicit re-trace events
* ``event``   — free-form (base keys only)

The validator is hand-rolled (no jsonschema dependency); the
:data:`EVENT_SCHEMA` dict is the same contract in JSON-Schema notation
for documentation and external tooling.
"""
from __future__ import annotations

from numbers import Number

#: version writers stamp on new events (4: per-worker forensic round
#: fields ``worker_bits``/``worker_delta``/``worker_keep``/
#: ``worker_norms``/``worker_staleness``/``suspicion``/
#: ``byzantine_true``)
SCHEMA_VERSION = 4
#: versions the validator accepts — each older version carries a strict
#: subset of the newer optional fields, so old streams stay valid forever
ACCEPTED_VERSIONS = (1, 2, 3, 4)

KINDS = ("event", "span", "counter", "gauge", "hist", "round", "wire",
         "ledger", "compile")

#: JSON-Schema rendering of the contract (documentation / external tools).
EVENT_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "title": "repro.telemetry event",
    "type": "object",
    "required": ["v", "kind", "name", "ts", "wall"],
    "properties": {
        "v": {"enum": list(ACCEPTED_VERSIONS)},
        "kind": {"enum": list(KINDS)},
        "name": {"type": "string", "minLength": 1},
        "ts": {"type": "number", "minimum": 0},
        "wall": {"type": "number"},
        "dur_s": {"type": "number", "minimum": 0},
        "value": {"type": ["number", "integer"]},
        "step": {"type": "integer", "minimum": 0},
        "ledger_id": {"type": "integer", "minimum": 0},
        "uplink": {"type": "integer", "minimum": 0},
        "downlink": {"type": "integer", "minimum": 0},
        "rounds": {"type": "integer", "minimum": 0},
        "uplink_bits": {"type": "integer", "minimum": 0},
        "downlink_bits": {"type": "integer", "minimum": 0},
        "total_bits": {"type": "integer", "minimum": 0},
        "event": {"type": "string"},
        "args": {"type": "object"},
        "parent": {"type": "string", "minLength": 1},
        "center_bytes": {"type": "integer", "minimum": 0},
        "agg_kernel": {"enum": ["sparse", "fused", "dense"]},
        "seq": {"type": "integer", "minimum": 0},
        "pid": {"type": "integer", "minimum": 0},
        "n_records": {"type": "integer", "minimum": 0},
        "cohort_size": {"type": "integer", "minimum": 0},
        "n_arrivals": {"type": "integer", "minimum": 0},
        "queue_depth": {"type": "integer", "minimum": 0},
        "participation": {"type": "number"},
        "arrival_staleness": {"type": "array",
                              "items": {"type": "integer", "minimum": 0}},
        "worker_bits": {"type": "array",
                        "items": {"type": "integer", "minimum": 0}},
        "worker_delta": {"type": "array",
                         "items": {"type": ["number", "null"]}},
        "worker_keep": {"type": "array",
                        "items": {"type": ["number", "null"]}},
        "worker_norms": {"type": "array",
                         "items": {"type": ["number", "null"]}},
        "worker_staleness": {"type": "array",
                             "items": {"type": ["integer", "null"],
                                       "minimum": 0}},
        "suspicion": {"type": "array",
                      "items": {"type": "number",
                                "minimum": 0, "maximum": 1}},
        "byzantine_true": {"type": "array",
                           "items": {"type": "integer", "minimum": 0}},
    },
    "allOf": [
        {"if": {"properties": {"kind": {"const": "span"}}},
         "then": {"required": ["dur_s"]}},
        {"if": {"properties": {"kind": {"enum": ["counter", "gauge", "hist"]}}},
         "then": {"required": ["value"]}},
        {"if": {"properties": {"kind": {"const": "round"}}},
         "then": {"required": ["step"]}},
        {"if": {"properties": {"kind": {"const": "wire"}}},
         "then": {"required": ["ledger_id", "uplink", "downlink", "rounds"]}},
        {"if": {"properties": {"kind": {"const": "ledger"}}},
         "then": {"required": ["ledger_id", "uplink_bits", "downlink_bits",
                               "total_bits", "rounds"]}},
        {"if": {"properties": {"kind": {"const": "compile"}}},
         "then": {"required": ["event", "dur_s"]}},
    ],
}

_REQUIRED_BY_KIND = {
    "span": ("dur_s",),
    "counter": ("value",),
    "gauge": ("value",),
    "hist": ("value",),
    "round": ("step",),
    "wire": ("ledger_id", "uplink", "downlink", "rounds"),
    "ledger": ("ledger_id", "uplink_bits", "downlink_bits",
               "total_bits", "rounds"),
    "compile": ("event", "dur_s"),
    "event": (),
}

_NONNEG_INTS = ("step", "ledger_id", "uplink", "downlink", "rounds",
                "uplink_bits", "downlink_bits", "total_bits",
                "center_bytes", "seq", "pid", "n_records",
                "cohort_size", "n_arrivals", "queue_depth")

_AGG_KERNELS = ("sparse", "fused", "dense")


def _is_number(v) -> bool:
    return isinstance(v, Number) and not isinstance(v, bool)


def _is_nonneg_int(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool) and v >= 0


#: v4 per-worker list fields → per-item predicate + description
_WORKER_LISTS = {
    "worker_bits": (_is_nonneg_int, "non-negative ints"),
    "worker_delta": (lambda v: v is None or _is_number(v),
                     "numbers or nulls"),
    "worker_keep": (lambda v: v is None or _is_number(v),
                    "numbers or nulls"),
    "worker_norms": (lambda v: v is None or _is_number(v),
                     "numbers or nulls"),
    "worker_staleness": (lambda v: v is None or _is_nonneg_int(v),
                         "non-negative ints or nulls"),
    "suspicion": (lambda v: _is_number(v) and 0 <= v <= 1,
                  "numbers in [0, 1]"),
    "byzantine_true": (_is_nonneg_int, "non-negative ints"),
}


def validate_event(obj) -> list:
    """Return a list of problem strings (empty ⇒ the event is valid)."""
    errors = []
    if not isinstance(obj, dict):
        return [f"event must be an object, got {type(obj).__name__}"]
    if obj.get("v") not in ACCEPTED_VERSIONS:
        errors.append(f"v must be one of {ACCEPTED_VERSIONS}, "
                      f"got {obj.get('v')!r}")
    kind = obj.get("kind")
    if kind not in KINDS:
        errors.append(f"kind must be one of {KINDS}, got {kind!r}")
    name = obj.get("name")
    if not isinstance(name, str) or not name:
        errors.append(f"name must be a non-empty string, got {name!r}")
    for key in ("ts", "wall"):
        if not isinstance(obj.get(key), Number) \
                or isinstance(obj.get(key), bool):
            errors.append(f"{key} must be a number, got {obj.get(key)!r}")
    for key in _REQUIRED_BY_KIND.get(kind, ()):
        if key not in obj:
            errors.append(f"kind={kind!r} requires key {key!r}")
    if "dur_s" in obj:
        if not isinstance(obj["dur_s"], Number) or isinstance(
                obj["dur_s"], bool) or obj["dur_s"] < 0:
            errors.append(f"dur_s must be a number ≥ 0, got {obj['dur_s']!r}")
    if "value" in obj:
        if not isinstance(obj["value"], Number) \
                or isinstance(obj["value"], bool):
            errors.append(f"value must be a number, got {obj['value']!r}")
    for key in _NONNEG_INTS:
        if key in obj and (not isinstance(obj[key], int)
                           or isinstance(obj[key], bool) or obj[key] < 0):
            errors.append(f"{key} must be a non-negative int, "
                          f"got {obj[key]!r}")
    if "args" in obj and not isinstance(obj["args"], dict):
        errors.append(f"args must be an object, got {type(obj['args'])}")
    if "agg_kernel" in obj and obj["agg_kernel"] not in _AGG_KERNELS:
        errors.append(f"agg_kernel must be one of {_AGG_KERNELS}, "
                      f"got {obj['agg_kernel']!r}")
    if "participation" in obj:
        if not isinstance(obj["participation"], Number) \
                or isinstance(obj["participation"], bool):
            errors.append(f"participation must be a number, "
                          f"got {obj['participation']!r}")
    if "arrival_staleness" in obj:
        ages = obj["arrival_staleness"]
        if not isinstance(ages, list) or any(
                not isinstance(a, int) or isinstance(a, bool) or a < 0
                for a in ages):
            errors.append("arrival_staleness must be a list of "
                          f"non-negative ints, got {ages!r}")
    for key, (ok, what) in _WORKER_LISTS.items():
        if key in obj:
            vals = obj[key]
            if not isinstance(vals, list) or not all(ok(v) for v in vals):
                errors.append(f"{key} must be a list of {what}, "
                              f"got {vals!r}")
    return errors


def validate_stream(lines) -> list:
    """Validate an iterable of JSONL lines; returns
    ``[(line_no, problem), …]`` (empty ⇒ the whole stream is valid).
    Blank lines are skipped; a truncated final line (a live writer) is
    reported so callers can choose to tolerate it."""
    import json

    problems = []
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            problems.append((i, f"not JSON: {e}"))
            continue
        for err in validate_event(obj):
            problems.append((i, err))
    return problems
