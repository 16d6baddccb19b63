"""repro.telemetry: zero-cost-when-disabled instrumentation.

Pins the subsystem's three contracts:

* **disabled is free** — every emit early-returns, ``span()`` is a shared
  no-op (a bare profiler annotation while a JAX profiler session records,
  which leaves the lowered round unchanged), and :func:`device_event`
  stages nothing: the lowered HLO with telemetry disabled is
  bit-identical to code without the call;
* **enabled is exact** — events are schema-valid JSONL, the Chrome trace
  parses, per-transmit wire events sum to the WireLedger's integer
  totals, and round records mirror the histories both runtimes return;
* **observation does not perturb results** — a sweep run with telemetry
  on produces a byte-identical merged store to one with telemetry off,
  and the compile-counter pins the expected number of XLA compiles
  (recompile hygiene: 3 for the adaptive-k ladder's 3 distinct k,
  exactly 1 per sweep cell, 1 pooled program per runtime).
"""
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.api import ExperimentSpec
from repro.compression import AdaptiveTopK
from repro.sweep import runner
from repro.sweep import store as store_mod
from repro.sweep.grid import plan_grid
from repro.sweep.report import telemetry_report, wire_table
from repro.telemetry import (
    CompileCounter,
    RoundRecord,
    Telemetry,
    compile_scope,
    device_event,
    get_telemetry,
    rejected_from_keep,
    validate_event,
    validate_stream,
)
from repro.telemetry.__main__ import (
    check_chrome_trace,
    check_wire_exactness,
    main as telemetry_cli,
)
from repro.telemetry.core import _NOOP_SPAN


@pytest.fixture
def tel(tmp_path, monkeypatch):
    """A fresh, sink-backed Telemetry installed as the process global
    (so the runtimes' ``get_telemetry()`` calls see it), detached after
    the test."""
    from repro.telemetry import core

    t = Telemetry()
    t.enable(str(tmp_path / "telemetry"))
    monkeypatch.setattr(core, "_GLOBAL", t)
    yield t
    t.disable()


def _events(t):
    t.flush()
    path = os.path.join(t.out_dir, "events.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


PAPER_KW = dict(problem="synthetic-logistic:120:12", m_workers=4,
                aggregator="norm_trim:0.3", attack="gaussian", alpha=0.25)


# ------------------------------------------------------------- disabled
def test_disabled_is_noop(tmp_path):
    t = Telemetry()
    assert not t.enabled
    t.event("x", a=1)
    t.count("c")
    t.gauge("g", 2.0)
    t.observe("h", 3.0)
    t.wire(ledger_id=0, uplink=1, downlink=2, rounds=1)
    t.round(RoundRecord(step=0))
    assert t.span("s") is _NOOP_SPAN          # shared object, no allocation
    assert t.span("other") is _NOOP_SPAN
    assert t.counter_value("c") is None
    assert t.histogram("h") is None
    assert list(tmp_path.iterdir()) == []     # nothing ever touches disk


def test_device_event_hlo_identity():
    """Disabled device_event stages NOTHING: the lowered HLO is
    bit-identical to a build without the call, and contains no host
    callback; enabled, it differs and carries one."""
    t_off = Telemetry()
    t_on = Telemetry().enable()               # memory-only; no sinks needed
    x = jnp.arange(8.0)

    def step(z):                               # instrumented body
        device_event("probe", tel=t_off, s=jnp.sum(z))
        return z * 2.0 + 1.0

    instrumented = jax.jit(step).lower(x).as_text()

    def step(z):                               # same name ⇒ same HLO module
        return z * 2.0 + 1.0

    bare = jax.jit(step).lower(x).as_text()
    assert instrumented == bare
    assert "callback" not in instrumented

    def step(z):
        device_event("probe", tel=t_on, s=jnp.sum(z))
        return z * 2.0 + 1.0

    enabled = jax.jit(step).lower(x).as_text()
    assert enabled != bare
    assert "callback" in enabled
    t_on.disable()


def test_disabled_span_is_a_bare_annotation_while_profiling(tmp_path):
    """Telemetry off: the span enters the JAX profiler's trace while a
    session records, and is the shared no-op before and after."""
    from jax.profiler import TraceAnnotation

    t = Telemetry()
    assert not TraceAnnotation.is_enabled()
    assert t.span("s") is _NOOP_SPAN
    with jax.profiler.trace(str(tmp_path)):
        span = t.span("s", step=3)
        assert isinstance(span, TraceAnnotation)
        with span:
            pass
    assert t.span("s") is _NOOP_SPAN
    assert list(tmp_path.rglob("*.xplane.pb"))


# -------------------------------------------------------------- enabled
def test_emits_are_schema_valid_and_trace_parses(tel):
    tel.event("e", foo="bar")
    tel.count("n", 2)
    tel.gauge("g", 1.5)
    tel.observe("lat", 0.25)
    with tel.span("outer", label="x"):
        with tel.span("inner"):
            assert tel.current_span() == "inner"
    tel.wire(ledger_id=7, uplink=10, downlink=4, rounds=1)
    tel.ledger_snapshot(ledger_id=7, snapshot={
        "uplink_bits": 10, "downlink_bits": 4, "total_bits": 14,
        "rounds": 1})
    tel.round(RoundRecord(step=0, loss=1.0, rejected=[2]))
    tel.flush()
    events = _events(tel)
    for ev in events:
        assert validate_event(ev) == [], ev
    assert check_wire_exactness(events) == []
    assert check_chrome_trace(os.path.join(tel.out_dir, "trace.json")) == []


def test_enabled_span_names_its_parent_and_reaches_the_profiler(tel, tmp_path):
    with jax.profiler.trace(str(tmp_path / "prof")):
        with tel.span("outer", label="x"):
            with tel.span("inner"):
                pass
    spans = {e["name"]: e for e in _events(tel) if e["kind"] == "span"}
    assert spans["inner"]["parent"] == "outer"
    assert "parent" not in spans["outer"]
    assert validate_event(spans["inner"]) == []
    names = {n for _, _, n in _host_events(tmp_path / "prof")}
    assert {"outer", "inner"} <= names


def test_histogram_percentiles():
    t = Telemetry().enable()
    for v in range(1, 101):
        t.observe("lat", float(v))
    h = t.histogram("lat")
    assert h["count"] == 100 and h["min"] == 1.0 and h["max"] == 100.0
    assert h["p50"] == pytest.approx(50.0, abs=1)
    assert h["p99"] == pytest.approx(99.0, abs=1)
    t.disable()


# -------------------------------------------------- runtimes emit rounds
def test_paper_run_round_records_and_wire_exactness(tel):
    spec = ExperimentSpec(compressor="adaptive_topk:0.25:0.9", **PAPER_KW)
    exp = spec.build()
    _, hist = exp.run(4)
    events = _events(tel)
    rounds = [e for e in events if e["kind"] == "round"]
    assert len(rounds) == 4
    for i, r in enumerate(rounds):
        assert r["step"] == i and r["runtime"] == "paper"
        assert r["attack"] == "gaussian" and r["alpha"] == 0.25
        assert r["loss"] == pytest.approx(hist["loss"][i])
        assert r["grad_norm"] == pytest.approx(hist["grad_norm"][i])
        assert r["uplink_delta"] == pytest.approx(hist["uplink_delta"][i])
        assert r["k"] == hist["k_trajectory"][i]
        assert isinstance(r["rejected"], list)
        assert r["model_decrease"] is not None
    # acceptance criterion (a): wire events sum EXACTLY to ledger totals
    assert check_wire_exactness(events) == []
    run_wire = [e for e in events
                if e["kind"] == "wire" and e.get("label") == "round"]
    assert sum(e["uplink"] for e in run_wire) == hist["uplink_bits"]
    assert sum(e["downlink"] for e in run_wire) == hist["downlink_bits"]


def test_mesh_run_round_records_and_device_event(tel):
    spec = ExperimentSpec(problem="quadratic:16", runtime="mesh",
                          m_workers=4, aggregator="norm_trim:0.3",
                          attack="gaussian", alpha=0.25,
                          compressor="topk:0.5")
    exp = spec.build()
    _, hist = exp.run(3)
    events = _events(tel)
    rounds = [e for e in events if e["kind"] == "round"]
    assert len(rounds) == 3
    assert all(r["runtime"] == "mesh" for r in rounds)
    assert hist["uplink_delta"] and len(hist["uplink_delta"]) == 3
    # the staged jax.debug.callback shipped the device-side keep mask out
    aggs = [e for e in events
            if e["kind"] == "event" and e["name"] == "mesh.aggregate"]
    assert len(aggs) == 3
    assert len(aggs[0]["keep"]) == 4
    assert check_wire_exactness(events) == []


def test_saddle_escape_flag_and_step():
    """matrix-factor carries a known saddle value; the run must flag the
    first round whose loss drops below it (paper's headline claim)."""
    from repro.telemetry import core

    t = Telemetry()
    spec = ExperimentSpec(problem="matrix-factor:6:2", m_workers=4,
                          aggregator="mean", M=5.0)
    exp = spec.build()
    saved = core._GLOBAL
    core._GLOBAL = t
    try:
        _, hist = exp.run(25)
    finally:
        core._GLOBAL = saved
    sv = exp.problem.saddle_value
    esc = hist["saddle_escape_step"]
    below = [i for i, l in enumerate(hist["loss"]) if l < sv]
    assert esc == (below[0] if below else None)


# ------------------------------------ run-loop spans on the profiler
def _host_events(log_dir):
    """``(start_ns, end_ns, name)`` of every host event in the one
    ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = log_dir.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def _profiled(log_dir, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        return fn()


@pytest.mark.parametrize("path", ["host_loop", "stretch"])
def test_paper_run_spans_nest_on_the_profiler_host_plane(path, tmp_path,
                                                         monkeypatch):
    """With telemetry off, a profiled run puts its spans on the host
    plane: on the host loop (an adaptive compressor keeps it) newton.solve
    ⊃ newton.round ⊃ {step, wait, pooled}, one round span a round; in
    device stretches newton.solve ⊃ newton.stretch, one a stretch."""
    from repro.telemetry import core

    monkeypatch.setattr(core, "_GLOBAL", Telemetry())
    kw = dict(PAPER_KW)
    if path == "host_loop":
        kw["compressor"] = "adaptive_topk:0.25:0.9"
    exp = ExperimentSpec(**kw).build()
    exp.run(1)                                   # compiles outside the trace
    _, hist = _profiled(tmp_path, lambda: exp.run(3))
    spans = {}
    for s, e, name in _host_events(tmp_path):
        if name.startswith("newton."):
            spans.setdefault(name, []).append((s, e))

    def inside(child, parents):
        return all(any(ps <= s and e <= pe for ps, pe in spans[parents])
                   for s, e in spans[child])

    assert len(spans["newton.solve"]) == 1
    if path == "stretch":
        assert set(spans) == {"newton.solve", "newton.stretch"}
        assert len(spans["newton.stretch"]) == 1
        assert inside("newton.stretch", "newton.solve")
        assert hist["device_rounds"] == hist["rounds"] == 3
        return
    assert hist["device_rounds"] == 0
    assert len(spans["newton.round"]) == hist["rounds"] == 3
    assert inside("newton.round", "newton.solve")
    for child in ("newton.round.step", "newton.round.wait",
                  "newton.round.pooled"):
        assert len(spans[child]) == 3
        assert inside(child, "newton.round")


def test_cubic_iters_count_algorithm_2():
    """hist["cubic_iters"] is the most iterations any worker's Algorithm 2
    ran in the round, counted here by a plain Python loop."""
    spec = ExperimentSpec(problem="synthetic-logistic:120:12", m_workers=4,
                          aggregator="mean")
    exp = spec.build()
    _, hist = exp.run(1)
    cfg, prob = exp.algo.config, exp.problem
    loss = exp.algo.loss_fn
    counts = []
    for X, y in zip(prob.X_workers, prob.y_workers):
        g = jax.grad(loss)(prob.w0, X, y)
        H = jax.hessian(loss)(prob.w0, X, y)
        lr = 1.0 / (cfg.gamma * (jnp.linalg.norm(H, ord="fro")
                                 + cfg.M * cfg.gamma) + 1e-8)
        s, G, it = jnp.zeros_like(g), g, 0
        while float(jnp.linalg.norm(G)) > cfg.solver_tol \
                and it < cfg.solver_iters:
            s = s - lr * G
            G = g + cfg.gamma * (H @ s) \
                + 0.5 * cfg.M * cfg.gamma**2 * jnp.linalg.norm(s) * s
            it += 1
        counts.append(it)
    assert 1 < max(counts) < cfg.solver_iters
    assert hist["cubic_iters"] == [max(counts)]


def test_round_hlo_identical_with_profiler_open_and_closed(tmp_path):
    """The spans are host-side: the round a profiled run lowers is the
    round an unprofiled run lowers."""
    def lowered():
        algo = ExperimentSpec(**PAPER_KW).build().algo
        algo._ensure_channels(12, 4)
        w = jnp.zeros(12)
        X, y = jnp.ones((4, 30, 12)), jnp.ones((4, 30))
        return algo._step.lower(w, w, algo.init_comm_state(), X, y,
                                jax.random.PRNGKey(0)).as_text()

    closed = lowered()
    assert _profiled(tmp_path, lowered) == closed


# -------------------------------------------- observation ≠ perturbation
def _run_sweep(store_path, n_cells=2):
    plan = plan_grid({"seed": list(range(n_cells))},
                     {**PAPER_KW, "compressor": "topk:0.25", "n_steps": 3})
    st = store_mod.ResultStore(store_path)
    summary = runner.run_plan(plan, st)
    assert summary["failed"] == 0
    return st


def test_sweep_store_byte_identical_with_telemetry_on_off(
        tmp_path, monkeypatch):
    """Telemetry is an observer: the merged (volatile-stripped,
    hash-sorted) store bytes are identical with it on and off."""
    from repro.telemetry import core

    off = tmp_path / "off.jsonl"
    monkeypatch.setattr(core, "_GLOBAL", Telemetry())   # decidedly off
    _run_sweep(str(off))
    on = tmp_path / "on.jsonl"
    t = Telemetry().enable(str(tmp_path / "tel"))
    monkeypatch.setattr(core, "_GLOBAL", t)
    _run_sweep(str(on))
    t.disable()
    store_mod.merge([str(off)], str(tmp_path / "off_m.jsonl"))
    store_mod.merge([str(on)], str(tmp_path / "on_m.jsonl"))
    assert (tmp_path / "off_m.jsonl").read_bytes() \
        == (tmp_path / "on_m.jsonl").read_bytes()
    # and the telemetry-on run actually observed: spans for every phase
    t.flush()
    names = {e["name"] for e in _events(t) if e["kind"] == "span"}
    assert {"sweep.shard", "sweep.cell", "sweep.cell.build",
            "sweep.cell.run", "sweep.cell.store"} <= names


def test_sweep_store_persists_wire_adaptivity_columns(tmp_path):
    """Satellite: per-round uplink_delta and the adaptive-k trajectory
    land in the stored cell metrics, and sweep.report can pivot them."""
    plan = plan_grid({"seed": [0]},
                     {**PAPER_KW, "compressor": "adaptive_topk:0.25:0.9",
                      "n_steps": 3})
    st = store_mod.ResultStore(str(tmp_path / "s.jsonl"))
    assert runner.run_plan(plan, st)["failed"] == 0
    (rec,) = st.ok_records()
    m = rec["metrics"]
    assert len(m["uplink_delta"]) == 3
    assert len(m["k_trajectory"]) == 3
    assert m["k_trajectory"][0] == 3    # ceil-free int(0.25·12)
    (row,) = wire_table([rec])
    assert row["k_start"] == m["k_trajectory"][0]
    assert row["k_final"] == m["k_trajectory"][-1]
    assert row["delta_mean"] == pytest.approx(
        sum(m["uplink_delta"]) / 3)


# ------------------------------------------------- compile-count pins
def test_compile_pin_adaptive_topk_d4096():
    """Recompile hygiene: the pinned d=4096 δ̂ ladder moves k three times
    (410→820→1640, then holds), so a k-static jitted consumer compiles
    EXACTLY 3 times — one XLA compile per distinct k, none for the holds."""
    from repro.kernels.ref import topk_compress_ref

    d = 4096
    comp = AdaptiveTopK(d, 205, 3277, delta_target=0.6)
    x = jax.random.normal(jax.random.PRNGKey(0), (d,))
    f = jax.jit(partial(topk_compress_ref), static_argnums=1)
    ks = []
    cc = CompileCounter()
    with cc, compile_scope("pin.adaptive"):
        for delta in (0.2, 0.3, 0.5, 0.7, 0.9, 0.9):
            comp.schedule_update(grad_norm=1.0, measured_delta=delta)
            ks.append(comp.k)
            f(x, comp.k)
    assert ks == [410, 820, 1640, 1640, 1640, 1640]
    assert cc.backend_compiles("pin.adaptive") == len(set(ks)) == 3


def test_compile_pin_sweep_one_compile_per_cell(tmp_path):
    """A 2-cell sweep differing only in seed compiles the paper step
    EXACTLY twice — once per cell (each Experiment owns a fresh jit),
    never per round.  Guards against per-step retrace regressions."""
    cc = CompileCounter()
    with cc:
        _run_sweep(str(tmp_path / "s.jsonl"), n_cells=2)
    assert cc.backend_compiles("newton.step") == 2


@pytest.mark.parametrize("runtime_kw, telemetry_on, pooled, round_scopes", [
    ({}, False, 0, ("newton.step",)),
    ({}, True, 1, ("newton.step",)),
    (dict(runtime="async", participation=0.5, staleness=1), False, 1,
     ("async.compute", "async.downlink")),
], ids=["paper", "paper-host-loop", "async"])
def test_compile_pin_pooled_once_per_runtime(runtime_kw, telemetry_on, pooled,
                                             round_scopes, request):
    """A runtime's first run compiles its programs once: the host loops
    the pooled loss-and-norm program and the round, the paper runtime's
    device stretches one program under ``newton.step`` (the pooled
    evaluation inside it); a second run with another key compiles nothing
    under ``newton.pooled`` or the runtime's round programs."""
    if telemetry_on:                # telemetry on keeps the host loop
        request.getfixturevalue("tel")
    exp = ExperimentSpec(**runtime_kw, **PAPER_KW).build()
    first = CompileCounter()
    with first:
        exp.run(3, key=jax.random.PRNGKey(1))
    assert first.backend_compiles("newton.pooled") == pooled
    if not runtime_kw:
        assert first.backend_compiles("newton.step") == 1
    again = CompileCounter()
    with again:
        exp.run(3, key=jax.random.PRNGKey(2))
    for scope in ("newton.pooled",) + round_scopes:
        assert again.backend_compiles(scope) == 0, scope


# --------------------------------------------------------- CLI / report
def test_validate_cli_exit_codes(tel, tmp_path, capsys):
    spec = ExperimentSpec(compressor="topk:0.25", **PAPER_KW)
    spec.build().run(2)
    tel.flush()
    events_path = os.path.join(tel.out_dir, "events.jsonl")
    trace_path = os.path.join(tel.out_dir, "trace.json")
    assert telemetry_cli([
        "validate", events_path, "--trace", trace_path, "--check-wire",
    ]) == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 1, "kind": "wire", "name": "wire"}\n')
    assert telemetry_cli(["validate", str(bad)]) == 1
    capsys.readouterr()


def test_validate_stream_catches_missing_fields():
    good = json.dumps({"v": 1, "kind": "event", "name": "x",
                       "ts": 0.0, "wall": 0.0})
    bad = json.dumps({"v": 1, "kind": "span", "name": "x",
                      "ts": 0.0, "wall": 0.0})    # span without dur_s
    problems = validate_stream([good, bad])
    assert [ln for ln, _ in problems] == [2]


def test_telemetry_report_aggregates(tel, tmp_path):
    _run_sweep(str(tmp_path / "s.jsonl"))
    tel.flush()
    lines = []
    rep = telemetry_report(os.path.join(tel.out_dir, "events.jsonl"),
                           printer=lines.append)
    assert rep["cells"]["ok"] == 2 and rep["cells"]["failed"] == 0
    assert rep["rounds"] == 6                      # 2 cells × 3 rounds
    assert rep["wire"]["uplink"] > 0
    span_names = {r["span"] for r in rep["spans"]}
    assert "sweep.cell.run" in span_names
    assert any("sweep report" not in ln and "cells:" in ln for ln in lines)


# ----------------------------------------------- schema v2 (center path)


def test_round_records_carry_center_path_fields(tel):
    """Round records carry the v2 center-path fields (center_bytes +
    agg_kernel) at the current schema version — sparse path."""
    from repro.telemetry.schema import SCHEMA_VERSION

    spec = ExperimentSpec(problem="synthetic-logistic:120:12", m_workers=4,
                          aggregator="mean", compressor="topk:0.25",
                          error_feedback="none")
    exp = spec.build()
    exp.run(2)
    events = _events(tel)
    rounds = [e for e in events if e["kind"] == "round"]
    assert rounds and all(e["v"] == SCHEMA_VERSION for e in rounds)
    d, m = 12, 4
    k = max(1, round(0.25 * d))
    for r in rounds:
        assert r["agg_kernel"] == "sparse"
        assert r["center_bytes"] == m * k * 8 + 4 * d
    assert validate_stream(json.dumps(e) for e in events) == []


def test_round_record_dense_path_fields(tel):
    spec = ExperimentSpec(**PAPER_KW)   # norm_trim + gaussian attack ⇒ dense
    exp = spec.build()
    exp.run(2)
    rounds = [e for e in _events(tel) if e["kind"] == "round"]
    d, m = 12, 4
    for r in rounds:
        assert r["agg_kernel"] == "dense"
        assert r["center_bytes"] == m * d * 4 + 4 * d


def test_schema_v2_validator_coverage():
    """v1-v3 events stay valid forever; per-version field constraints
    enforced; unknown versions rejected."""
    from repro.telemetry.schema import ACCEPTED_VERSIONS, SCHEMA_VERSION

    assert SCHEMA_VERSION == 4 and ACCEPTED_VERSIONS == (1, 2, 3, 4)
    base = {"kind": "round", "name": "newton.round", "ts": 0.1,
            "wall": 1.0, "step": 0}
    assert validate_event({**base, "v": 1}) == []          # v1 round: valid
    assert validate_event({**base, "v": 2, "center_bytes": 128,
                           "agg_kernel": "sparse"}) == []
    assert validate_event({**base, "v": 5})                # unknown version
    assert any("agg_kernel" in p for p in
               validate_event({**base, "v": 2, "agg_kernel": "vectorized"}))
    assert any("center_bytes" in p for p in
               validate_event({**base, "v": 2, "center_bytes": -4}))
    assert any("center_bytes" in p for p in
               validate_event({**base, "v": 2, "center_bytes": 3.5}))


def test_schema_v4_worker_field_validation():
    """The per-worker forensic lists: typed entries, null participation
    holes where allowed, suspicion clamped to [0, 1]."""
    base = {"kind": "round", "name": "newton.round", "ts": 0.1,
            "wall": 1.0, "step": 0, "v": 4}
    ok = {**base, "worker_bits": [64, 0], "worker_delta": [0.9, None],
          "worker_keep": [1.0, None], "worker_norms": [0.5, None],
          "worker_staleness": [0, None], "suspicion": [0.0, 1.0],
          "byzantine_true": [0]}
    assert validate_event(ok) == []
    assert any("worker_bits" in p for p in
               validate_event({**base, "worker_bits": [-1]}))
    assert any("worker_bits" in p for p in
               validate_event({**base, "worker_bits": [None]}))
    assert any("suspicion" in p for p in
               validate_event({**base, "suspicion": [1.5]}))
    assert any("suspicion" in p for p in
               validate_event({**base, "suspicion": [None]}))
    assert any("byzantine_true" in p for p in
               validate_event({**base, "byzantine_true": [0.5]}))
    assert any("worker_staleness" in p for p in
               validate_event({**base, "worker_staleness": [1.5]}))
    assert any("worker_keep" in p for p in
               validate_event({**base, "worker_keep": "all"}))
