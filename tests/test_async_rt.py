"""repro.async_rt + the sweep executor pool.

The subsystem's acceptance criteria as tests: degenerate-config
bit-exactness with the synchronous runtime (BOTH center layouts),
deterministic event scheduling and arrival ordering, EF-state versioning
per arrival (dropped packets never advance the center's belief), exact
wire accounting under drops/duplicates, spec-axis validation and serde
hash-compatibility, the ``staleness`` sweep preset, pool-vs-serial
byte-identical merged stores with failure isolation, the
order-insensitive wire validator, and the schema-v3 async fields.
(Hypothesis cohort properties live in ``test_properties.py`` — the
unit-test modules stay hypothesis-free by repo convention.)
"""
import dataclasses
import json
import random

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, SpecError
from repro.api.aggregators import make_aggregator
from repro.async_rt import (
    AsyncConfig,
    AsyncCubicNewton,
    EventScheduler,
    Message,
    MessageQueue,
    StalenessWeighted,
    cohort_size,
    sample_cohort,
)
from repro.sweep import ResultStore, merge, plan_grid, run_plan
from repro.sweep.grids import staleness_grid
from repro.telemetry import Telemetry, validate_event
from repro.telemetry.__main__ import check_wire_exactness

# tiny shared scenarios (jit caches stay warm across the module)
DENSE_KW = dict(problem="synthetic-logistic:80:6", m_workers=5, M=10.0,
                alpha=0.2, attack="gaussian", aggregator="norm_trim:0.4",
                compressor="topk:0.5", seed=0)      # EF21 auto ⇒ dense center
SPARSE_KW = dict(problem="synthetic-logistic:80:6", m_workers=5, M=10.0,
                 attack="none", aggregator="mean", compressor="topk:0.5",
                 error_feedback="none", seed=0)     # sparse center auto


# ------------------------------------------------------------ scheduler
def test_full_participation_cohort_is_every_worker():
    np.testing.assert_array_equal(sample_cohort(3, 9, 7, 1.0), np.arange(7))
    assert cohort_size(7, 1.0) == 7


def test_cohort_size_floors_at_one():
    assert cohort_size(10, 0.01) == 1       # a round is never a no-op
    assert cohort_size(5, 0.5) == 2         # round(2.5) banker's-rounds down


def test_cohort_deterministic_sorted_without_replacement():
    c = sample_cohort(0, 4, 10, 0.5)
    np.testing.assert_array_equal(c, sample_cohort(0, 4, 10, 0.5))
    ids = c.tolist()
    assert len(ids) == 5 and len(set(ids)) == 5 and ids == sorted(ids)
    assert all(0 <= i < 10 for i in ids)


def test_scheduler_fault_probability_extremes():
    s = EventScheduler(0, 4, staleness=0, drop=1.0, duplicate=1.0)
    assert s.lag(0, 0) == 0
    assert s.dropped(0, 0) and s.duplicated(0, 0)
    q = EventScheduler(0, 4, staleness=3)
    assert not q.dropped(5, 2) and not q.duplicated(5, 2)
    assert all(0 <= q.lag(t, i) <= 3 for t in range(6) for i in range(4))


def test_message_queue_drains_due_in_deterministic_order():
    q = MessageQueue()
    mk = lambda w, t, c=0: Message(worker=w, send_round=t, version=t,
                                   copy=c, payload=None)
    q.push(1, mk(2, 0))          # lagged send from round 0
    q.push(0, mk(1, 0))
    q.push(0, mk(1, 0, c=1))     # its duplicate
    q.push(2, mk(0, 1))          # not due yet
    assert q.depth == 4
    due = q.pop_due(0)
    assert [(m.worker, m.copy) for m in due] == [(1, 0), (1, 1)]
    assert q.depth == 2
    # round 1 drains the round-0 straggler BEFORE the round-1 send
    assert [(m.send_round, m.worker) for m in q.pop_due(1)] == [(0, 2)]
    assert [(m.send_round, m.worker) for m in q.pop_due(2)] == [(1, 0)]
    assert q.depth == 0


# ---------------------------------------------- staleness-weighted agg
def test_staleness_weighted_fresh_arrivals_match_base_rule():
    agg = make_aggregator("norm_trim:0.4")
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(6, 4)).astype(np.float32))
    base_a, base_k = agg(u)
    a, k = StalenessWeighted(agg, decay=0.5)(u, [0] * 6)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(base_k))
    np.testing.assert_allclose(np.asarray(a), np.asarray(base_a), rtol=1e-5)


def test_staleness_weighted_discounts_by_age():
    agg = make_aggregator("mean")
    u = jnp.asarray([[3.0, 0.0], [0.0, 3.0]], jnp.float32)
    a, k = StalenessWeighted(agg, decay=0.5)(u, [0, 1])
    expected = (u[0] + 0.5 * u[1]) / 1.5          # weights decay**age
    np.testing.assert_allclose(np.asarray(a), np.asarray(expected),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(k), np.ones(2))


def test_staleness_weighted_single_arrival_never_screened():
    agg = make_aggregator("norm_trim:0.4")
    u = jnp.asarray([[2.0, -1.0, 0.5]], jnp.float32)
    a, k = StalenessWeighted(agg, decay=1.0)(u, [4])
    np.testing.assert_array_equal(np.asarray(k), np.ones(1))
    np.testing.assert_allclose(np.asarray(a), np.asarray(u[0]), rtol=1e-6)


def test_staleness_weighted_rejects_bad_decay():
    agg = make_aggregator("mean")
    for decay in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="decay"):
            StalenessWeighted(agg, decay=decay)
    with pytest.raises(ValueError, match="norm_guard"):
        StalenessWeighted(agg, norm_guard=0.0)


def test_staleness_norm_guard_rejects_lone_byzantine_packet():
    """Regression: under low participation a round where ONLY a Byzantine
    packet lands must not become the center update — the guard screens a
    lone arrival against the last screened aggregate's norm."""
    sw = StalenessWeighted(make_aggregator("norm_trim:0.4"), decay=1.0)
    rng = np.random.default_rng(1)
    # honest gradients share a direction (like real descent directions),
    # so a lone honest arrival has the same scale as the aggregate
    honest = jnp.asarray(
        (np.array([2.0, -1.0, 0.5, 1.0])
         + 0.1 * rng.normal(size=(5, 4))).astype(np.float32))
    sw(honest, [0] * 5)                       # screened round → reference

    bomb = jnp.asarray([[1e4, -1e4, 1e4, -1e4]], jnp.float32)
    a, k = sw(bomb, [0])
    np.testing.assert_array_equal(np.asarray(k), np.zeros(1))
    np.testing.assert_array_equal(np.asarray(a), np.zeros(4))

    # a rejected round must not move the reference: the bomb still
    # bounces on the next lone-arrival round
    _, k2 = sw(bomb, [1])
    np.testing.assert_array_equal(np.asarray(k2), np.zeros(1))

    # an honest-scale lone arrival still passes
    a3, k3 = sw(honest[:1], [0])
    np.testing.assert_array_equal(np.asarray(k3), np.ones(1))
    np.testing.assert_allclose(np.asarray(a3), np.asarray(honest[0]),
                               rtol=1e-6)


def test_async_low_participation_saddle_attack_stays_bounded():
    """End-to-end: participation so low that single-arrival rounds are
    common, with saddle-attack Byzantine workers — the guard keeps the
    trajectory finite and bounded."""
    spec = ExperimentSpec(
        runtime="async", participation=0.2, staleness=3, drop=0.3,
        problem="synthetic-logistic:80:6", m_workers=10, M=10.0,
        alpha=0.2, attack="saddle:50.0", aggregator="norm_trim:0.4",
        seed=0,
    )
    w, h = spec.build().run(12)
    # the scenario actually exercises the guard: some rounds deliver a
    # single packet, and with α=0.2 some of those are Byzantine
    assert any(n == 1 for n in h["n_arrivals"])
    assert bool(jnp.all(jnp.isfinite(w)))
    assert np.isfinite(h["loss"]).all()
    assert h["loss"][-1] < 10 * h["loss"][0] + 1.0


# --------------------------------------- degenerate-config bit-exactness
@pytest.fixture(scope="module")
def dense_pair():
    w_s, h_s = ExperimentSpec(**DENSE_KW).build().run(3)
    w_a, h_a = ExperimentSpec(runtime="async", **DENSE_KW).build().run(3)
    return (w_s, h_s), (w_a, h_a)


def test_degenerate_async_bit_exact_with_paper_dense_layout(dense_pair):
    (w_s, h_s), (w_a, h_a) = dense_pair
    assert bool(jnp.all(w_s == w_a))              # bit-exact iterates
    assert h_a["loss"] == h_s["loss"]             # exact float trajectories
    assert h_a["uplink_bits"] == h_s["uplink_bits"]
    assert h_a["downlink_bits"] == h_s["downlink_bits"]
    assert h_a["async_degenerate"] is True
    assert "async_degenerate" not in h_s


def test_degenerate_async_bit_exact_with_paper_sparse_layout():
    sync = ExperimentSpec(**SPARSE_KW).build()
    w_s, h_s = sync.run(3)
    deg = ExperimentSpec(runtime="async", **SPARSE_KW).build()
    w_a, h_a = deg.run(3)
    # the degenerate path delegates to the synchronous program, so the
    # sparse-domain center stays selected — and stays bit-exact
    assert sync.algo._use_sparse_center and deg.algo._use_sparse_center
    assert bool(jnp.all(w_s == w_a))
    assert h_a["loss"] == h_s["loss"]
    assert h_a["uplink_bits"] == h_s["uplink_bits"]
    assert h_a["async_degenerate"] is True


# ----------------------------------------------------- buffered rounds
@pytest.fixture(scope="module")
def buffered():
    exp = ExperimentSpec(runtime="async", participation=0.5, staleness=2,
                         **DENSE_KW).build()
    w, h = exp.run(4)
    return exp, w, h


def test_buffered_round_series_and_wire_accounting(buffered):
    exp, _, h = buffered
    assert h["async_degenerate"] is False
    assert h["cohort_size"] == [2] * 4            # round(0.5·5) = 2, every round
    assert len(h["loss"]) == 4 and h["rounds"] == 4
    total_sends = sum(h["cohort_size"])
    assert sum(h["n_arrivals"]) + h["queue_depth"][-1] == total_sends
    msg_bits = exp.algo.bits_per_step()["uplink"] // 5
    assert h["uplink_bits"] == msg_bits * total_sends   # billed at send time
    for mean_age in h["staleness_mean"]:
        assert mean_age is None or 0 <= mean_age <= 2
    assert all(d >= 0 for d in h["queue_depth"])


def test_buffered_run_is_reproducible(buffered):
    exp2 = ExperimentSpec(runtime="async", participation=0.5, staleness=2,
                          **DENSE_KW).build()
    _, h2 = exp2.run(4)
    _, _, h = buffered
    assert h2["loss"] == h["loss"]
    assert h2["n_arrivals"] == h["n_arrivals"]
    assert h2["uplink_bits"] == h["uplink_bits"]


def test_drop_everything_freezes_iterate_and_center_ef_state():
    kw = dict(problem="synthetic-logistic:80:6", m_workers=5, M=10.0,
              attack="none", aggregator="mean", compressor="topk:0.5",
              error_feedback="ef21", seed=0)
    exp = ExperimentSpec(runtime="async", drop=1.0, **kw).build()
    _, h = exp.run(3)
    assert h["n_arrivals"] == [0, 0, 0]
    assert h["downlink_bits"] == 0                # nothing ever broadcast
    assert len(set(h["loss"])) == 1               # w never moved
    msg_bits = exp.algo.bits_per_step()["uplink"] // 5
    assert h["uplink_bits"] == msg_bits * 5 * 3   # drops still pay the wire
    # EF versioning: no arrival ⇒ the center's per-worker channel state
    # never advances ⇒ the (deterministic) transmit is identical each
    # round.  If drops advanced the state this δ̂ series would move.
    assert len(set(h["uplink_delta"])) == 1


def test_duplicates_pay_twice_and_deliver_twice():
    kw = dict(problem="synthetic-logistic:80:6", m_workers=5, M=10.0,
              attack="none", aggregator="mean", compressor="topk:0.5",
              error_feedback="ef21", seed=0)
    exp = ExperimentSpec(runtime="async", duplicate=1.0, **kw).build()
    _, h = exp.run(3)
    msg_bits = exp.algo.bits_per_step()["uplink"] // 5
    assert h["uplink_bits"] == 2 * msg_bits * 5 * 3     # every packet twice
    assert h["n_arrivals"] == [10, 10, 10]              # delivered twice
    # EF-committed ONCE per send + equal-weight mean over the doubled
    # stack ⇒ the trajectory tracks the duplicate-free (degenerate) run
    _, h_ref = ExperimentSpec(runtime="async", **kw).build().run(3)
    np.testing.assert_allclose(h["loss"], h_ref["loss"], rtol=5e-3)


def test_sparse_center_demand_rejected_on_buffered_path():
    exp = ExperimentSpec(runtime="async", participation=0.5,
                         **SPARSE_KW).build()
    cfg = dataclasses.replace(exp.config, sparse_center=True)
    algo = AsyncCubicNewton(exp.problem.loss_fn, cfg,
                            exp.spec.to_attack_config(),
                            AsyncConfig(participation=0.5))
    with pytest.raises(ValueError, match="sparse_center"):
        algo.run(exp.problem.w0, exp.problem.X_workers,
                 exp.problem.y_workers, 1)


def test_sparse_capable_channel_falls_back_to_dense_when_buffered():
    exp = ExperimentSpec(runtime="async", participation=0.5,
                         **SPARSE_KW).build()
    _, h = exp.run(2)
    assert exp.algo._use_sparse_center is False   # auto resolved: dense
    assert h["async_degenerate"] is False
    assert len(h["loss"]) == 2


# ------------------------------------------------- spec axes and serde
def test_async_axes_validate_ranges():
    good = ExperimentSpec(runtime="async", participation=0.5, staleness=3,
                          drop=0.1, duplicate=0.1, staleness_decay=0.9)
    good.validate()
    bad = [dict(participation=0.0), dict(participation=1.5),
           dict(staleness=-1), dict(drop=1.5), dict(duplicate=-0.1),
           dict(staleness_decay=0.0)]
    for kw in bad:
        with pytest.raises(SpecError):
            ExperimentSpec(runtime="async", **kw).validate()


def test_non_default_axes_require_async_runtime():
    with pytest.raises(SpecError, match="runtime"):
        ExperimentSpec(participation=0.5).validate()
    with pytest.raises(SpecError, match="runtime"):
        ExperimentSpec(runtime="mesh", problem="quadratic:8",
                       staleness=2).validate()


def test_async_rejects_two_round_mode():
    with pytest.raises(SpecError, match="async"):
        ExperimentSpec(runtime="async", exact_gradient=True).validate()


def test_to_dict_omits_default_axes_and_round_trips():
    plain = ExperimentSpec(**DENSE_KW)
    d = plain.to_dict()
    for axis in ("participation", "staleness", "drop", "duplicate",
                 "staleness_decay"):
        assert axis not in d          # pre-async spec dicts stay byte-stable
    assert ExperimentSpec.from_dict(d) == plain
    stale = ExperimentSpec(runtime="async", staleness=3, drop=0.25)
    d2 = stale.to_dict()
    assert d2["staleness"] == 3 and d2["drop"] == 0.25
    assert "participation" not in d2  # still-default axes stay omitted
    assert ExperimentSpec.from_dict(d2) == stale
    assert ExperimentSpec.from_json(stale.to_json()) == stale


def test_staleness_grid_preset_plans_all_cells():
    axes, base = staleness_grid(n_steps=2)
    plan = plan_grid(axes, base)
    assert len(plan.entries) == 12 and not plan.skipped   # 3 × 2 × 2
    assert all(e.spec.runtime == "async" for e in plan.entries)
    degen = [e for e in plan.entries
             if e.spec.staleness == 0 and e.spec.participation == 1.0
             and e.spec.alpha == 0.0]
    assert len(degen) == 1            # the paper-runtime bit-exact anchor
    d = degen[0].spec.to_dict()
    assert "staleness" not in d and "participation" not in d


# ------------------------------------------------------- executor pool
POOL_AXES = {"aggregator": ["mean", "norm_trim"]}
POOL_BASE = {"problem": "synthetic-logistic:200:8", "m_workers": 10,
             "alpha": 0.2, "attack": "gaussian", "seed": 0, "n_steps": 2}


@pytest.fixture(scope="module")
def pool_stores(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("pool")
    plan = plan_grid(POOL_AXES, POOL_BASE)
    s_sum = run_plan(plan, ResultStore(str(tmp / "serial.jsonl")), jobs=1)
    p_sum = run_plan(plan, ResultStore(str(tmp / "pool.jsonl")), jobs=2)
    return tmp, plan, s_sum, p_sum


def test_pool_builds_every_cell(pool_stores):
    _, plan, s_sum, p_sum = pool_stores
    assert s_sum["built"] == p_sum["built"] == len(plan.entries) == 2
    assert s_sum["failed"] == p_sum["failed"] == 0


def test_pool_merge_byte_identical_to_serial(pool_stores):
    tmp, _, _, _ = pool_stores
    merge([str(tmp / "serial.jsonl")], str(tmp / "m_serial.jsonl"))
    merge([str(tmp / "pool.jsonl")], str(tmp / "m_pool.jsonl"))
    a = (tmp / "m_serial.jsonl").read_bytes()
    assert a and a == (tmp / "m_pool.jsonl").read_bytes()
    # volatile diagnostics present per-run, stripped by merge
    raw = [json.loads(ln) for ln
           in (tmp / "pool.jsonl").read_text().splitlines()]
    assert all("wall_time_s" in r and "worker_id" in r for r in raw)
    merged = [json.loads(ln) for ln
              in (tmp / "m_pool.jsonl").read_text().splitlines()]
    assert all("wall_time_s" not in r and "worker_id" not in r
               for r in merged)


@pytest.mark.parametrize("platforms", [None, "tpu", "tpu,cpu"])
def test_pool_refused_unless_cpu_only(tmp_path, monkeypatch, platforms):
    """A chip serves one process at a time, so jobs > 1 is refused
    unless the environment pins JAX to the CPU; nothing is built."""
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    store = ResultStore(str(tmp_path / "s.jsonl"))
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        run_plan(plan_grid(POOL_AXES, POOL_BASE), store, jobs=2)
    assert not (tmp_path / "s.jsonl").exists()


def test_pool_failure_isolation_and_retry(tmp_path):
    plan = plan_grid(POOL_AXES, POOL_BASE)
    bad = plan.entries[0].hash
    store = ResultStore(str(tmp_path / "s.jsonl"))
    first = run_plan(plan, store, jobs=2, _inject_fail=frozenset({bad}))
    assert first["built"] == 1 and first["failed"] == 1
    rec = store.get(bad)
    assert rec["status"] == "failed" and "injected" in rec["error"]
    again = run_plan(plan, store, jobs=2, retry_failed=True)
    assert again == {"built": 1, "cached": 1, "failed": 0,
                     "shard": (0, 1), "total": 2}
    assert store.get(bad)["status"] == "ok"


# ------------------------------------------------ wire validator (v3)
def _wire(pid, lid, seq, up=8, down=0, rounds=0):
    return {"v": 3, "kind": "wire", "name": "ledger.record", "ts": 0.0,
            "wall": 0.0, "ledger_id": lid, "uplink": up, "downlink": down,
            "rounds": rounds, "seq": seq, "pid": pid}


def _snap(pid, lid, n, up, down=0, rounds=0):
    return {"v": 3, "kind": "ledger", "name": "ledger.snapshot", "ts": 0.0,
            "wall": 0.0, "ledger_id": lid, "uplink_bits": up,
            "downlink_bits": down,
            "total_bits": up + down, "rounds": rounds,
            "n_records": n, "pid": pid}


def test_wire_validator_is_order_insensitive():
    events = ([_wire(11, 0, s) for s in range(4)]
              + [_snap(11, 0, 4, up=32)]
              + [_wire(22, 0, s, up=4) for s in range(3)]   # pid-colliding id
              + [_snap(22, 0, 3, up=12)])
    for seed in range(5):
        shuffled = list(events)
        random.Random(seed).shuffle(shuffled)
        assert check_wire_exactness(shuffled) == []


def test_wire_validator_groups_generations_by_pid():
    # same ledger_id from two pool workers: a pid-blind validator would
    # pool their sums and fail both snapshots
    events = [_wire(1, 7, 0, up=10), _snap(1, 7, 1, up=10),
              _wire(2, 7, 0, up=99), _snap(2, 7, 1, up=99)]
    assert check_wire_exactness(events) == []
    assert any("sum(wire.uplink)" in p for p in check_wire_exactness(
        [_wire(1, 7, 0, up=10), _snap(1, 7, 1, up=11)]))


def test_wire_validator_detects_missing_and_duplicated_seqs():
    # sums agree (the lost record carried 0 bits) but seq 2 never arrived
    missing = [_wire(5, 0, 0), _wire(5, 0, 1), _wire(5, 0, 3, up=0),
               _snap(5, 0, 4, up=16)]
    assert any("missing seqs [2]" in p
               for p in check_wire_exactness(missing))
    duped = [_wire(5, 0, 0), _wire(5, 0, 1), _wire(5, 0, 1),
             _snap(5, 0, 2, up=16)]
    assert any("duplicated seqs [1]" in p
               for p in check_wire_exactness(duped))


def test_wire_validator_accepts_pre_v3_streams_sum_only():
    legacy = [{"v": 1, "kind": "wire", "name": "ledger.record", "ts": 0.0,
               "ledger_id": 3, "uplink": 6, "downlink": 2, "rounds": 1},
              {"v": 1, "kind": "ledger", "name": "ledger.snapshot",
               "ts": 0.0, "ledger_id": 3, "uplink_bits": 6,
               "downlink_bits": 2, "total_bits": 8, "rounds": 1}]
    assert check_wire_exactness(legacy) == []


# --------------------------------------------------------- schema v3
def test_schema_v3_async_round_fields():
    base = {"v": 3, "kind": "round", "name": "newton.round", "ts": 0.1,
            "wall": 1.0, "step": 0}
    good = {**base, "cohort_size": 3, "n_arrivals": 2, "queue_depth": 1,
            "participation": 0.5, "arrival_staleness": [0, 2]}
    assert validate_event(good) == []
    assert any("arrival_staleness" in p for p in
               validate_event({**base, "arrival_staleness": [0, -1]}))
    assert any("participation" in p for p in
               validate_event({**base, "participation": "half"}))
    assert any("cohort_size" in p for p in
               validate_event({**base, "cohort_size": -1}))
    assert validate_event(_wire(1234, 0, 0)) == []
    assert validate_event(_snap(1234, 0, 1, up=8)) == []


def test_async_run_emits_valid_rounds_histograms_and_exact_wire(
        tmp_path, monkeypatch):
    from repro.telemetry import core

    t = Telemetry()
    t.enable(str(tmp_path / "telemetry"))
    monkeypatch.setattr(core, "_GLOBAL", t)
    try:
        exp = ExperimentSpec(runtime="async", participation=0.5,
                             staleness=2, **DENSE_KW).build()
        _, hist = exp.run(3)
        t.flush()
        with open(str(tmp_path / "telemetry" / "events.jsonl")) as f:
            events = [json.loads(ln) for ln in f if ln.strip()]
        for ev in events:
            assert validate_event(ev) == [], ev
        assert check_wire_exactness(events) == []
        rounds = [e for e in events if e["kind"] == "round"]
        assert len(rounds) == 3
        for r in rounds:
            assert r["runtime"] == "async"
            assert r["cohort_size"] == 2 and r["participation"] == 0.5
            assert r["n_arrivals"] == len(r["arrival_staleness"])
            assert all(0 <= a <= 2 for a in r["arrival_staleness"])
            assert r["queue_depth"] >= 0
        assert t.histogram("async.queue_depth")["count"] == 3
        assert (t.histogram("async.staleness") or {"count": 0})["count"] \
            == sum(hist["n_arrivals"])
    finally:
        t.disable()
