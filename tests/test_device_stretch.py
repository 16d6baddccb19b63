"""Rounds run as device stretches: one ``lax.while_loop`` program a stretch,
with the ε test, the pooled evaluation and the test accuracy inside.

Pins that a stretch gives, exactly, the solve of a round-at-a-time
reference over the separately jitted programs (same iterate, same history,
same round at which ε is met), that ``run()`` runs one round a stretch
wherever the host must act between rounds, and that the stretch compiles
once per runtime, in ``step()`` or the first ``run()``, never again.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec
from repro.core import newton
from repro.telemetry import ANY, CompileCounter, Telemetry

SMALL = dict(problem="synthetic-logistic:120:12", m_workers=4,
             aggregator="norm_trim:0.3")

CONFIGS = {
    "gauss-normtrim": dict(SMALL, attack="gaussian", alpha=0.25),
    "saddle-robust": dict(SMALL, problem="synthetic-regression:120:12",
                          attack="saddle", alpha=0.25),
    "exact-gradient": dict(SMALL, exact_gradient=True),
    "momentum": dict(SMALL, momentum=0.5, attack="gaussian", alpha=0.25),
    "topk-ef21": dict(SMALL, compressor="topk:0.25", error_feedback="ef21"),
    "sparse-center": dict(SMALL, compressor="topk:0.25", error_feedback="none",
                          attack="flipped_label", alpha=0.25),
    "logistic-accuracy": dict(problem="a9a-logistic", aggregator="norm_trim:0.3",
                              attack="gaussian", alpha=0.2),
    "matrix-factor-escape": dict(SMALL, problem="matrix-factor:10:2"),
}
CASES = ([(c, case) for c in CONFIGS
          for case in ("cap", "eps-mid-stretch", "eps-between-float32")]
         + [(c, "two-stretches") for c in ("gauss-normtrim", "topk-ef21")])


def _round_at_a_time(exp, n_steps, key, grad_tol=None, eval_fn=None):
    """The reference solve: one round at a time over the separately jitted
    programs (the round ``algo._step``, the pooled loss and norm
    ``algo._pooled``) and the eager evaluation, with the history, the
    stop at ε, the saddle escape and the bits a round kept here; an
    adaptive compressor moves its k after each round by its own rule."""
    algo, prob = exp.algo, exp.problem
    algo._ensure_channels(prob.dim, prob.m_workers)
    eval_fn = prob.eval_fn if eval_fn is None else eval_fn
    X, y = prob.X_workers, prob.y_workers
    Xf, yf = X.reshape(-1, X.shape[-1]), y.reshape(-1)
    w, v, state = prob.w0, jnp.zeros_like(prob.w0), algo.init_comm_state()
    hist = {"loss": [], "grad_norm": [], "eval": [], "bits_cumulative": [],
            "uplink_delta": [], "cubic_iters": [], "k_trajectory": [],
            "saddle_escape_step": None, "truncated": False}
    up = down = 0
    for t in range(n_steps):
        key, sub = jax.random.split(key)
        k, bps = algo._uplink_k(), algo.bits_per_step()
        w, v, state, info = algo._step(w, v, state, X, y, sub)
        loss, gn = (float(a) for a in algo._pooled(w, Xf, yf))
        delta = float(info["uplink_delta"])
        hist["loss"].append(loss)
        hist["grad_norm"].append(gn)
        hist["uplink_delta"].append(delta)
        hist["cubic_iters"].append(int(info["cubic_iters"]))
        hist["k_trajectory"].append(k)
        if eval_fn is not None:
            hist["eval"].append(float(eval_fn(w)))
        up, down = up + bps["uplink"], down + bps["downlink"]
        hist["bits_cumulative"].append(up + down)
        if (prob.saddle_value is not None
                and hist["saddle_escape_step"] is None
                and loss < prob.saddle_value):
            hist["saddle_escape_step"] = t
        if grad_tol is not None and gn <= grad_tol:
            break
        algo._maybe_adapt(gn, measured_delta=delta)
    hist.update(uplink_bits=up, downlink_bits=down, total_bits=up + down,
                rounds=len(hist["loss"]) * algo.rounds_per_step)
    return w, hist


def _stretch_bounds(monkeypatch, tel):
    """Makes ``tel`` the global telemetry; returns the list that gets the
    round bound of each ``newton.stretch`` span it opens."""
    from repro.telemetry import core

    bounds, span = [], tel.span

    def spy(name, **attrs):
        if name == "newton.stretch":
            bounds.append(attrs["max_rounds"])
        return span(name, **attrs)

    monkeypatch.setattr(tel, "span", spy)
    monkeypatch.setattr(core, "_GLOBAL", tel)
    return bounds


def _below(x):
    """A float64 strictly between the float32 ``x`` and the float32 below
    it, nearer to ``x``: rounded to the nearest float32 it would be ``x``."""
    x = np.float32(x)
    return float(x) - 0.25 * (float(x) - float(np.nextafter(x, np.float32(0))))


def _assert_same_history(stretch, reference):
    (w_s, h_s), (w_r, h_r) = stretch, reference
    np.testing.assert_array_equal(np.asarray(w_s), np.asarray(w_r))
    assert h_s == h_r


@pytest.mark.parametrize("config, case", CASES)
def test_stretch_gives_the_host_loops_solve(config, case):
    """Every list of the history, the exact bits, the rounds, the saddle
    escape and the iterate match the round-at-a-time reference's on the
    same key."""
    exp = ExperimentSpec(**CONFIGS[config]).build()
    if config == "sparse-center":
        exp.algo._ensure_channels(exp.problem.dim, exp.problem.m_workers)
        assert exp.algo._use_sparse_center
    key = jax.random.PRNGKey(5)
    n_steps, grad_tol = 4, None
    if case == "two-stretches":
        n_steps = newton.STRETCH_ROUNDS + 3
    elif case != "cap":
        _, ref = _round_at_a_time(exp, 4, key)
        gn = ref["grad_norm"]
        i = int(np.argmin(gn[:3]))          # the lowest norm of rounds 0-2
        grad_tol = gn[i] if case == "eps-mid-stretch" else _below(gn[i])
    stretch = exp.run(n_steps, key=key, grad_tol=grad_tol)
    _assert_same_history(stretch,
                         _round_at_a_time(exp, n_steps, key, grad_tol))
    hist = stretch[1]
    if case == "two-stretches":
        assert len(hist["loss"]) == n_steps > newton.STRETCH_ROUNDS
    if grad_tol is not None:
        # the device's ε test is the host's float64 test on the same norms
        norms = hist["grad_norm"]
        assert all(g > grad_tol for g in norms[:-1])
        assert norms[-1] <= grad_tol or len(norms) == n_steps
        # ε met at round i, or just missed there by less than a float32 step
        assert len(norms) == i + 1 if case == "eps-mid-stretch" \
            else len(norms) > i + 1
    if config == "matrix-factor-escape":
        assert hist["saddle_escape_step"] is not None


def test_float32_at_most_is_the_host_test():
    for x in (0.1, 1e-3, 0.2, 3.0, 1e30, _below(0.7)):
        t = newton._float32_at_most(x)
        assert float(t) <= x < float(np.nextafter(t, np.float32(np.inf)))


def test_stretch_runs_every_round_with_fixed_compressors(monkeypatch):
    bounds = _stretch_bounds(monkeypatch, Telemetry())
    exp = ExperimentSpec(**CONFIGS["topk-ef21"]).build()
    _, hist = exp.run(5, key=jax.random.PRNGKey(1))
    assert hist["rounds"] == 5
    assert bounds == [5]              # all five rounds in one stretch


def _untraceable_accuracy(w):
    return float((w > 0).mean())


@pytest.mark.parametrize("why", ["telemetry", "adaptive_topk",
                                 "untraceable_eval"])
def test_host_loop_where_the_host_decides(why, tmp_path, monkeypatch):
    """Telemetry on, an adaptive compressor and an ``eval_fn`` that needs
    concrete values each make every stretch one round, with the host's
    work between them; the solve is the round-at-a-time reference's."""
    tel = Telemetry()
    if why == "telemetry":
        tel.enable(str(tmp_path / "telemetry"))
    bounds = _stretch_bounds(monkeypatch, tel)
    kw = dict(SMALL)
    if why == "adaptive_topk":
        kw["compressor"] = "adaptive_topk:0.25:0.9"
    exp = ExperimentSpec(**kw).build()
    eval_fn = _untraceable_accuracy if why == "untraceable_eval" else None
    key = jax.random.PRNGKey(2)
    try:
        solve = exp.run(3, key=key, eval_fn=eval_fn)
    finally:
        tel.disable()
    assert solve[1]["rounds"] == 3
    assert bounds == [1, 1, 1]        # one stretch a round
    _assert_same_history(solve, _round_at_a_time(
        ExperimentSpec(**kw).build(), 3, key, eval_fn=eval_fn))
    if why == "untraceable_eval":
        assert len(solve[1]["eval"]) == 3
        assert exp.algo._traces == {_untraceable_accuracy: False}


def test_past_deadline_gives_one_round_truncated(monkeypatch):
    bounds = _stretch_bounds(monkeypatch, Telemetry())
    exp = ExperimentSpec(**SMALL).build()
    _, hist = exp.run(10, key=jax.random.PRNGKey(3),
                      deadline=time.monotonic() - 1.0)
    assert hist["rounds"] == 1
    assert bounds == [1]
    assert hist["truncated"] is True


def test_second_run_compiles_nothing():
    exp = ExperimentSpec(**SMALL).build()
    first = CompileCounter()
    with first:
        exp.run(3, key=jax.random.PRNGKey(1))
    assert first.backend_compiles("newton.step") == 1
    again = CompileCounter()
    with again:
        exp.run(6, key=jax.random.PRNGKey(2), grad_tol=1e-3)
    assert again.backend_compiles(ANY) == 0


def test_step_compiles_what_run_runs(monkeypatch):
    """A ``step()`` compiles the stretch, test accuracy included, and
    returns the round's ``info``; a ``run()`` after it compiles nothing."""
    exp = ExperimentSpec(**CONFIGS["logistic-accuracy"]).build()
    prob = exp.problem
    cc = CompileCounter()
    with cc:
        w, v, state, info = exp.algo.step(prob.w0, prob.X_workers,
                                          prob.y_workers, jax.random.PRNGKey(0))
    assert cc.backend_compiles("newton.step") == 1
    assert set(info) == {"update_norms", "keep", "uplink_delta", "cubic_iters"}
    assert info["keep"].shape == (prob.m_workers,)
    # the round's key is step()'s own key, as in the jitted round
    w_ref, *_ = exp.algo._step(prob.w0, v * 0, exp.algo.init_comm_state(),
                               prob.X_workers, prob.y_workers,
                               jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_ref))
    bounds = _stretch_bounds(monkeypatch, Telemetry())
    after = CompileCounter()
    with after:
        _, hist = exp.run(3, key=jax.random.PRNGKey(4), grad_tol=1e-6)
    assert after.backend_compiles(ANY) == 0
    assert bounds == [3] and hist["rounds"] == len(hist["eval"]) == 3
