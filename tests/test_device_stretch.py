"""Rounds run as device stretches: one ``lax.while_loop`` program a stretch,
with the ε test, the pooled evaluation and the test accuracy inside.

Pins that a stretch gives the host loop's solve exactly (same iterate, same
history, same round at which ε is met), that ``run()`` takes the host loop
wherever the host must act between rounds, and that the stretch compiles
once per runtime, in ``step()`` or the first ``run()``, never again.
"""
import time

import jax
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


def _host_loop(exp, n_steps, key, grad_tol=None, deadline=None):
    algo, prob = exp.algo, exp.problem
    algo._ensure_channels(prob.dim, prob.m_workers)
    return algo._solve(prob.w0, prob.X_workers, prob.y_workers, n_steps, key,
                       prob.eval_fn, grad_tol, None, deadline,
                       prob.saddle_value)


def _below(x):
    """A float64 strictly between the float32 ``x`` and the float32 below
    it, nearer to ``x``: rounded to the nearest float32 it would be ``x``."""
    x = np.float32(x)
    return float(x) - 0.25 * (float(x) - float(np.nextafter(x, np.float32(0))))


def _assert_same_solve(stretch, host):
    (w_s, h_s), (w_h, h_h) = stretch, host
    np.testing.assert_array_equal(np.asarray(w_s), np.asarray(w_h))
    assert h_h["device_rounds"] == 0
    assert h_s["device_rounds"] == h_s["rounds"]
    assert {k: v for k, v in h_s.items() if k != "device_rounds"} \
        == {k: v for k, v in h_h.items() if k != "device_rounds"}


@pytest.mark.parametrize("config, case", CASES)
def test_stretch_gives_the_host_loops_solve(config, case):
    """Every list of the history, the exact bits, the rounds, the saddle
    escape and the iterate match the host loop's on the same key."""
    exp = ExperimentSpec(**CONFIGS[config]).build()
    if config == "sparse-center":
        exp.algo._ensure_channels(exp.problem.dim, exp.problem.m_workers)
        assert exp.algo._use_sparse_center
    key = jax.random.PRNGKey(5)
    n_steps, grad_tol = 4, None
    if case == "two-stretches":
        n_steps = newton.STRETCH_ROUNDS + 3
    elif case != "cap":
        _, ref = _host_loop(exp, 4, key)
        gn = ref["grad_norm"]
        i = int(np.argmin(gn[:3]))          # the lowest norm of rounds 0-2
        grad_tol = gn[i] if case == "eps-mid-stretch" else _below(gn[i])
    stretch = exp.run(n_steps, key=key, grad_tol=grad_tol)
    _assert_same_solve(stretch, _host_loop(exp, n_steps, key, grad_tol))
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


def test_stretch_runs_every_round_with_fixed_compressors():
    exp = ExperimentSpec(**CONFIGS["topk-ef21"]).build()
    _, hist = exp.run(5, key=jax.random.PRNGKey(1))
    assert hist["device_rounds"] == hist["rounds"] == 5


def _untraceable_accuracy(w):
    return float((w > 0).mean())


@pytest.mark.parametrize("why", ["telemetry", "adaptive_topk",
                                 "untraceable_eval"])
def test_host_loop_where_the_host_decides(why, tmp_path, monkeypatch):
    """Telemetry on, an adaptive compressor and an ``eval_fn`` that needs
    concrete values each keep the host loop: no round runs on the device."""
    from repro.telemetry import core

    tel = Telemetry()
    if why == "telemetry":
        tel.enable(str(tmp_path / "telemetry"))
    monkeypatch.setattr(core, "_GLOBAL", tel)
    kw = dict(SMALL)
    if why == "adaptive_topk":
        kw["compressor"] = "adaptive_topk:0.25:0.9"
    exp = ExperimentSpec(**kw).build()
    eval_fn = _untraceable_accuracy if why == "untraceable_eval" else None
    try:
        _, hist = exp.run(3, key=jax.random.PRNGKey(2), eval_fn=eval_fn)
    finally:
        tel.disable()
    assert hist["rounds"] == 3
    assert hist["device_rounds"] == 0
    if why == "untraceable_eval":
        assert len(hist["eval"]) == 3
        assert exp.algo._traces == {_untraceable_accuracy: False}


def test_past_deadline_gives_one_round_truncated():
    exp = ExperimentSpec(**SMALL).build()
    _, hist = exp.run(10, key=jax.random.PRNGKey(3),
                      deadline=time.monotonic() - 1.0)
    assert hist["rounds"] == hist["device_rounds"] == 1
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


def test_step_compiles_what_run_runs():
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
    after = CompileCounter()
    with after:
        _, hist = exp.run(3, key=jax.random.PRNGKey(4), grad_tol=1e-6)
    assert after.backend_compiles(ANY) == 0
    assert hist["device_rounds"] == 3 and len(hist["eval"]) == 3
