"""Algorithm 2 for all m workers as one Pallas kernel, against the vmapped
``while_loop`` it replaces on a TPU.

The kernel runs here in interpret mode.  It sums ``H s`` and the norms in
another order than XLA, so its float32 iterates differ in the last bits.
Where ‖G‖ crosses τ within a float32 rounding of G (about 1e-7 of ‖g‖),
that can move a worker's stop by one iteration.  So the exact iteration
counts are compared where τ lies well above that rounding (τ = 1e-3·‖g_i‖),
and the configured τ = 1e-6 is compared on the iterates, with counts
within one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec
from repro.core import cubic, newton
from repro.core.cubic import (
    _default_lr,
    cubic_kernel_plan,
    solve_cubic_gd_batched,
    solve_cubic_gd_counted,
)
from repro.kernels import cubic_solve_stacked
from repro.telemetry import Telemetry

M, GAMMA = 10.0, 1.0


def _workers(seed, m, d, gnorm=1.0):
    """m random symmetric, indefinite Hessians and gradients of norm
    ``gnorm``; worker 1's gradient is 0."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    A = jax.random.normal(k1, (m, d, d)) / np.sqrt(d)
    H = (A + jnp.swapaxes(A, 1, 2)) / 2
    g = jax.random.normal(k2, (m, d))
    g = gnorm * g / jnp.linalg.norm(g, axis=1, keepdims=True)
    return g.at[1].set(0.0), H


def _vmapped(g, H, tol, max_iters):
    return jax.vmap(lambda gi, Hi: solve_cubic_gd_counted(
        gi, Hi, M, GAMMA, None, tol, max_iters))(g, H)


def _kernel(g, H, tol, max_iters):
    lr = jax.vmap(_default_lr, in_axes=(0, None, None))(H, M, GAMMA)
    return cubic_solve_stacked(g, H, lr, M=M, gamma=GAMMA, tol=tol,
                               max_iters=max_iters, interpret=True)


def _rel_gap(s, s_ref):
    s, s_ref = np.asarray(s), np.asarray(s_ref)
    return np.linalg.norm(s - s_ref, axis=1) / np.maximum(
        np.linalg.norm(s_ref, axis=1), 1e-30)


@pytest.mark.parametrize("m, d", [(20, 300), (20, 123), (3, 128), (9, 37)])
def test_kernel_matches_vmapped_loop(m, d):
    """The same iterations for every worker (0 for the one with g = 0)
    and the same s within 1e-5; d a multiple of 128 or not, m of 8 or
    not."""
    g, H = _workers(0, m, d)
    s_ref, it_ref = _vmapped(g, H, 1e-3, 500)
    s, it = _kernel(g, H, 1e-3, 500)
    assert it.dtype == jnp.int32 and it.shape == (m,)
    np.testing.assert_array_equal(np.asarray(it), np.asarray(it_ref))
    assert int(it[1]) == 0 and not np.any(np.asarray(s[1]))
    assert np.delete(np.asarray(it_ref), 1).min() > 10
    assert _rel_gap(s, s_ref).max() < 1e-5


@pytest.mark.parametrize("m, d", [(20, 300), (20, 123)])
def test_kernel_at_the_configured_tolerance(m, d):
    """τ = 1e-6, as the cells run: s within 1e-5 of the vmapped loop's,
    each worker's count within one iteration of its own."""
    g, H = _workers(1, m, d)
    s_ref, it_ref = _vmapped(g, H, 1e-6, 500)
    s, it = _kernel(g, H, 1e-6, 500)
    assert np.abs(np.asarray(it) - np.asarray(it_ref)).max() <= 1
    assert _rel_gap(s, s_ref).max() < 1e-5


def test_kernel_stops_workers_at_max_iters():
    """A cap that some workers reach and others do not: the capped ones
    stop at it, the rest at their own counts, as in the vmapped loop."""
    g, H = _workers(2, 8, 64)            # uncapped: 51 to 64 iterations
    s_ref, it_ref = _vmapped(g, H, 1e-3, 58)
    s, it = _kernel(g, H, 1e-3, 58)
    np.testing.assert_array_equal(np.asarray(it), np.asarray(it_ref))
    it = np.asarray(it)
    assert 0 < it[it != 58].max() < 58 and (it == 58).sum() >= 3
    assert _rel_gap(s, s_ref).max() < 1e-5


def test_shared_gradient_as_the_two_round_mode_calls_it():
    """Remark 5's global gradient, one (d,) vector for every worker: the
    batched solve on its broadcast equals the vmapped loop that closes
    over it."""
    g, H = _workers(3, 20, 123)
    g0 = g[0]
    s_ref, it_ref = jax.vmap(lambda Hi: solve_cubic_gd_counted(
        g0, Hi, M, GAMMA, None, 1e-3, 500))(H)
    s, it = _kernel(jnp.broadcast_to(g0, (20, 123)), H, 1e-3, 500)
    np.testing.assert_array_equal(np.asarray(it), np.asarray(it_ref))
    assert _rel_gap(s, s_ref).max() < 1e-5


def test_plan_takes_the_vmapped_loop_off_the_tpu():
    """On the CPU the batched solve is the vmapped loop, bit for bit."""
    assert cubic_kernel_plan(20, 300) == "xla"
    g, H = _workers(4, 5, 40)
    s, it = jax.jit(lambda g, H: solve_cubic_gd_batched(
        g, H, M=M, gamma=GAMMA, tol=1e-6, max_iters=500))(g, H)
    s_ref, it_ref = jax.jit(lambda g, H: _vmapped(g, H, 1e-6, 500))(g, H)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(it), np.asarray(it_ref))


def test_plan_on_a_tpu_follows_the_vmem_budget(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cubic_kernel_plan(20, 300) == "pallas"
    assert cubic_kernel_plan(20, 123) == "pallas"
    assert cubic_kernel_plan(20, 1024) == "xla"       # 80 MiB of Hessians
    assert cubic_kernel_plan(cubic.CUBIC_MAX_WORKERS + 1, 8) == "xla"


def _force_kernel(monkeypatch):
    plan = lambda m, d: "pallas"
    monkeypatch.setattr(cubic, "cubic_kernel_plan", plan)
    monkeypatch.setattr(newton, "cubic_kernel_plan", plan)


def _solver_spans(monkeypatch):
    """The ``solver`` of each ``newton.stretch`` span the run opens."""
    from repro.telemetry import core

    tel, seen = Telemetry(), []
    span = tel.span

    def spy(name, **attrs):
        if name == "newton.stretch":
            seen.append(attrs["solver"])
        return span(name, **attrs)

    monkeypatch.setattr(tel, "span", spy)
    monkeypatch.setattr(core, "_GLOBAL", tel)
    return seen


SMALL = dict(problem="synthetic-regression:120:12", m_workers=4,
             aggregator="norm_trim:0.3", attack="saddle", alpha=0.25)


@pytest.mark.parametrize("kw", [SMALL, dict(SMALL, exact_gradient=True)],
                         ids=["saddle-robust", "exact-gradient"])
def test_runtime_solve_with_the_kernel(kw, monkeypatch):
    """A paper-runtime solve to ε with the kernel forced: the rounds and
    the ledger's bits of the XLA path's solve, the iterate within 1e-5;
    the stretch spans name the solver each one ran."""
    seen = _solver_spans(monkeypatch)
    key = jax.random.PRNGKey(7)
    xla = ExperimentSpec(**kw).build()
    w_ref, h_ref = xla.run(20, key=key, grad_tol=0.1)
    assert xla.algo.cubic_solver == "xla"
    _force_kernel(monkeypatch)
    fused = ExperimentSpec(**kw).build()
    w, hist = fused.run(20, key=key, grad_tol=0.1)
    assert fused.algo.cubic_solver == "pallas"
    assert seen == ["xla", "pallas"]
    assert hist["grad_norm"][-1] <= 0.1 and hist["rounds"] < 20
    assert hist["rounds"] == h_ref["rounds"]
    assert hist["bits_cumulative"] == h_ref["bits_cumulative"]
    assert hist["total_bits"] == h_ref["total_bits"]
    rel = float(jnp.linalg.norm(w - w_ref) / jnp.linalg.norm(w_ref))
    assert rel < 1e-5


def test_async_round_with_the_kernel(monkeypatch):
    """The async runtime's all-worker compute goes through the same
    batched solve: its reconstructed updates match the XLA path's."""
    kw = dict(SMALL, runtime="async", participation=0.5, staleness=2)
    key = jax.random.PRNGKey(8)
    xla = ExperimentSpec(**kw).build()
    w_ref, h_ref = xla.run(3, key=key)
    _force_kernel(monkeypatch)
    w, hist = ExperimentSpec(**kw).build().run(3, key=key)
    assert hist["total_bits"] == h_ref["total_bits"]
    rel = float(jnp.linalg.norm(w - w_ref) / jnp.linalg.norm(w_ref))
    assert rel < 1e-5
