"""Algorithm 1 end-to-end on the paper's two problems (synthetic LIBSVM
twins): non-Byzantine convergence + robustness under all four attacks, and
the robust-vs-naive contrast that motivates norm thresholding."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.api import ExperimentSpec, problems
from repro.core import AttackConfig, DistributedCubicNewton, NewtonConfig
from repro.data import make_classification, make_regression, shard_to_workers


def logistic_loss(w, X, y):
    z = X @ w
    yy = 2.0 * y - 1.0
    return jnp.mean(jnp.log1p(jnp.exp(-yy * z))) + 0.5e-3 * w @ w


def robust_regression_loss(w, X, y):
    r = y - X @ w
    return jnp.mean(jnp.log(r * r / 2.0 + 1.0))


@pytest.fixture(scope="module")
def logistic_data():
    # margin=4 ⇒ near-separable (low Bayes floor) so loss-ratio assertions
    # measure the optimizer, not the noise floor.
    X, y, _ = make_classification(
        jax.random.PRNGKey(0), 2000, 20, margin=4.0, label_noise=0.01
    )
    Xm, ym = shard_to_workers(X, y, 10)
    return Xm, ym, X, y


@pytest.fixture(scope="module")
def regression_data():
    X, y, w_star = make_regression(jax.random.PRNGKey(1), 2000, 20)
    Xm, ym = shard_to_workers(X, y, 10)
    return Xm, ym, X, y, w_star


def test_nonbyzantine_convergence(logistic_data):
    Xm, ym, X, y = logistic_data
    algo = DistributedCubicNewton(logistic_loss, NewtonConfig(M=10.0, beta=0.0))
    w, hist = algo.run(jnp.zeros(20), Xm, ym, 15)
    assert hist["loss"][-1] < 0.55 * hist["loss"][0]
    assert hist["grad_norm"][-1] < 0.1


def test_fast_gradient_decay(logistic_data):
    """The second-order signature: large early progress (the 1/T^{2/3} rate
    shows up as few-round convergence in the paper's Table 1)."""
    Xm, ym, X, y = logistic_data
    algo = DistributedCubicNewton(logistic_loss, NewtonConfig(M=10.0, beta=0.0))
    w, hist = algo.run(jnp.zeros(20), Xm, ym, 8)
    assert hist["grad_norm"][-1] < 0.45 * hist["grad_norm"][0]


@pytest.mark.parametrize("attack", ["gaussian", "negative", "flipped_label", "random_label"])
def test_byzantine_robustness(logistic_data, attack):
    """All four §6 attacks at α=20%, β=α+2/m (the paper's setting)."""
    Xm, ym, X, y = logistic_data
    algo = DistributedCubicNewton(
        logistic_loss,
        NewtonConfig(M=10.0, beta=0.2 + 2 / 10),
        AttackConfig(name=attack, alpha=0.2),
    )
    w, hist = algo.run(jnp.zeros(20), Xm, ym, 15)
    assert hist["loss"][-1] < 0.75 * hist["loss"][0]
    acc = float(((X @ w > 0) == (y > 0.5)).mean())
    assert acc > 0.75


def test_robust_beats_naive_mean_under_gaussian_attack(logistic_data):
    Xm, ym, X, y = logistic_data
    atk = AttackConfig(name="gaussian", alpha=0.2, sigma=100.0)
    naive = DistributedCubicNewton(logistic_loss, NewtonConfig(beta=0.0), atk)
    robust = DistributedCubicNewton(logistic_loss, NewtonConfig(beta=0.4), atk)
    w_n, h_n = naive.run(jnp.zeros(20), Xm, ym, 10)
    w_r, h_r = robust.run(jnp.zeros(20), Xm, ym, 10)
    assert h_r["loss"][-1] < h_n["loss"][-1] - 0.05


def test_nonconvex_robust_regression(regression_data):
    Xm, ym, X, y, w_star = regression_data
    algo = DistributedCubicNewton(
        robust_regression_loss, NewtonConfig(M=10.0, beta=0.1)
    )
    w, hist = algo.run(jnp.zeros(20), Xm, ym, 25)
    assert hist["loss"][-1] < hist["loss"][0]
    # recovered the planted parameter despite outliers (the non-convex loss's
    # whole point)
    assert float(jnp.linalg.norm(w - w_star)) < 0.5 * float(jnp.linalg.norm(w_star))


def test_two_round_exact_gradient(logistic_data):
    """Remark 5: ε_g = 0 variant converges and counts 2 rounds per step."""
    Xm, ym, X, y = logistic_data
    algo = DistributedCubicNewton(
        logistic_loss, NewtonConfig(M=10.0, beta=0.1, exact_gradient=True)
    )
    w, hist = algo.run(jnp.zeros(20), Xm, ym, 10)
    assert hist["rounds"] == 20
    assert hist["grad_norm"][-1] < 0.1


def test_momentum_variant(logistic_data):
    """Beyond-paper: CR-with-momentum [WZLL20] converges at least as fast
    in early rounds as the paper's momentum-free Algorithm 1."""
    Xm, ym, X, y = logistic_data
    base = DistributedCubicNewton(logistic_loss, NewtonConfig(M=10.0, beta=0.1))
    mom = DistributedCubicNewton(
        logistic_loss,
        dataclasses.replace(NewtonConfig(M=10.0, beta=0.1), momentum=0.5),
    )
    _, h_b = base.run(jnp.zeros(20), Xm, ym, 10)
    _, h_m = mom.run(jnp.zeros(20), Xm, ym, 10)
    assert h_m["loss"][-1] <= h_b["loss"][-1] + 1e-3
    assert all(jnp.isfinite(jnp.asarray(h_m["loss"])))


@pytest.mark.parametrize("problem", ["synthetic-logistic:120:12",
                                     "synthetic-regression:120:12"])
def test_pooled_program_matches_separate_passes(problem):
    """The fused pooled program gives the loss and gradient norm of two
    separate passes, and a seeded run reads the same through either."""
    exp = ExperimentSpec(problem=problem, m_workers=4,
                         aggregator="norm_trim:0.3", attack="gaussian",
                         alpha=0.25).build()
    algo, prob = exp.algo, exp.problem
    assert algo.loss_fn in (problems.logistic_loss,
                            problems.robust_regression_loss)
    lossf = jax.jit(algo.loss_fn)
    gradf = jax.jit(jax.grad(algo.loss_fn))
    X = prob.X_workers.reshape(-1, prob.dim)
    y = prob.y_workers.reshape(-1)
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (prob.dim,))
    loss, gn = algo._pooled(w, X, y)
    assert float(loss) == pytest.approx(float(lossf(w, X, y)), rel=1e-6)
    assert float(gn) == pytest.approx(
        float(jnp.linalg.norm(gradf(w, X, y))), rel=1e-6)

    # a run's device stretches evaluate inside their program; the host
    # loop, given the two separate passes, reads the same
    key = jax.random.PRNGKey(7)
    _, fused = exp.run(4, key=key)
    assert fused["device_rounds"] == 4
    algo._pooled = lambda w, X, y: (lossf(w, X, y),
                                    jnp.linalg.norm(gradf(w, X, y)))
    _, separate = algo._solve(prob.w0, prob.X_workers, prob.y_workers, 4, key,
                              None, None, None, None, None)
    assert fused["rounds"] == separate["rounds"] == 4
    assert fused["total_bits"] == separate["total_bits"]
    for name in ("loss", "grad_norm"):
        assert fused[name] == pytest.approx(separate[name], rel=1e-6)
