"""Problem catalog: spec strings → ready-to-run distributed problems.

The third registry of the facade.  A problem spec names the objective,
its data, and (optionally) its size; :func:`make_problem` materializes
worker-sharded data deterministically from the experiment seed:

    "a9a-logistic" / "w8a-logistic"      paper §6 logistic regression
    "a9a-robust"   / "w8a-robust"        paper §6 robust regression
    "synthetic-logistic:<n>:<d>"         separable classification twin
    "synthetic-regression:<n>:<d>"       heavy-tailed robust regression
    "matrix-factor:<d>:<r>"              low-rank factorization with a
                                         strict saddle at U = 0 (the
                                         saddle-escape testbed)
    "quadratic:<d>"                      tiny least-squares pytree
                                         problem for the MESH runtime

The canonical loss functions live here (they were previously duplicated
across benchmarks, examples, and tests).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..configs import PAPER_WORKLOADS
from ..data import (
    make_classification,
    make_regression,
    paper_dataset,
    shard_to_workers,
)
from .errors import SpecError

PROBLEM_SPECS = tuple(PAPER_WORKLOADS) + (
    "synthetic-logistic:<n>:<d>", "synthetic-regression:<n>:<d>",
    "matrix-factor:<d>:<r>", "quadratic:<d>",
)


# ---------------------------------------------------------------- losses
def logistic_loss(w, X, y):
    """Eq. (8): regularized logistic regression (λ/2n scaling as in paper)."""
    z = X @ w
    yy = 2.0 * y - 1.0
    return jnp.mean(jnp.log1p(jnp.exp(-yy * z))) + 0.5 / X.shape[0] * (w @ w)


def robust_regression_loss(w, X, y):
    """Eq. (9): non-convex robust linear regression."""
    r = y - X @ w
    return jnp.mean(jnp.log(r * r / 2.0 + 1.0))


def factor_loss(w, X, y):
    """¼‖UUᵀ − Σ‖²_F with w = flat U (d·r); strict saddle at U = 0."""
    del y
    n, d = X.shape
    r = w.shape[0] // d
    U = w.reshape(d, r)
    Sigma = X.T @ X / n
    R = U @ U.T - Sigma
    return 0.25 * jnp.sum(R * R)


def accuracy(w, X, y):
    """Share of rows classified right, as a jnp scalar (traceable)."""
    return ((X @ w > 0) == (y > 0.5)).mean()


# ---------------------------------------------------------------- catalog
@dataclasses.dataclass
class Problem:
    """Materialized problem: loss + worker-sharded data + metadata."""

    spec: str
    kind: str                 # "logistic" | "robust_regression" | ...
    loss_fn: Callable
    dim: int
    m_workers: int
    X_workers: jnp.ndarray = None
    y_workers: jnp.ndarray = None
    w0: jnp.ndarray = None
    X_full: jnp.ndarray = None
    y_full: jnp.ndarray = None
    X_test: Optional[jnp.ndarray] = None
    y_test: Optional[jnp.ndarray] = None
    w_star: Optional[jnp.ndarray] = None
    saddle_value: Optional[float] = None   # matrix-factor only
    batch: Optional[dict] = None           # mesh problems: worker batches

    @property
    def eval_fn(self) -> Optional[Callable]:
        """Test accuracy for classification problems, else None: a
        ``functools.partial`` of :func:`accuracy` over the test split, so
        the runtime can stage it on the device with the split as
        arguments."""
        if self.kind == "logistic" and self.X_test is not None:
            return functools.partial(accuracy, X=self.X_test, y=self.y_test)
        return None

    def accuracy(self, w) -> float:
        X = self.X_test if self.X_test is not None else self.X_full
        y = self.y_test if self.y_test is not None else self.y_full
        return float(accuracy(w, X, y))


def _ints(spec: str, arg: str, defaults: tuple) -> tuple:
    parts = [p for p in arg.split(":") if p]
    try:
        vals = tuple(int(p) for p in parts)
    except ValueError:
        raise SpecError(
            f"problem spec {spec!r}: size parameters must be integers"
        ) from None
    if len(vals) > len(defaults):
        raise SpecError(
            f"problem spec {spec!r}: at most {len(defaults)} parameters"
        )
    return vals + defaults[len(vals):]


def fixed_workers(spec: str) -> Optional[int]:
    """Cluster size a problem pins (the paper workloads partition over a
    fixed 20 machines); None when m_workers is free."""
    if spec in PAPER_WORKLOADS:
        return PAPER_WORKLOADS[spec].m_workers
    return None


def problem_dim(spec: str) -> Optional[int]:
    """The flat iterate dimension a spec implies (None for mesh problems
    whose params come from an external model)."""
    if spec in PAPER_WORKLOADS:
        return PAPER_WORKLOADS[spec].dim
    head, _, arg = spec.partition(":")
    if head in ("synthetic-logistic", "synthetic-regression"):
        return _ints(spec, arg, (4000, 40))[1]
    if head == "matrix-factor":
        d, r = _ints(spec, arg, (10, 2))
        return d * r
    if head == "quadratic":
        return _ints(spec, arg, (8,))[0] + 1    # w plus bias
    raise SpecError(
        f"unknown problem spec {spec!r}; expected one of {PROBLEM_SPECS}"
    )


def make_problem(spec: str, m_workers: int, seed: int = 0) -> Problem:
    """Materialize a problem's data deterministically from the seed.

    Memoized on ``(spec, m_workers, seed)``: sweeps (aggregator × attack
    grids share one dataset per cell row) reuse the same
    :class:`Problem` instead of regenerating identical arrays — safe
    because the jax arrays are immutable and the seed fully determines
    the data.
    """
    return _materialize(spec, int(m_workers), int(seed))


@functools.lru_cache(maxsize=4)
def _materialize(spec: str, m_workers: int, seed: int) -> Problem:
    if spec in PAPER_WORKLOADS:
        wl = PAPER_WORKLOADS[spec]
        data = paper_dataset(wl, seed)
        loss = logistic_loss if wl.problem == "logistic" else robust_regression_loss
        return Problem(
            spec=spec, kind=wl.problem, loss_fn=loss, dim=wl.dim,
            m_workers=wl.m_workers,
            X_workers=data["X_workers"], y_workers=data["y_workers"],
            w0=jnp.zeros(wl.dim),
            X_full=data["X_train"], y_full=data["y_train"],
            X_test=data["X_test"], y_test=data["y_test"],
        )

    head, _, arg = spec.partition(":")
    key = jax.random.PRNGKey(seed)

    if head == "synthetic-logistic":
        n, d = _ints(spec, arg, (4000, 40))
        X, y, w_star = make_classification(key, n, d, margin=3.0)
        Xw, yw = shard_to_workers(X, y, m_workers)
        return Problem(spec=spec, kind="logistic", loss_fn=logistic_loss,
                       dim=d, m_workers=m_workers, X_workers=Xw, y_workers=yw,
                       w0=jnp.zeros(d), X_full=X, y_full=y, w_star=w_star)

    if head == "synthetic-regression":
        n, d = _ints(spec, arg, (4000, 40))
        X, y, w_star = make_regression(key, n, d)
        Xw, yw = shard_to_workers(X, y, m_workers)
        return Problem(spec=spec, kind="robust_regression",
                       loss_fn=robust_regression_loss, dim=d,
                       m_workers=m_workers, X_workers=Xw, y_workers=yw,
                       w0=jnp.zeros(d), X_full=X, y_full=y, w_star=w_star)

    if head == "matrix-factor":
        d, r = _ints(spec, arg, (10, 2))
        n = 400
        ku, kx = jax.random.split(key)
        U_star = jax.random.normal(ku, (d, r))
        X = jax.random.normal(kx, (m_workers, n, r)) @ U_star.T
        X = X + 0.01 * jax.random.normal(
            jax.random.fold_in(kx, 1), (m_workers, n, d)
        )
        y = jnp.zeros(X.shape[:2])
        Xf = X.reshape(-1, d)
        # start NEXT to the strict saddle U = 0
        w0 = 1e-3 * jax.random.normal(jax.random.fold_in(key, 2), (d * r,))
        return Problem(
            spec=spec, kind="matrix_factor", loss_fn=factor_loss, dim=d * r,
            m_workers=m_workers, X_workers=X, y_workers=y, w0=w0,
            X_full=Xf, y_full=y.reshape(-1),
            saddle_value=float(factor_loss(jnp.zeros(d * r), Xf, None)),
        )

    if head == "quadratic":
        # mesh-runtime problem: params are a {"w", "b"} pytree, batches
        # carry a leading worker axis — the facade's both-runtimes testbed.
        (din,) = _ints(spec, arg, (8,))
        n = 32
        wstar = jax.random.normal(key, (din,))
        X = jax.random.normal(jax.random.fold_in(key, 1), (m_workers, n, din))
        Y = X @ wstar + 0.01 * jax.random.normal(
            jax.random.fold_in(key, 2), (m_workers, n)
        )

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"] + params["b"]
            return jnp.mean((pred - batch["y"]) ** 2)

        return Problem(spec=spec, kind="quadratic", loss_fn=loss_fn,
                       dim=din + 1, m_workers=m_workers,
                       w0={"w": jnp.zeros(din), "b": jnp.zeros(())},
                       w_star=wstar, batch={"x": X, "y": Y})

    raise SpecError(
        f"unknown problem spec {spec!r}; expected one of {PROBLEM_SPECS}"
    )
