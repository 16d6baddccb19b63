"""Cubic sub-problem solvers (the inner problem of the paper's Algorithm 1).

Every worker solves, on its *local* gradient g and Hessian H (Eq. (2)):

    s* = argmin_s  gᵀs + (γ/2) sᵀHs + (M γ²/6) ‖s‖³

Three solvers are provided:

* :func:`solve_cubic_exact` — eigendecomposition + 1-D root finding on the
  Nesterov–Polyak secular equation.  Only feasible for small d (the paper's
  LIBSVM regime, d ≤ 300).  Used as the test oracle.
* :func:`solve_cubic_gd` — the paper's Algorithm 2: plain gradient descent on
  the sub-problem with explicit H, run as a ``lax.while_loop`` on ‖G‖ > τ
  (iteration-capped so it always terminates under jit);
  :func:`solve_cubic_gd_counted` also returns the loop's trip count, and
  :func:`solve_cubic_gd_batched` runs it for m workers at once — as one
  Pallas kernel on a TPU where the Hessians fit in VMEM
  (:func:`cubic_kernel_plan`), else as the vmapped loop.
* :func:`solve_cubic_hvp` — matrix-free Algorithm 2 for pytree parameters:
  H·s is a Hessian-vector product closure (two backprops), the loop is a
  ``lax.fori_loop`` with a fixed iteration count so the distributed train
  step lowers to a static program.  This is the TPU-scale adaptation noted
  in DESIGN.md §3.

First-order optimality (Lemma 4, Eq. 16):  g + γHs + (Mγ²/2)‖s‖ s = 0.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels.cubic_solve import cubic_solve_stacked, cubic_stack_bytes
from .tree_util import (
    tree_axpy,
    tree_norm,
    tree_zeros_like,
)


class CubicParams(NamedTuple):
    """Hyper-parameters of the sub-problem (paper's M, γ)."""

    M: float = 10.0
    gamma: float = 1.0


# ---------------------------------------------------------------------------
# Exact solver (small d) — the oracle
# ---------------------------------------------------------------------------


def _secular_norm(r, evals, u, M, gamma):
    """‖ (γΛ + (Mγ²/2) r)^{-1} u ‖ for the secular equation."""
    denom = gamma * evals + 0.5 * M * gamma**2 * r
    return jnp.sqrt(jnp.sum((u / denom) ** 2))


@partial(jax.jit, static_argnames=("n_bisect",))
def solve_cubic_exact(g, H, M=10.0, gamma=1.0, n_bisect=100):
    """Nesterov–Polyak exact solution via eigendecomposition + bisection.

    The stationarity condition gives ``s = -(γH + (Mγ²/2) r I)^{-1} g`` where
    ``r = ‖s‖`` must satisfy the secular equation
    ``φ(r) := ‖(γH + (Mγ²/2) r I)^{-1} g‖ − r = 0`` on
    ``r > max(0, −2λ_min(H)/(Mγ))`` (where the shifted matrix is PD).  φ is
    strictly decreasing there, so bisection converges.
    """
    evals, evecs = jnp.linalg.eigh(H)
    u = evecs.T @ g
    lam_min = evals[0]
    r_lo = jnp.maximum(0.0, -2.0 * lam_min / (M * gamma)) + 1e-12
    # Upper bound: ‖s‖ ≤ r_lo + sqrt(2‖g‖/(Mγ²)) + 2‖g‖/(γ|λ|) slack.
    gnorm = jnp.linalg.norm(g)
    r_hi = r_lo + jnp.sqrt(2.0 * gnorm / (M * gamma**2) + 1e-12) + gnorm / (
        0.5 * M * gamma**2 * (r_lo + 1e-6)
    )

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        phi = _secular_norm(mid, evals, u, M, gamma) - mid
        lo = jnp.where(phi > 0, mid, lo)
        hi = jnp.where(phi > 0, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_bisect, body, (r_lo, r_hi))
    r = 0.5 * (lo + hi)
    denom = gamma * evals + 0.5 * M * gamma**2 * r
    s = -(evecs @ (u / denom))
    return s


# ---------------------------------------------------------------------------
# Algorithm 2 — gradient-based cubic solver (explicit Hessian)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("max_iters",))
def solve_cubic_gd(g, H, M=10.0, gamma=1.0, lr=None, tol=1e-6, max_iters=2000):
    """The paper's Algorithm 2, verbatim (with an iteration cap for jit).

        s ← 0;  G ← g
        while ‖G‖ > τ:
            s ← s − ξ G
            G ← g + γ H s + (Mγ²/2) ‖s‖ s
    """
    return solve_cubic_gd_counted(g, H, M, gamma, lr, tol, max_iters)[0]


def _default_lr(H, M, gamma):
    """1/(γ(‖H‖_F+Mγ)): a safe step for the smooth part of the sub-problem."""
    return 1.0 / (gamma * (jnp.linalg.norm(H, ord="fro") + M * gamma) + 1e-8)


@partial(jax.jit, static_argnames=("max_iters",))
def solve_cubic_gd_counted(g, H, M=10.0, gamma=1.0, lr=None, tol=1e-6,
                           max_iters=2000):
    """:func:`solve_cubic_gd` that also returns the loop's trip count:
    ``(s, iterations)``, an int32 — the data-dependent factor in the
    round's device time."""
    if lr is None:
        lr = _default_lr(H, M, gamma)

    def cond(state):
        it, s, G = state
        return jnp.logical_and(jnp.linalg.norm(G) > tol, it < max_iters)

    def body(state):
        it, s, G = state
        s = s - lr * G
        G = g + gamma * (H @ s) + 0.5 * M * gamma**2 * jnp.linalg.norm(s) * s
        return it + 1, s, G

    it, s, _ = jax.lax.while_loop(cond, body, (0, jnp.zeros_like(g), g))
    return s, it


# The transposed, padded Hessian stack the kernel may hold in VMEM (a TPU
# v5e core has 128 MiB of it); a larger stack takes the vmapped loop
CUBIC_VMEM_BUDGET = 48 * 2**20
# workers the kernel unrolls its matvec over
CUBIC_MAX_WORKERS = 128


def cubic_kernel_plan(m: int, d: int) -> str:
    """Which implementation :func:`solve_cubic_gd_batched` runs for m
    workers at dimension d: ``"pallas"`` (one kernel launch) when JAX's
    backend is a TPU, m ≤ :data:`CUBIC_MAX_WORKERS` and the padded Hessian
    stack fits :data:`CUBIC_VMEM_BUDGET`; else ``"xla"`` (the vmapped
    ``while_loop``): on the CPU, and at large d."""
    if (jax.default_backend() == "tpu" and m <= CUBIC_MAX_WORKERS
            and cubic_stack_bytes(m, d) <= CUBIC_VMEM_BUDGET):
        return "pallas"
    return "xla"


def solve_cubic_gd_batched(g, H, M=10.0, gamma=1.0, tol=1e-6, max_iters=2000):
    """Algorithm 2 for m workers at once: g (m, d), H (m, d, d) →
    ``(s (m, d), iterations (m,) int32)``, worker i stopping at its own
    count as :func:`solve_cubic_gd_counted` alone would.  The kernel sums
    in another order, so its s may differ in the last bits, and a count
    by one where ‖G‖ meets τ within float32 rounding.  ``M``, ``gamma``,
    ``tol`` and ``max_iters`` are Python numbers; the implementation is
    :func:`cubic_kernel_plan`'s for the shapes."""
    m, d = g.shape
    if cubic_kernel_plan(m, d) == "pallas":
        lr = jax.vmap(_default_lr, in_axes=(0, None, None))(H, M, gamma)
        return cubic_solve_stacked(g, H, lr, M=M, gamma=gamma, tol=tol,
                                   max_iters=max_iters)
    return jax.vmap(lambda gi, Hi: solve_cubic_gd_counted(
        gi, Hi, M=M, gamma=gamma, tol=tol, max_iters=max_iters))(g, H)


# ---------------------------------------------------------------------------
# Matrix-free Algorithm 2 (pytrees, HVP closure) — the at-scale path
# ---------------------------------------------------------------------------


def make_hvp(loss_fn: Callable, params, *batch):
    """Return ``hvp(v) = ∇²f(params)·v`` as a pytree→pytree closure.

    Forward-over-reverse: jvp of grad — two backprop-equivalents per call,
    exact (no finite differences).  ``loss_fn(params, *batch) -> scalar``.
    """
    grad_fn = lambda p: jax.grad(loss_fn)(p, *batch)

    def hvp(v):
        return jax.jvp(grad_fn, (params,), (v,))[1]

    return hvp


def solve_cubic_hvp(
    g,
    hvp: Callable,
    M: float = 10.0,
    gamma: float = 1.0,
    lr: float | None = None,
    n_iters: int = 8,
    norm_fn: Callable = tree_norm,
):
    """Algorithm 2 on a pytree with matrix-free H·s.

    ``norm_fn`` computes the *global* ‖s‖ — in the sharded setting it must
    psum partial squares over the model axis (see core/newton.py), which is
    why it is injectable.  A fixed ``fori_loop`` keeps the lowered program
    static (DESIGN.md §8.2); n_iters plays the role of τ.
    """
    if lr is None:
        # Scale-free default: trust Algorithm 2 with a conservative step.
        gn = norm_fn(g)
        lr = 1.0 / (gamma * (gn + M * gamma) + 1e-8)

    def body(_, s):
        Hs = hvp(s)
        sn = norm_fn(s)
        # s ← s − ξ G,  G = g + γ H s + (Mγ²/2)‖s‖ s    (kept in leaf dtype so
        # the fori_loop carry matches bf16 params exactly)
        return jax.tree_util.tree_map(
            lambda gi, hsi, si: (
                si.astype(jnp.float32)
                - lr
                * (
                    gi.astype(jnp.float32)
                    + gamma * hsi.astype(jnp.float32)
                    + 0.5 * M * gamma**2 * sn * si.astype(jnp.float32)
                )
            ).astype(si.dtype),
            g,
            Hs,
            s,
        )

    return jax.lax.fori_loop(0, n_iters, body, tree_zeros_like(g))


def cubic_model_value(s, g, H, M=10.0, gamma=1.0):
    """Sub-problem objective value m(s) — used by tests & property checks."""
    return (
        g @ s
        + 0.5 * gamma * s @ (H @ s)
        + M / 6.0 * gamma**2 * jnp.linalg.norm(s) ** 3
    )


def cubic_residual(s, g, H, M=10.0, gamma=1.0):
    """‖g + γHs + (Mγ²/2)‖s‖s‖ — first-order stationarity residual (Eq. 16)."""
    G = g + gamma * (H @ s) + 0.5 * M * gamma**2 * jnp.linalg.norm(s) * s
    return jnp.linalg.norm(G)
