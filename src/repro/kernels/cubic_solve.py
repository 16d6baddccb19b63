"""Pallas TPU kernel: Algorithm 2 for all m workers in one launch.

Every worker i runs the paper's Algorithm 2 on its own gradient g_i and
Hessian H_i (explicit, the LIBSVM regime, d ≤ a few hundred):

    s ← 0;  G ← g
    while ‖G‖ > τ and it < max_iters:
        s ← s − ξ_i G
        G ← g + γ H s + (Mγ²/2) ‖s‖ s

One ``pallas_call`` runs the whole loop for the stacked (m, d) gradients
and (m, d, d) Hessians.  The Hessians are loaded into VMEM once, transposed
(so that H_i s_i contracts over sublanes: a column of s times each row of
Hᵀ, summed down the rows), and stay there for every iteration; s, G and
the iteration counts stay on chip.  The loop masks workers as the vmapped
``while_loop`` does: each iteration first takes every worker's active flag
from the current G, only active workers update, and the loop ends when
none is active, so worker i stops at its own count.  Everything is f32 on
the vector unit (no MXU pass, whose bf16 inputs would lower the precision);
only the order of the sums differs from the XLA loop.

Padding: d to a multiple of 128 lanes (8 sublanes on the contracted axis
of Hᵀ), the worker rows of g, s and G to a multiple of 8, all with zeros.
Zero rows and columns keep s and G at exactly 0, so no norm moves, and a
padded worker (‖G‖ = 0) is never active.  Validated in interpret mode
against the vmapped :func:`repro.core.cubic.solve_cubic_gd_counted`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

# VMEM the launch asks for beyond its transposed Hessian stack: g, s, G,
# the matvec rows and each worker's (d, d) product
CUBIC_VMEM_HEADROOM = 16 * 2**20


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def _padded_shape(m: int, d: int) -> tuple:
    """``(P, dk, dj)``: padded worker rows, and the sublane (contracted)
    and lane extents of each transposed Hessian in VMEM."""
    return _round_up(m, 8), _round_up(d, 8), _round_up(d, 128)


def cubic_stack_bytes(m: int, d: int) -> int:
    """Bytes of the transposed, padded f32 Hessian stack the kernel holds
    in VMEM: (m, dk, dj)."""
    _, dk, dj = _padded_shape(m, d)
    return 4 * m * dk * dj


def _solve_kernel(lr_ref, g_ref, ht_ref, s_ref, it_ref, hs_ref, *, M, gamma,
                  tol, max_iters):
    m, dk, _ = ht_ref.shape
    g = g_ref[...]                          # (P, dj)
    lr = lr_ref[...]                        # (P, 1)
    c = 0.5 * M * gamma**2
    hs_ref[...] = jnp.zeros_like(hs_ref)    # the padded rows stay 0

    def norm(x):
        return jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))   # (P, 1)

    def active(it, G):
        # int32, not bool: Mosaic carries no i1 vector through a loop
        return ((norm(G) > tol) & (it < max_iters)).astype(jnp.int32)

    def matvec(s):
        # (H_i s_i)_j = Σ_k Hᵀ_i[k, j] s_i[k]: column i of sᵀ down the
        # sublanes of Hᵀ_i, one row of the result a worker
        st = s.T                            # (dj, P)
        for i in range(m):
            hs_ref[i:i + 1, :] = jnp.sum(ht_ref[i] * st[:dk, i:i + 1],
                                         axis=0, keepdims=True)
        return hs_ref[...]

    def body(carry):
        it, s, G, act = carry
        s_new = s - lr * G
        G_new = g + gamma * matvec(s_new) + c * norm(s_new) * s_new
        on = act > 0
        s = jnp.where(on, s_new, s)
        G = jnp.where(on, G_new, G)
        it = it + act
        return it, s, G, active(it, G)

    it0 = jnp.zeros(lr.shape, jnp.int32)
    it, s, _, _ = jax.lax.while_loop(
        lambda carry: jnp.max(carry[3]) > 0, body,
        (it0, jnp.zeros_like(g), g, active(it0, g)))
    s_ref[...] = s
    it_ref[...] = jnp.broadcast_to(it, it_ref.shape)


@functools.partial(jax.jit, static_argnames=("M", "gamma", "tol",
                                             "max_iters", "interpret"))
def cubic_solve_stacked(g, H, lr, *, M=10.0, gamma=1.0, tol=1e-6,
                        max_iters=2000, interpret=None):
    """Algorithm 2 for every worker in one launch: g (m, d), H (m, d, d),
    lr (m,) the per-worker step ξ_i → ``(s (m, d), iterations (m,) int32)``.
    Call it on the stacked arrays, never under ``vmap`` (the batching rule
    would make the worker axis a sequential grid)."""
    interpret = resolve_interpret(interpret)
    m, d = g.shape
    P, dk, dj = _padded_shape(m, d)
    f32 = jnp.float32
    gp = jnp.pad(g.astype(f32), ((0, P - m), (0, dj - d)))
    lrp = jnp.pad(lr.astype(f32)[:, None], ((0, P - m), (0, 0)))
    ht = jnp.pad(jnp.swapaxes(H.astype(f32), 1, 2),
                 ((0, 0), (0, dk - d), (0, dj - d)))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    s, it = pl.pallas_call(
        functools.partial(_solve_kernel, M=M, gamma=gamma, tol=tol,
                          max_iters=max_iters),
        in_specs=[vmem, vmem, vmem],
        out_specs=[vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct((P, dj), f32),
                   jax.ShapeDtypeStruct((P, 128), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((P, dj), f32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=cubic_stack_bytes(m, d) + CUBIC_VMEM_HEADROOM),
        interpret=interpret,
    )(lrp, gp, ht)
    return s[:m, :d], it[:m, 0]
