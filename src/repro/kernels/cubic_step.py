"""Pallas TPU kernel: fused Algorithm-2 inner iteration (explicit Hessian).

The paper's cubic solver inner loop (Algorithm 2) on the LIBSVM-scale
problems (d ≤ ~1k) is a chain of small ops — matvec, norm, three axpys —
each of which would round-trip HBM as a separate XLA kernel.  This kernel
fuses one full iteration

    G = g + γ·H s + (Mγ²/2)·‖s‖·s ;   s ← s − ξ·G

into a single VMEM-resident pass: H is tiled (block_d rows at a time, each
row tile (block_d, d) in VMEM), the matvec accumulates in fp32, and the norm
is computed once from s which stays resident.  For d=300 (w8a) the whole
state is (300² + 3·300)·4B ≈ 360 KB — comfortably inside the ~16 MB VMEM,
so the default is a single-tile launch.

Validated in interpret mode against :func:`repro.kernels.ref.cubic_step_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret


def _cubic_kernel(s_ref, g_ref, h_ref, o_ref, *, M, gamma, lr):
    s = s_ref[...].astype(jnp.float32)      # (d,)
    g = g_ref[...].astype(jnp.float32)      # (d,)
    H = h_ref[...].astype(jnp.float32)      # (d, d)
    sn = jnp.sqrt(jnp.sum(s * s))
    Hs = jax.lax.dot_general(
        H, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    G = g + gamma * Hs + 0.5 * M * gamma**2 * sn * s
    o_ref[...] = (s - lr * G).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("M", "gamma", "lr", "interpret")
)
def cubic_step(s, g, H, *, M=10.0, gamma=1.0, lr=1e-2, interpret=None):
    """One fused Algorithm-2 iteration.  s,g: (d,), H: (d,d)."""
    interpret = resolve_interpret(interpret)
    d = s.shape[0]
    kernel = functools.partial(_cubic_kernel, M=M, gamma=gamma, lr=lr)
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[
            pl.BlockSpec((d,), lambda: (0,)),
            pl.BlockSpec((d,), lambda: (0,)),
            pl.BlockSpec((d, d), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((d,), lambda: (0,)),
        out_shape=jax.ShapeDtypeStruct((d,), s.dtype),
        interpret=interpret,
    )(s, g, H)


def cubic_solve_fused(g, H, *, M=10.0, gamma=1.0, lr=None, n_iters=200,
                      interpret=None):
    """Full Algorithm-2 run with the fused kernel as the loop body."""
    if lr is None:
        lr = float(1.0 / (gamma * (jnp.linalg.norm(H) + M * gamma) + 1e-8))
    step = functools.partial(
        cubic_step, M=M, gamma=gamma, lr=lr, interpret=interpret
    )
    def body(_, s):
        return step(s, g, H)
    return jax.lax.fori_loop(0, n_iters, body, jnp.zeros_like(g))
