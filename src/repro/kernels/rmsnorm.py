"""Pallas TPU kernel: RMSNorm (row-tiled, fp32 accumulation).

Grid tiles the row axis; each step normalizes a (block_rows, d) tile fully
in VMEM — one HBM read + one write per element instead of the separate
square/mean/rsqrt/scale kernels XLA would otherwise emit on the norm-heavy
decode path.  d up to ~16k fp32 at block_rows=128 stays ≈ 8 MB < VMEM.

Validated in interpret mode against :func:`repro.kernels.ref.rmsnorm_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)          # (rows, d)
    w = w_ref[...].astype(jnp.float32)          # (d,)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * (1.0 + w)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, w, *, eps=1e-6, block_rows=128, interpret=None):
    """x: (N, d); w: (d,)."""
    interpret = resolve_interpret(interpret)
    N, d = x.shape
    block_rows = min(block_rows, N)
    assert N % block_rows == 0, (N, block_rows)
    kernel = functools.partial(_rms_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(N // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
        interpret=interpret,
    )(x, w)
