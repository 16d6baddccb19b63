"""Pallas TPU flash attention (causal / sliding-window, fp32 accumulation).

TPU-native tiling (DESIGN.md §3): grid = (B·H, S/Bq, S/Bk) with the KV axis
innermost so the (max, sum-exp, accumulator) scratch carries across KV steps
for one query tile; block shapes are MXU-aligned (Bq, Bk multiples of 128,
head_dim lanes).  Per-tile VMEM footprint:

    q (Bq,Dh) + k,v (Bk,Dh) + acc (Bq,Dh) + logits (Bq,Bk)   ≈ 4·128·128·4B
                                                             « 16 MB VMEM.

Out-of-band tiles (fully above the causal diagonal / outside the window) are
skipped with ``pl.when`` — the kernel issues no MXU work for them, which is
the structural win over the masked dense form.

Validated in interpret mode on CPU against :func:`repro.kernels.ref.flash_attention_ref`
(this container has no TPU; interpret=True executes the same kernel body).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
               *, causal, window, block_q, block_k, n_k, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # visibility of this (q, k) tile pair
    relevant = True
    if causal:
        relevant = k_start <= q_start + block_q - 1  # some key ≤ some query
    if window > 0:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - window
        )

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (Bq, Dh)
        k = k_ref[0].astype(jnp.float32)          # (Bk, Dh)
        v = v_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                  # (Bq, Bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_scr[...] = l_prev * corr + p.sum(axis=-1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(q, k, v, *, causal=True, window=0,
                    block_q=128, block_k=128, interpret=None):
    """q,k,v: (B, H, S, Dh) → (B, H, S, Dh).  GQA is handled by the caller
    (repeat kv heads) — the kernel sees head-major already-matched tensors."""
    B, H, S, Dh = q.shape
    interpret = resolve_interpret(interpret)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    n_q, n_k = S // block_q, S // block_k
    scale = 1.0 / (Dh ** 0.5)

    qf = q.reshape(B * H, S, Dh)
    kf = k.reshape(B * H, S, Dh)
    vf = v.reshape(B * H, S, Dh)

    kernel = functools.partial(
        _fa_kernel, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),   # running max
            pltpu.VMEM((block_q,), jnp.float32),   # running sum-exp
            pltpu.VMEM((block_q, Dh), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, Dh)
