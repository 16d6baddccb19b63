"""jit'd public wrappers around the Pallas kernels.

These present model-layer-friendly signatures (GQA head matching, layout
transposes) so call sites can swap between the pure-JAX reference path and
the TPU kernels with one flag.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .cubic_solve import cubic_solve_stacked
from .flash_attention import flash_attention
from .rmsnorm import rmsnorm
from .robust_agg import (
    AGG_BLOCK,
    DENSE_FUSED_MAX_M,
    SPARSE_SCATTER_MAX_D,
    agg_kernel_plan,
    aggregate_sparse,
    aggregate_sparse_gridded,
    aggregate_sparse_scatter,
    coordinate_median_fused,
    krum_scores_fused,
    krum_select_fused,
    sort_workers_fused,
    trimmed_mean_fused,
)
from .topk_compress import (
    DEFAULT_BLOCK,
    SINGLE_TILE_MAX_D,
    kernel_plan,
    topk_compress,
    topk_compress_sharded,
    topk_compress_tiled,
    topk_decompress,
)


def attention_bshd(q, k, v, *, causal=True, window=0, **kw):
    """(B, S, H, Dh) layout (the model zoo's) → flash kernel layout and back.
    GQA: kv heads repeated up to q heads before the kernel."""
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    out = flash_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
        **kw,
    )
    return out.transpose(0, 2, 1, 3)


def rmsnorm_nd(x, w, **kw):
    """RMSNorm over the last axis of an arbitrarily-batched tensor."""
    shape = x.shape
    out = rmsnorm(x.reshape(-1, shape[-1]), w, **kw)
    return out.reshape(shape)


__all__ = [
    "AGG_BLOCK",
    "DEFAULT_BLOCK",
    "DENSE_FUSED_MAX_M",
    "SINGLE_TILE_MAX_D",
    "SPARSE_SCATTER_MAX_D",
    "agg_kernel_plan",
    "aggregate_sparse",
    "aggregate_sparse_gridded",
    "aggregate_sparse_scatter",
    "attention_bshd",
    "coordinate_median_fused",
    "cubic_solve_stacked",
    "flash_attention",
    "kernel_plan",
    "krum_scores_fused",
    "krum_select_fused",
    "rmsnorm",
    "rmsnorm_nd",
    "sort_workers_fused",
    "topk_compress",
    "topk_compress_sharded",
    "topk_compress_tiled",
    "topk_decompress",
    "trimmed_mean_fused",
]
