"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §2):

* :mod:`flash_attention` — online-softmax attention, causal + sliding window
  (the prefill/train hot loop of every attention arch).
* :mod:`cubic_step` — fused Algorithm-2 inner iteration for the paper's
  explicit-Hessian regime (the solver hot loop of the reproduction).
* :mod:`topk_compress` — fused top-k compression payload, the wire
  hot-spot of repro.compression: a single-tile launch (threshold
  bisection + MXU pack) for d ≤ 1408 and a sharded grid-over-blocks
  launch with a two-pass radix-select global threshold for model-scale
  vectors; ``topk_compress`` auto-selects by d (``kernel_plan``).
* :mod:`robust_agg` — fused robust aggregation for the center's hot
  path: sparse-domain segmented scatter-add over top-k wire payloads
  (O(m·k) center memory, never densifying), blocked O(m²) krum pairwise
  distances with on-chip score reduction, and a tiled per-coordinate
  bitonic row sort behind trimmed-mean / coordinate-median;
  ``agg_kernel_plan`` auto-selects the launch.
* :mod:`rmsnorm` — row-tiled RMSNorm.

Each has a pure-jnp oracle in :mod:`ref` and a jit wrapper in :mod:`ops`.
:func:`mode.resolve_interpret` is the one place that decides whether a
launch is interpreted: off-TPU it is, on a TPU it lowers through Mosaic.
"""
from .ops import (
    AGG_BLOCK,
    DEFAULT_BLOCK,
    DENSE_FUSED_MAX_M,
    SINGLE_TILE_MAX_D,
    SPARSE_SCATTER_MAX_D,
    agg_kernel_plan,
    aggregate_sparse,
    aggregate_sparse_gridded,
    aggregate_sparse_scatter,
    attention_bshd,
    coordinate_median_fused,
    cubic_solve_fused,
    cubic_step,
    flash_attention,
    kernel_plan,
    krum_scores_fused,
    krum_select_fused,
    rmsnorm,
    rmsnorm_nd,
    sort_workers_fused,
    topk_compress,
    topk_compress_sharded,
    topk_compress_tiled,
    topk_decompress,
    trimmed_mean_fused,
)

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
    "cubic_solve_fused",
    "cubic_step",
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
