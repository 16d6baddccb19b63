"""Pallas TPU kernels: fused robust aggregation — keep the center compressed.

The center's aggregation rules (``repro.core.aggregation``) consume m
dense (d,) worker vectors.  With top-k uplinks the wire carries only
O(m·k) entries, yet the XLA center path scatters every payload to dense
before aggregating — O(m·d) memory traffic exactly where m·d is largest.
The kernels here close that gap from both ends, mirroring the PR-4
two-pass top-k kernel's sharded structure:

* **sparse-domain aggregation** (:func:`aggregate_sparse`) — a segmented
  scatter-add/merge over the raw (indices, values) wire payloads.  The m
  dense vectors are never materialized: center memory is the O(m·k)
  payload stream plus the single (d,) aggregate.
* **fused distance kernels** — krum's O(m²) pairwise squared distances
  with the score reduction on-chip (:func:`krum_scores_fused`), and the
  per-coordinate sort behind trimmed-mean / coordinate-median as a tiled
  (m, block) bitonic network (:func:`sort_workers_fused`).  Both are
  pinned against the registry implementations the way ``topk_compress``
  is pinned against ``lax.top_k``.

:func:`agg_kernel_plan` is the ``kernel_plan``-style dispatcher the
``repro.api.aggregators`` kernel variants select through.

Sparse segmented merge
----------------------
The contract (oracle: :func:`repro.kernels.ref.sparse_aggregate_ref`):
entries merge in **index-sorted, worker-stable order** — for one output
coordinate, contributions combine lowest-index-first with duplicates in
worker order.  The launch:

1. *stream prep (host-visible jnp, O(m·k log m·k))*: per-worker weights
   fold into the values; the raveled (N = m·k) stream is stably sorted
   by coordinate; duplicate coordinates — adjacent after the sort — are
   combined into their first occurrence (cumsum differences) and the
   leftovers re-keyed to the sentinel coordinate d_pad and sorted to the
   tail.  After this pass every output coordinate owns **at most one**
   stream entry, so a ``block``-wide output block owns at most ``block``
   entries — the static occupancy bound the kernel's window relies on.
2. *gridded merge*: a 1-D grid over output blocks.  Block j's entries
   are the contiguous run [S[j], S[j+1]) of the sorted stream
   (S = searchsorted of the block edges).  Data-dependent offsets meet
   static BlockSpecs via the **two-view window trick**: the stream is
   passed twice with (1, W) blocks at scalar-prefetched chunk indices
   q = S[j]//W and q+1, so the two consecutive views together always
   cover [S[j], S[j]+W] ⊇ the run (W ≥ block ≥ occupancy).  The kernel
   walks each view in 256-lane chunks sliced from its ref; in-window
   entries outside the run are masked by position, and the masked
   one-hot (chunk, block) matmul scatters values to their in-block
   columns.
   Because of step 1's dedup, the matmul is an exact permutation — no
   float summation happens inside the kernel.

:data:`SPARSE_SCATTER_MAX_D` gates the launch: below it a single jnp
``.at[].add`` over the payload stream is already payload-shaped (it too
never builds an (m, d) array), so the kernel only takes over where the
grid pays for itself.

Fused distance kernels
----------------------
* **krum** — grid over coordinate blocks; each step accumulates the
  (P, P) pairwise-squared-distance tile (P = m padded to a power of
  two) one column per worker — every row of its (P, block) tile
  against that worker's row — revisiting the
  output block (``@pl.when(j == 0)`` init).  The last grid step finishes
  on-chip: the diagonal takes the registry's +1e30, padding rows/columns
  are masked to +1e30, every column is sorted ascending by a bitonic
  network over sublanes, and the k-nearest partial sums land in a (1, P)
  score row.  Only the m scores leave the kernel; ``argmin`` on the host
  matches :func:`repro.core.aggregation.krum_select`.
* **row sort** — trimmed-mean and coordinate-median reduce to one
  per-coordinate ascending sort over workers; the kernel runs the same
  bitonic network on (P, block) tiles (+inf row padding sinks below
  every real value).  Sorting only permutes values, so the kernel output
  equals ``jnp.sort(updates, axis=0)`` bit-for-bit and the registry's
  own slice/mean epilogue runs unchanged on top.

The bitonic network sorts the sublane axis in p(p+1)/2 vectorized
compare-exchange steps (p = log₂P): partners via ``jnp.roll(±s)``, the
keep-min side chosen by ``has_bit ^ ascending`` per merge stage.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

# sparse merge: output-block width (multiple of 128 lanes); the largest
# tile is the (256, block) one-hot scatter matmul operand
AGG_BLOCK = 1024
# below this d the jnp scatter-add fallback (also payload-shaped — it
# never builds (m, d)) beats the grid-launch overhead
SPARSE_SCATTER_MAX_D = 4096
# dense fused rules hold a (P, P) distance/score tile on-chip, P = m
# rounded up to a power of two — past this m the registry path serves
DENSE_FUSED_MAX_M = 256
# dense fused rules: coordinate-block width per grid step
DENSE_BLOCK = 512
# diagonal / padding mask, matching krum_select's jnp.eye(m) * 1e30
_BIG = 1e30


def _round_up(n, mult):
    return -(-n // mult) * mult


def _pow2_at_least(n, floor=8):
    p = floor
    while p < n:
        p *= 2
    return p


def agg_kernel_plan(m: int, d: int, *, k=None, block=None):
    """Launch plan for aggregating m workers at dimension d.

    With ``k`` (a sparse payload width): ``("scatter", block)`` — the
    payload-shaped jnp fallback — or ``("sparse_gridded", block)``.
    Without ``k`` (dense fused rules): ``("fused", P)`` with the padded
    worker-tile height, or ``("dense", None)`` when m exceeds the
    on-chip (P, P) budget and the registry path serves.  Raises
    ``ValueError`` for a block the TPU tiling cannot serve — the
    build-time sanity check of the ``*_kernel`` aggregator specs."""
    if k is not None:
        blk = AGG_BLOCK if block is None else block
        if blk % 128 != 0 or blk <= 0:
            raise ValueError(
                f"sparse aggregation block size must be a positive multiple "
                f"of 128 lanes, got {blk}"
            )
        # VMEM peak: 512 rows of (·, block) 4-byte tiles — the (256,
        # block) one-hot scatter operand and its column iota
        if 4 * 512 * blk > 14 * 2**20:
            raise ValueError(
                f"sparse aggregation block={blk} needs "
                f"~{(4 * 512 * blk) >> 20} MB VMEM tiles (> the ~14 MB "
                f"budget) — use block ≤ 4096"
            )
        if d <= SPARSE_SCATTER_MAX_D:
            return ("scatter", blk)
        return ("sparse_gridded", blk)
    blk = DENSE_BLOCK if block is None else block
    if blk % 128 != 0 or blk <= 0:
        raise ValueError(
            f"fused aggregation block size must be a positive multiple of "
            f"128 lanes, got {blk}"
        )
    if m > DENSE_FUSED_MAX_M:
        return ("dense", None)
    return ("fused", _pow2_at_least(m))


# ---------------------------------------------------------------------------
# sparse-domain aggregation: segmented scatter-add over wire payloads
# ---------------------------------------------------------------------------


def _sorted_stream(vals, idx, d_pad, weights):
    """Payloads → the deduplicated index-sorted stream (module docstring,
    step 1).  Returns (values (N,), coordinates (N,) int32) with at most
    one entry per coordinate; evicted duplicates carry the sentinel
    coordinate ``d_pad`` and value 0 at the stream tail."""
    v = vals.astype(jnp.float32)
    if weights is not None:
        v = v * weights.astype(jnp.float32)[:, None]
    vs = v.reshape(-1)
    ix = idx.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(ix, stable=True)          # worker-stable within ties
    vs, ix = vs[order], ix[order]
    n = vs.shape[0]
    first = jnp.searchsorted(ix, ix, side="left")
    last = jnp.searchsorted(ix, ix, side="right") - 1
    csum = jnp.cumsum(vs)
    run_sum = csum[last] - csum[first] + vs[first]
    is_first = jnp.arange(n) == first
    vs = jnp.where(is_first, run_sum, 0.0)
    ix = jnp.where(is_first, ix, d_pad)
    order = jnp.argsort(ix, stable=True)          # sentinels sink to the tail
    return vs[order], ix[order]


def _sparse_agg_kernel(qw_ref, s_ref, e_ref, v0_ref, v1_ref, i0_ref, i1_ref,
                       out_ref, *, window, block, chunk):
    j = pl.program_id(0)
    base = j * block
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, block), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def merge(v_ref, i_ref, start):
        """Scatter the live entries of one (1, window) view, whose first
        lane sits at stream position ``start``, chunk by chunk."""

        def body(c, acc):
            off = pl.multiple_of(c * chunk, chunk)
            vc = v_ref[:, pl.ds(off, chunk)]
            ic = i_ref[:, pl.ds(off, chunk)]
            pos = start + off + lane
            lc = ((pos >= s_ref[j]) & (pos < e_ref[j])).astype(jnp.float32)
            # the dedup pass guarantees ≤ 1 live entry per column: the
            # matmul is an exact permutation-scatter, never a float
            # reduction
            onehot = ((ic.reshape(chunk, 1) - base) == cols).astype(
                jnp.float32) * lc.reshape(chunk, 1)
            return acc + jax.lax.dot_general(
                vc, onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )

        return body

    # the two views are consecutive windows of the stream: walk each
    # ref in turn (Mosaic slices refs, not loaded values)
    start = qw_ref[j] * window
    acc = jax.lax.fori_loop(0, window // chunk, merge(v0_ref, i0_ref, start),
                            jnp.zeros((1, block), jnp.float32))
    out_ref[...] = jax.lax.fori_loop(
        0, window // chunk, merge(v1_ref, i1_ref, start + window), acc)


@functools.partial(jax.jit, static_argnames=("d", "block", "interpret"))
def aggregate_sparse_gridded(vals, idx, d, weights=None, *, block=AGG_BLOCK,
                             interpret=None):
    """Gridded segmented-merge launch: (m, k) payloads → the (d,) f32
    weighted scatter-add aggregate, O(m·k + d) memory, any d."""
    interpret = resolve_interpret(interpret)
    m, k = vals.shape
    assert idx.shape == (m, k)
    nb = _round_up(d, block) // block
    d_pad = nb * block
    vs, ix = _sorted_stream(vals, idx, d_pad, weights)
    n = vs.shape[0]
    # window ≥ the per-block occupancy bound min(N, block); 256-multiples
    # keep each view divisible by the 256-wide scatter chunks
    window = _round_up(min(max(n, 1), block), 256)
    npad = (_round_up(n, window) // window + 2) * window
    vp = jnp.pad(vs, (0, npad - n)).reshape(1, npad)
    ip = jnp.pad(ix, (0, npad - n), constant_values=d_pad).reshape(1, npad)
    # S[j] = first stream position with coordinate ≥ j·block; sentinels
    # (evicted duplicates, padding) sort past S[nb] and never merge
    S = jnp.searchsorted(
        ix, jnp.arange(nb + 1, dtype=jnp.int32) * block).astype(jnp.int32)
    chunk = 256                      # divides window (a 256-multiple)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, window), lambda j, q, s, e: (0, q[j])),
            pl.BlockSpec((1, window), lambda j, q, s, e: (0, q[j] + 1)),
            pl.BlockSpec((1, window), lambda j, q, s, e: (0, q[j])),
            pl.BlockSpec((1, window), lambda j, q, s, e: (0, q[j] + 1)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda j, q, s, e: (0, j)),
    )
    out = pl.pallas_call(
        functools.partial(_sparse_agg_kernel, window=window, block=block,
                          chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
        interpret=interpret,
    )(S[:nb] // window, S[:nb], S[1:], vp, vp, ip, ip)
    return out[0, :d]


def aggregate_sparse_scatter(vals, idx, d, weights=None):
    """Payload-shaped jnp fallback: one scatter-add over the raveled
    stream.  Also never materializes an (m, d) array."""
    v = vals.astype(jnp.float32)
    if weights is not None:
        v = v * weights.astype(jnp.float32)[:, None]
    return jnp.zeros((d,), jnp.float32).at[idx.reshape(-1)].add(v.reshape(-1))


def aggregate_sparse(vals, idx, d, weights=None, *, block=None,
                     interpret=None):
    """Weighted sum of m sparse payloads, Σᵢ wᵢ · scatter(valsᵢ, idxᵢ),
    without densifying any per-worker vector: values (m, k), indices
    (m, k) int32, optional weights (m,) → the (d,) f32 aggregate.

    Auto-selects the launch by d (:func:`agg_kernel_plan`): the jnp
    scatter-add up to :data:`SPARSE_SCATTER_MAX_D`, the gridded
    segmented-merge kernel beyond it.  Both agree with
    :func:`repro.kernels.ref.sparse_aggregate_ref`."""
    plan, blk = agg_kernel_plan(vals.shape[0], d, k=vals.shape[1],
                                block=block)
    if plan == "scatter":
        return aggregate_sparse_scatter(vals, idx, d, weights)
    return aggregate_sparse_gridded(vals, idx, d, weights, block=blk,
                                    interpret=interpret)


# ---------------------------------------------------------------------------
# fused distance kernels: krum pairwise distances, per-coordinate row sort
# ---------------------------------------------------------------------------


def _bitonic_sort_cols(x):
    """Sort every column of a (P, B) tile ascending along the sublane
    axis (P a power of two) — the vectorized bitonic network."""
    P = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    stages = P.bit_length() - 1
    for stage in range(stages):
        for sub in range(stage, -1, -1):
            s = 1 << sub
            has_bit = (row & s) != 0
            partner = jnp.where(has_bit, jnp.roll(x, s, axis=0),
                                jnp.roll(x, -s, axis=0))
            asc = (row & (1 << (stage + 1))) == 0
            keep_min = has_bit ^ asc
            x = jnp.where(keep_min, jnp.minimum(x, partner),
                          jnp.maximum(x, partner))
    return x


def _krum_kernel(x_ref, d2_ref, score_ref, *, m, k_near, n_blocks):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        d2_ref[...] = jnp.zeros_like(d2_ref)
        score_ref[...] = jnp.zeros_like(score_ref)

    x = x_ref[...].astype(jnp.float32)            # (P, block)
    P = x.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)

    def body(c, acc):
        # column c of the distance tile: every worker against worker c
        diff = x - x_ref[pl.ds(c, 1), :].astype(jnp.float32)   # (P, block)
        col = jnp.sum(diff * diff, axis=1, keepdims=True)      # (P, 1)
        return jnp.where(cols == c, col, acc)

    d2_ref[...] += jax.lax.fori_loop(
        0, P, body, jnp.zeros((P, P), jnp.float32))

    @pl.when(j == n_blocks - 1)
    def _score():
        # on-chip score stage: registry diagonal, padding masked to the
        # same +1e30, columns sorted ascending, k-nearest partial sums.
        # By symmetry d2[i, j] == d2[j, i] exactly, so column sums equal
        # krum_select's row-wise nearest.sum(1).
        d2 = d2_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
        d2 = d2 + jnp.where(rows == cols, _BIG, 0.0)
        d2 = jnp.where((rows >= m) | (cols >= m), _BIG, d2)
        srt = _bitonic_sort_cols(d2)
        score_ref[...] = jnp.sum(srt[:k_near, :], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n_byz", "block", "interpret"))
def krum_scores_fused(flat, n_byz, *, block=DENSE_BLOCK, interpret=None):
    """Krum scores for an (m, d) stack: blocked O(m²) pairwise squared
    distances with the score reduction on-chip — only the (m,) scores
    leave the kernel.  k-nearest count matches ``krum_select``:
    k = max(m − n_byz − 2, 1)."""
    interpret = resolve_interpret(interpret)
    m, d = flat.shape
    plan, P = agg_kernel_plan(m, d, block=block)
    if plan != "fused":
        raise ValueError(
            f"fused krum serves m ≤ {DENSE_FUSED_MAX_M} (a (P, P) VMEM "
            f"score tile), got m={m} — use the registry path"
        )
    nd = _round_up(d, block) // block
    xp = jnp.pad(flat.astype(jnp.float32), ((0, P - m), (0, nd * block - d)))
    _, scores = pl.pallas_call(
        functools.partial(_krum_kernel, m=m,
                          k_near=max(m - int(n_byz) - 2, 1),
                          n_blocks=nd),
        grid=(nd,),
        in_specs=[pl.BlockSpec((P, block), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((P, P), lambda j: (0, 0)),
            pl.BlockSpec((1, P), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, P), jnp.float32),
            jax.ShapeDtypeStruct((1, P), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    return scores[0, :m]


def krum_select_fused(flat, n_byz, *, block=DENSE_BLOCK, interpret=None):
    """Fused-kernel drop-in for :func:`repro.core.aggregation.krum_select`:
    the index of the worker with the smallest k-nearest distance sum."""
    return jnp.argmin(krum_scores_fused(flat, n_byz, block=block,
                                        interpret=interpret))


def _rowsort_kernel(x_ref, out_ref):
    out_ref[...] = _bitonic_sort_cols(x_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sort_workers_fused(updates, *, block=DENSE_BLOCK, interpret=None):
    """Per-coordinate ascending sort over the worker axis of an (m, d)
    stack, tiled (P, block) per grid step (+inf row padding sinks below
    every real value).  Sorting only permutes, so this equals
    ``jnp.sort(updates, axis=0)`` bit-for-bit — the registry's
    trimmed-mean/median epilogues run unchanged on the output."""
    interpret = resolve_interpret(interpret)
    m, d = updates.shape
    plan, P = agg_kernel_plan(m, d, block=block)
    if plan != "fused":
        raise ValueError(
            f"fused row sort serves m ≤ {DENSE_FUSED_MAX_M}, got m={m} — "
            f"use the registry path"
        )
    nd = _round_up(d, block) // block
    xp = jnp.pad(updates.astype(jnp.float32),
                 ((0, P - m), (0, nd * block - d)),
                 constant_values=jnp.inf)
    srt = pl.pallas_call(
        _rowsort_kernel,
        grid=(nd,),
        in_specs=[pl.BlockSpec((P, block), lambda j: (0, j))],
        out_specs=pl.BlockSpec((P, block), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((P, nd * block), jnp.float32),
        interpret=interpret,
    )(xp)
    return srt[:m, :d]


def trimmed_mean_fused(updates, trim_frac: float, *, block=DENSE_BLOCK,
                       interpret=None):
    """Fused-kernel drop-in for :func:`repro.core.aggregation.trimmed_mean`
    (identical k clamp and slice/mean epilogue on the kernel-sorted
    stack)."""
    m = updates.shape[0]
    srt = sort_workers_fused(updates, block=block, interpret=interpret)
    kt = min(int(round(trim_frac * m)), (m - 1) // 2)
    kept = srt if kt == 0 else srt[kt:m - kt]
    return kept.mean(0)


def coordinate_median_fused(updates, *, block=DENSE_BLOCK, interpret=None):
    """Fused-kernel drop-in for
    :func:`repro.core.aggregation.coordinate_median` — the middle row(s)
    of the kernel-sorted stack, combined with ``jnp.median``'s midpoint
    mean (low + high) · 0.5 on even m."""
    m = updates.shape[0]
    srt = sort_workers_fused(updates, block=block, interpret=interpret)
    if m % 2:
        return srt[m // 2]
    return (srt[m // 2 - 1] + srt[m // 2]) * 0.5
