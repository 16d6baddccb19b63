"""Pallas TPU kernels: fused top-k compression (threshold-select + pack).

The XLA path for top-k compression is three kernels with HBM round-trips
between them: ``top_k`` (a full sort on TPU), a gather, and a scatter at
the receiver.  The kernels here produce the packed wire payload — k
values and k int32 indices in index-ascending order — without ever
sorting.  Two launches cover every scale:

* **single-tile** (d ≤ :data:`SINGLE_TILE_MAX_D`): the whole vector is
  VMEM-resident and one launch does threshold-select (bisection on the
  magnitude range) + pack (see below) — the paper's d ≤ a-few-k regime.
* **sharded** (any d): a grid over coordinate blocks with a two-pass
  global threshold — the model-scale path (see "Sharded launch" below).

:func:`topk_compress` picks the launch by d; both are validated in
interpret mode against :func:`repro.kernels.ref.topk_compress_ref` and
agree with ``jax.lax.top_k`` bit-for-bit, including its tie rule
(ties at the threshold magnitude keep the lowest indices).

Single-tile launch
------------------
1. *threshold-select*: bisection on the magnitude range finds the
   largest t with |{i : |x_i| ≥ t}| ≥ k (a fori_loop of d-wide
   reductions; after ~64 halvings the interval is below fp32 spacing, so
   the count is exact for distinct magnitudes);
2. *pack*: selected coordinates are compacted MXU-style — the rank of
   each selected coordinate is a strict-lower-triangular matvec (no
   cumsum primitive needed), and a (d, k) one-hot of those ranks gathers
   values and indices with two matmuls.  Coordinates strictly above the
   threshold band are always kept; ties at the threshold fill the
   remaining slots lowest-index-first (``lax.top_k``'s rule).

VMEM holds two (d_pad, d_pad) iota-comparison tiles, so d_pad² · 4 B
must fit in ~16 MB ⇒ d ≲ 1.4k.

Sharded launch
--------------
The two-pass global-threshold contract for model-scale vectors:

* **pass 1 — per-block radix histograms.** The vector is split into
  ``block``-wide coordinate blocks (a 1-D grid).  The fp32 bit pattern
  of |x_i| is order-isomorphic to the magnitude (non-negative floats
  compare like their int32 patterns; padding lanes are forced to the
  sentinel −1 so they never count), so a radix-select over the 31
  magnitude bits finds the EXACT bit pattern p of the k-th largest |x|:
  each round a gridded kernel histograms the next ``nbits`` of every
  in-prefix coordinate's pattern, and a host-visible reduction (plain
  jnp on the (n_blocks, n_buckets) counts) walks the global histogram
  from the top to pick the bucket holding the k-th magnitude.  Three
  rounds (10 + 10 + 11 bits, :data:`_RADIX_ROUNDS`) resolve all 31
  bits, so the threshold t = bitcast(p) is exact — no approximation,
  ties are whole-magnitude classes, and parity with ``lax.top_k`` is
  bit-exact.
* **threshold → per-block budgets (host-visible reduction).**  With p
  fixed, coordinates split into *sure* (pattern > p, all kept — fewer
  than k by construction) and *ties* (pattern == p, filling the
  remaining k − n_sure slots lowest-index-first, ``lax.top_k``'s rule).
  Per-block tie budgets and pack offsets are exclusive prefix sums of
  the per-block sure/tie counts — block order IS global index order, so
  lowest-index-first across blocks falls out of the cumsum.
* **pass 2 — per-block pack.**  Each grid step packs its block's
  survivors (sure + first-``budget`` ties) into its slice of the
  blocked wire payload using the same strict-lower-triangular-matvec
  rank trick as the single-tile kernel, now on (block, block) tiles;
  indices are rebased to global int32 coordinates.  A final fixed-shape
  scatter compacts the blocked slices at their pack offsets into the
  (k,) wire arrays — identical payload, identical wire bits: the
  blocked layout transmits exactly k values + k indices, so
  ``TopK.wire_bits`` (and the :class:`repro.comm.WireLedger` totals)
  are unchanged relative to the single-tile/XLA paths.

Per-launch VMEM is O(block²) regardless of d, so the default
``block=512`` keeps every tile comfortably inside 16 MB at any model
scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret

# single-tile launch: two (d_pad, d_pad) f32 comparison tiles must sit in
# ~16 MB VMEM next to the pack buffers ⇒ d ≲ 1.4k; beyond it
# topk_compress routes to the sharded grid automatically
SINGLE_TILE_MAX_D = 1408
# sharded launch: coordinate-block width (multiple of 128 lanes); the
# largest pass-1/2 tiles are (block, 2048) and (block, block) f32
DEFAULT_BLOCK = 512
# radix-select rounds over the 31 bits of the |x| fp32 pattern:
# (shift, nbits) — 10 + 10 + 11 bits resolve the threshold exactly
_RADIX_ROUNDS = ((21, 10), (11, 10), (0, 11))


def _round_up(n, mult):
    return -(-n // mult) * mult


def kernel_plan(d: int, block: int = DEFAULT_BLOCK):
    """Launch plan for a d-vector: ``("single_tile", d_pad)`` or
    ``("gridded", block)``.  Raises ``ValueError`` for a block size the
    TPU tiling cannot serve — the facade's build-time sanity check."""
    if block % 128 != 0 or block <= 0:
        raise ValueError(
            f"top-k kernel block size must be a positive multiple of 128 "
            f"lanes, got {block}"
        )
    # sharded-launch VMEM peaks: the (block, 2048) pass-1 histogram
    # one-hot vs the three (block, block) pass-2 rank/select tiles (f32)
    tile_bytes = 4 * max(block * 2048, 3 * block * block)
    if tile_bytes > 14 * 2**20:
        raise ValueError(
            f"top-k kernel block={block} needs ~{tile_bytes >> 20} MB of "
            f"VMEM tiles (> the ~14 MB budget) — use block ≤ 1024"
        )
    if d <= SINGLE_TILE_MAX_D:
        return ("single_tile", _round_up(max(d, 1), 128))
    return ("gridded", block)


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k, d, n_iter):
    x = x_ref[...].astype(jnp.float32)                      # (1, dp)
    dp = x.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, dp), 1)
    valid = pos < d
    ax = jnp.where(valid, jnp.abs(x), -1.0)                 # padding never selects

    # -- threshold-select: largest t ≥ 0 with count(|x| ≥ t) ≥ k --------
    def bisect(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((ax >= mid).astype(jnp.float32))
        take = cnt >= k
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    lo, hi = jax.lax.fori_loop(
        0, n_iter, bisect, (jnp.float32(0.0), jnp.max(ax))
    )
    # `sure` (|x| ≥ hi) are strictly inside the top-k band; `tie` sits at
    # the threshold and only fills the remaining slots, lowest index first
    # (lax.top_k's rule).  Keeping first-k of the raw ≥lo mask instead
    # would drop large-magnitude coordinates at high indices on ties.
    sure = ((ax >= hi) & valid).astype(jnp.float32)         # (1, dp)
    tie = ((ax >= lo) & valid).astype(jnp.float32) - sure

    # -- pack: ranks via strict-lower-triangular matvecs, gather via one-hot
    ii = jax.lax.broadcasted_iota(jnp.int32, (dp, dp), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (dp, dp), 1)
    lt = (ii < jj).astype(jnp.float32)

    def rank_of(sel):                                       # # selected before j
        return jax.lax.dot_general(
            sel, lt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    n_sure = jnp.sum(sure)
    keep = sure * (rank_of(sure) < k).astype(jnp.float32) + tie * (
        rank_of(tie) < k - n_sure
    ).astype(jnp.float32)
    rank = rank_of(keep)

    kp = vals_ref.shape[1]
    # Mosaic's iota is integer-only: build int32 and convert
    slot = jax.lax.broadcasted_iota(jnp.int32, (dp, kp), 1).astype(jnp.float32)
    sel = (rank.reshape(dp, 1) == slot).astype(jnp.float32) * keep.reshape(dp, 1)

    def gather(row):                                        # (1, dp) @ (dp, kp)
        return jax.lax.dot_general(
            row, sel, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    vals_ref[...] = gather(x).astype(vals_ref.dtype)
    idx_ref[...] = jnp.round(gather(pos.astype(jnp.float32))).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "n_iter", "interpret"))
def topk_compress_tiled(x, k, *, n_iter=64, interpret=None):
    """Single-tile launch (d ≤ :data:`SINGLE_TILE_MAX_D`): one VMEM-resident
    threshold-select + pack pass over the whole vector."""
    interpret = resolve_interpret(interpret)
    d = x.shape[-1]
    assert x.ndim == 1 and 1 <= k <= d
    dp, kp = _round_up(d, 128), _round_up(k, 128)
    xp = jnp.pad(x, (0, dp - d)).reshape(1, dp)
    kernel = functools.partial(_topk_kernel, k=k, d=d, n_iter=n_iter)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[pl.BlockSpec((1, dp), lambda: (0, 0))],
        out_specs=[
            pl.BlockSpec((1, kp), lambda: (0, 0)),
            pl.BlockSpec((1, kp), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.int32),
        ],
        interpret=interpret,
    )(xp)
    return vals[0, :k].astype(x.dtype), idx[0, :k]


# ---------------------------------------------------------------------------
# sharded launch: grid over coordinate blocks, two-pass global threshold
# ---------------------------------------------------------------------------


def _hist_kernel(patt_ref, prefix_ref, hist_ref, *, shift, nbits):
    """Pass 1, one radix round: per-block bucket counts of the next
    ``nbits`` of each in-prefix |x| bit pattern (padding = −1 never
    matches any prefix: −1 >> s == −1 ≠ prefix ≥ 0)."""
    patt = patt_ref[...]                                    # (1, B) int32
    nbuckets = 1 << nbits
    match = (patt >> (shift + nbits)) == prefix_ref[0, 0]
    # out-of-prefix coordinates take bucket −1, which no column matches
    # (folding the mask in keeps the (1, B) → (B, 1) relayout on int32:
    # Mosaic cannot reshape a boolean vector)
    bucket = jnp.where(match, (patt >> shift) & (nbuckets - 1), -1)
    B = patt.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (B, nbuckets), 1)
    onehot = bucket.reshape(B, 1) == cols
    hist_ref[...] = jnp.sum(onehot.astype(jnp.int32), axis=0, keepdims=True)


def _pack_kernel(x_ref, patt_ref, thresh_ref, budget_ref, vals_ref, idx_ref,
                 *, block):
    """Pass 2: pack this block's survivors — all sure coordinates
    (pattern > p) plus the first ``budget`` ties (pattern == p),
    lowest-index-first — into its slice of the blocked wire payload,
    via the strict-lower-triangular-matvec rank trick per tile."""
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)                      # (1, B)
    patt = patt_ref[...]
    p = thresh_ref[0, 0]
    budget = budget_ref[0, 0].astype(jnp.float32)
    B = block
    sure = (patt > p).astype(jnp.float32)                   # (1, B)
    tie = (patt == p).astype(jnp.float32)

    ii = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    lt = (ii < jj).astype(jnp.float32)

    def rank_of(sel):                                       # # selected before j
        return jax.lax.dot_general(
            sel, lt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    keep = sure + tie * (rank_of(tie) < budget).astype(jnp.float32)
    rank = rank_of(keep)
    W = vals_ref.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1).astype(jnp.float32)
    sel = (rank.reshape(B, 1) == slot).astype(jnp.float32) * keep.reshape(B, 1)

    def gather(row):                                        # (1, B) @ (B, W)
        return jax.lax.dot_general(
            row, sel, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    # local positions stay < B ≤ 2^24 (exact in f32); rebasing to global
    # int32 AFTER the matmul keeps the kernel exact at any d.  The
    # Precision.HIGHEST on the dots keeps the MXU from truncating the f32
    # operands to bf16 on real TPUs (positions > 256 and arbitrary values
    # must survive the matmul bit-exactly)
    lpos = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1).astype(jnp.float32)
    vals_ref[...] = gather(x)
    idx_ref[...] = jnp.round(gather(lpos)).astype(jnp.int32) + i * B


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def topk_compress_sharded(x, k, *, block=DEFAULT_BLOCK, interpret=None):
    """Sharded launch: grid over ``block``-wide coordinate blocks with the
    two-pass global threshold (module docstring, "Sharded launch") —
    model-scale vectors, O(block²) VMEM per grid step, any d."""
    interpret = resolve_interpret(interpret)
    d = x.shape[-1]
    assert x.ndim == 1 and 1 <= k <= d
    kernel_plan(d, block)                                   # block sanity
    nb = _round_up(d, block) // block
    # per-block rows are lifted to (nb, 1, ·) and the grid axis squeezed
    # out of every block, so each block's last two dims equal the array's
    # — the TPU's (8, 128) block rule then holds for any nb
    xp = jnp.pad(x.astype(jnp.float32), (0, nb * block - d)).reshape(
        nb, 1, block)
    # |x| fp32 bit patterns compare like magnitudes (non-negative floats);
    # padding lanes get the sentinel −1 so no kernel needs a valid mask
    patt = jax.lax.bitcast_convert_type(jnp.abs(xp), jnp.int32)
    gpos = jnp.arange(nb * block, dtype=jnp.int32).reshape(nb, 1, block)
    patt = jnp.where(gpos < d, patt, -1)
    row = pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0))

    # -- pass 1: radix-select the exact bit pattern p of the k-th |x| ----
    prefix = jnp.zeros((1, 1), jnp.int32)
    n_above = jnp.int32(0)                  # count strictly above the prefix
    for shift, nbits in _RADIX_ROUNDS:
        nbuckets = 1 << nbits
        hist = pl.pallas_call(
            functools.partial(_hist_kernel, shift=shift, nbits=nbits),
            grid=(nb,),
            in_specs=[row, pl.BlockSpec((1, 1), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((None, 1, nbuckets), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((nb, 1, nbuckets), jnp.int32),
            interpret=interpret,
        )(patt, prefix)
        # host-visible reduction: walk the global histogram from the top
        g = jnp.sum(hist, axis=(0, 1))
        S = jnp.cumsum(g[::-1])[::-1]                # S[q] = count(≥ bucket q)
        in_band = (n_above + S) >= k
        q = jnp.max(jnp.where(in_band,
                              jnp.arange(nbuckets, dtype=jnp.int32), -1))
        n_above = n_above + S[q] - g[q]
        prefix = (prefix << nbits) | q
    p = prefix                                       # (1, 1): exact pattern

    # -- threshold → per-block tie budgets and pack offsets --------------
    sure_b = jnp.sum(patt > p[0, 0], axis=(1, 2))
    tie_b = jnp.sum(patt == p[0, 0], axis=(1, 2))
    n_sure = jnp.sum(sure_b)
    tie_before = jnp.cumsum(tie_b) - tie_b           # block order = index order
    budget_b = jnp.clip(k - n_sure - tie_before, 0, tie_b).astype(jnp.int32)
    count_b = (sure_b + budget_b).astype(jnp.int32)
    base_b = (jnp.cumsum(count_b) - count_b).astype(jnp.int32)

    # -- pass 2: pack each block's survivors into the blocked payload ----
    W = min(block, _round_up(k, 128))                # per-block slice width
    vals, idx = pl.pallas_call(
        functools.partial(_pack_kernel, block=block),
        grid=(nb,),
        in_specs=[
            row,
            row,
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, W), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, W), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, W), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, W), jnp.int32),
        ],
        interpret=interpret,
    )(xp, patt, p, budget_b.reshape(nb, 1, 1))

    # compact the blocked slices at their offsets into the (k,) wire
    # arrays (Σ count_b == k exactly, so every slot is written once)
    wpos = jnp.arange(W, dtype=jnp.int32)[None, :]
    tgt = jnp.where(wpos < count_b[:, None], base_b[:, None] + wpos, k)
    vals_out = jnp.zeros((k,), jnp.float32).at[tgt.ravel()].set(
        vals.ravel(), mode="drop")
    idx_out = jnp.zeros((k,), jnp.int32).at[tgt.ravel()].set(
        idx.ravel(), mode="drop")
    return vals_out.astype(x.dtype), idx_out


def topk_compress(x, k, *, n_iter=64, interpret=None, block=DEFAULT_BLOCK):
    """Packed top-|x| payload of a 1-D vector: (values (k,), indices (k,)),
    index-ascending — the wire format of :class:`repro.compression.TopK`.

    Auto-selects the launch by d (:func:`kernel_plan`): the single-tile
    kernel up to :data:`SINGLE_TILE_MAX_D`, the sharded grid beyond it.
    Both agree with ``jax.lax.top_k`` bit-for-bit."""
    plan, _ = kernel_plan(x.shape[-1], block)
    if plan == "single_tile":
        return topk_compress_tiled(x, k, n_iter=n_iter, interpret=interpret)
    return topk_compress_sharded(x, k, block=block, interpret=interpret)


def topk_decompress(vals, idx, d):
    """Center-side reconstruction: scatter the packed payload to dense."""
    return jnp.zeros((d,), vals.dtype).at[idx].set(vals)
