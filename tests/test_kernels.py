"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (mandated by the
brief), executed in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
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
    flash_attention,
    kernel_plan,
    krum_scores_fused,
    krum_select_fused,
    rmsnorm,
    sort_workers_fused,
    topk_compress,
    topk_compress_sharded,
    topk_decompress,
    trimmed_mean_fused,
)
from repro.kernels.ref import (
    flash_attention_ref,
    krum_scores_ref,
    rmsnorm_ref,
    sparse_aggregate_ref,
    topk_compress_ref,
    topk_compress_sharded_ref,
)
from repro.core.aggregation import (
    coordinate_median,
    krum_select,
    trimmed_mean,
)


@pytest.mark.parametrize("B,H,S,Dh", [(1, 1, 128, 64), (2, 3, 256, 64), (1, 2, 256, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, S, Dh, causal, dtype, rng):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, H, S, Dh), dtype)
    k = jax.random.normal(kk, (B, H, S, Dh), dtype)
    v = jax.random.normal(kv, (B, H, S, Dh), dtype)
    o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    r = flash_attention_ref(q, k, v, causal=causal)
    atol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        o.astype(jnp.float32), r.astype(jnp.float32), atol=atol
    )


@pytest.mark.parametrize("window", [64, 128, 192])
def test_flash_attention_window(window, rng):
    B, H, S, Dh = 1, 2, 384, 64
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, H, S, Dh))
    k = jax.random.normal(kk, (B, H, S, Dh))
    v = jax.random.normal(kv, (B, H, S, Dh))
    o = flash_attention(q, k, v, causal=True, window=window, block_q=64, block_k=64)
    r = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(o, r, atol=2e-6)


def test_flash_attention_block_shape_invariance(rng):
    B, H, S, Dh = 1, 2, 256, 64
    q = jax.random.normal(rng, (B, H, S, Dh))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, H, S, Dh))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, H, S, Dh))
    o1 = flash_attention(q, k, v, block_q=64, block_k=64)
    o2 = flash_attention(q, k, v, block_q=128, block_k=32)
    np.testing.assert_allclose(o1, o2, atol=2e-6)


def test_attention_bshd_gqa(rng):
    """ops.py wrapper: (B,S,H,Dh) layout + GQA kv repetition."""
    B, S, H, Hkv, Dh = 2, 128, 4, 2, 64
    q = jax.random.normal(rng, (B, S, H, Dh))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, Hkv, Dh))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, Hkv, Dh))
    o = attention_bshd(q, k, v, causal=True, block_q=64, block_k=64)
    from repro.models.attention import reference_attention

    r = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(o, r, atol=2e-6)


@pytest.mark.parametrize("d", [64, 123, 300, 512])
@pytest.mark.parametrize("ratio", [0.05, 0.1, 0.5, 1.0])
def test_topk_compress_sweep(d, ratio, rng):
    """Fused threshold-select + pack vs the lax.top_k oracle: identical
    packed payload (index-ascending) on dense random vectors."""
    k = max(1, int(round(ratio * d)))
    x = jax.random.normal(jax.random.fold_in(rng, d * 1000 + k), (d,))
    v, i = topk_compress(x, k)
    vr, ir = topk_compress_ref(x, k)
    np.testing.assert_array_equal(i, ir)
    np.testing.assert_allclose(v, vr, atol=1e-6)
    np.testing.assert_allclose(
        topk_decompress(v, i, d), topk_decompress(vr, ir, d), atol=1e-6
    )


def test_topk_compress_edge_cases():
    # constant and zero vectors: ties keep the lowest indices
    for x in (jnp.zeros(130), jnp.ones(130)):
        v, i = topk_compress(x, 5)
        np.testing.assert_array_equal(i, jnp.arange(5))
        np.testing.assert_allclose(v, x[:5])


def test_topk_compress_ties_keep_large_magnitudes(rng):
    # threshold ties at low indices must not evict strictly larger values
    # at high indices (regression: first-k-by-index over the raw mask)
    x = jnp.array([2.0, 2.0, 2.0, -7.0])
    v, i = topk_compress(x, 2)
    vr, ir = topk_compress_ref(x, 2)
    np.testing.assert_array_equal(i, ir)
    np.testing.assert_allclose(v, vr)
    # sparse input, fewer nonzeros than k, nonzero at a high index
    xs = jnp.zeros(10).at[7].set(5.0)
    v, i = topk_compress(xs, 3)
    np.testing.assert_array_equal(i, topk_compress_ref(xs, 3)[1])
    # heavy-tie sweep: quantized magnitudes, random (d, k)
    for t in range(25):
        kk = jax.random.fold_in(rng, t)
        d = int(jax.random.randint(kk, (), 4, 60))
        xq = jnp.round(jax.random.normal(jax.random.fold_in(kk, 1), (d,)) * 3) / 3
        k = int(jax.random.randint(jax.random.fold_in(kk, 2), (), 1, d + 1))
        v, i = topk_compress(xq, k)
        vr, ir = topk_compress_ref(xq, k)
        np.testing.assert_array_equal(i, ir)
        np.testing.assert_allclose(v, vr, atol=1e-6)


def test_topk_compress_vmap(rng):
    xs = jax.random.normal(rng, (4, 300))
    vs, idxs = jax.jit(jax.vmap(lambda z: topk_compress(z, 30)))(xs)
    assert vs.shape == (4, 30) and idxs.shape == (4, 30)
    ref = jax.vmap(lambda z: topk_compress_ref(z, 30)[0])(xs)
    np.testing.assert_allclose(vs, ref, atol=1e-6)


# ------------------- sharded (gridded) top-k kernel -----------------------

_B = DEFAULT_BLOCK


def _assert_payload_parity(x, k, **kw):
    """Gridded kernel == lax.top_k oracle == blocked two-pass oracle,
    bit-for-bit (selected support, packed order, values)."""
    v, i = topk_compress_sharded(x, k, **kw)
    vr, ir = topk_compress_ref(x, k)
    np.testing.assert_array_equal(i, ir)
    np.testing.assert_array_equal(v, vr)
    vb, ib = topk_compress_sharded_ref(x, k, kw.get("block", _B))
    np.testing.assert_array_equal(ib, ir)
    np.testing.assert_array_equal(vb, vr)


@pytest.mark.parametrize("d", [_B - 1, _B, _B + 1, 1408, 1409, 4096, 65536])
@pytest.mark.parametrize("kind", ["first", "tenth", "last"])
def test_topk_sharded_oracle_sweep(d, kind, rng):
    """ISSUE sweep: gridded kernel parity at the block boundaries, the
    single-tile limit and beyond, k at both extremes and in between."""
    k = {"first": 1, "tenth": max(1, d // 10), "last": d - 1}[kind]
    x = jax.random.normal(jax.random.fold_in(rng, d * 7 + k), (d,))
    _assert_payload_parity(x, k)


@pytest.mark.parametrize("d", [_B - 1, _B + 1, 3000])
def test_topk_sharded_duplicate_magnitudes(d, rng):
    """Quantized magnitudes force many threshold ties; the tie class must
    fill lowest-index-first ACROSS blocks (lax.top_k's rule)."""
    x = jnp.round(jax.random.normal(jax.random.fold_in(rng, d), (d,)) * 2) / 2
    for k in (1, d // 3, d - 1):
        _assert_payload_parity(x, k)


def test_topk_sharded_all_zero_and_constant():
    # all-zero: every coordinate ties at t = 0 → keep the lowest indices
    for x in (jnp.zeros(3 * _B + 5), jnp.ones(3 * _B + 5)):
        v, i = topk_compress_sharded(x, 7)
        np.testing.assert_array_equal(i, jnp.arange(7))
        np.testing.assert_array_equal(v, x[:7])


def test_topk_sharded_negative_heavy(rng):
    """Values carry their sign through the pack; magnitude ordering only."""
    x = -jnp.abs(jax.random.normal(rng, (2 * _B + 17,))) - 0.5
    _assert_payload_parity(x, _B // 2)
    assert float(topk_compress_sharded(x, 5)[0].max()) < 0


def test_topk_sharded_sparse_high_index_survivors():
    # fewer nonzeros than k, the nonzero far from block 0: zero-ties fill
    # from index 0, the lone survivor keeps its global index
    d = 4 * _B
    xs = jnp.zeros(d).at[d - 3].set(9.0)
    _assert_payload_parity(xs, 3)


def test_topk_sharded_block_width_invariance(rng):
    """The packed payload must not depend on the launch's block width."""
    x = jax.random.normal(rng, (3000,))
    v1, i1 = topk_compress_sharded(x, 300, block=128)
    v2, i2 = topk_compress_sharded(x, 300, block=1024)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(v1, v2)


def test_topk_auto_select_dispatch(rng):
    """topk_compress routes by d: single-tile to the limit, gridded past
    it — and both sides of the boundary agree with the oracle."""
    assert kernel_plan(SINGLE_TILE_MAX_D)[0] == "single_tile"
    assert kernel_plan(SINGLE_TILE_MAX_D + 1)[0] == "gridded"
    for d in (SINGLE_TILE_MAX_D, SINGLE_TILE_MAX_D + 1):
        x = jax.random.normal(jax.random.fold_in(rng, d), (d,))
        v, i = topk_compress(x, 140)
        vr, ir = topk_compress_ref(x, 140)
        np.testing.assert_array_equal(i, ir)
        np.testing.assert_array_equal(v, vr)


def test_topk_sharded_vmap(rng):
    """Worker-stacked compression (the TreeChannel layout) over the
    gridded launch."""
    xs = jax.random.normal(rng, (3, 2000))
    vs, idxs = jax.jit(jax.vmap(lambda z: topk_compress_sharded(z, 64)))(xs)
    assert vs.shape == (3, 64) and idxs.shape == (3, 64)
    for b in range(3):
        vr, ir = topk_compress_ref(xs[b], 64)
        np.testing.assert_array_equal(idxs[b], ir)
        np.testing.assert_array_equal(vs[b], vr)


def test_kernel_plan_rejects_bad_blocks():
    with pytest.raises(ValueError, match="multiple of 128"):
        kernel_plan(4096, block=100)
    with pytest.raises(ValueError, match="VMEM"):
        kernel_plan(4096, block=4096)


# ------------------- sparse-domain aggregation kernel ---------------------


def _int_payload(m, k, d, seed, duplicates=False):
    """Integer-valued float payloads: every partial sum is exactly
    representable in f32, so dense/sparse/kernel paths must agree
    bit-for-bit regardless of summation order."""
    r = np.random.default_rng(seed)
    vals = r.integers(-8, 9, size=(m, k)).astype(np.float32)
    if duplicates:
        idx = r.integers(0, d, size=(m, k)).astype(np.int32)
    else:
        idx = np.stack([np.sort(r.choice(d, size=k, replace=False))
                        for _ in range(m)]).astype(np.int32)
    return jnp.asarray(vals), jnp.asarray(idx)


def _assert_sparse_parity(vals, idx, d, weights=None, exact=True):
    """Auto-dispatch, gridded kernel and scatter fallback all equal the
    numpy segmented-merge oracle."""
    ref = sparse_aggregate_ref(np.asarray(vals), np.asarray(idx), d,
                               None if weights is None else np.asarray(weights))
    check = (np.testing.assert_array_equal if exact else
             lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                     atol=1e-6))
    check(np.asarray(aggregate_sparse(vals, idx, d, weights)), ref)
    check(np.asarray(aggregate_sparse_gridded(vals, idx, d, weights)), ref)
    check(np.asarray(aggregate_sparse_scatter(vals, idx, d, weights)), ref)


@pytest.mark.parametrize("m,k,d", [
    (1, 1, 8),            # degenerate single worker, single entry
    (1, 16, 2048),        # m=1 at scatter scale
    (4, 32, 1408),        # scatter path, non-multiple-of-block d
    (8, 64, 8192),        # gridded path
    (3, 16, 65537),       # gridded, d past the ISSUE's 65536 + odd edge
    (4, 32, 65536),       # the ISSUE's sparse-path scale floor
])
def test_sparse_agg_integer_sweep(m, k, d):
    """Segmented merge vs the numpy oracle, bit-exact on integer-valued
    payloads across the scatter/gridded boundary (ISSUE: d up to 65536)."""
    vals, idx = _int_payload(m, k, d, seed=m * 10007 + k * 101 + d)
    _assert_sparse_parity(vals, idx, d)


@pytest.mark.parametrize("d", [512, 8192])
def test_sparse_agg_duplicate_indices(d):
    """Duplicate coordinates — within a worker and across workers — merge
    lowest-index-first; the dedup prepass keeps the kernel exact."""
    vals, idx = _int_payload(6, 40, min(d, 50), seed=d, duplicates=True)
    _assert_sparse_parity(vals, idx, d)


@pytest.mark.parametrize("d", [1024, 9000])
def test_sparse_agg_all_zero_payload(d):
    vals = jnp.zeros((5, 12), jnp.float32)
    idx = jnp.tile(jnp.arange(12, dtype=jnp.int32), (5, 1))
    _assert_sparse_parity(vals, idx, d)
    np.testing.assert_array_equal(
        np.asarray(aggregate_sparse(vals, idx, d)), np.zeros(d, np.float32))


@pytest.mark.parametrize("d", [2048, 16384])
def test_sparse_agg_weighted(d):
    """Per-worker weights (the norm-trim keep mask) fold into the merge;
    0/1 and small-integer weights stay exact."""
    vals, idx = _int_payload(7, 24, d, seed=d + 1)
    w01 = jnp.asarray([1, 0, 1, 1, 0, 1, 0], jnp.float32)
    _assert_sparse_parity(vals, idx, d, weights=w01)
    w_int = jnp.asarray([2, 1, 3, 1, 2, 1, 4], jnp.float32)
    _assert_sparse_parity(vals, idx, d, weights=w_int)


def test_sparse_agg_float_payloads(rng):
    """Dense random floats with distinct per-worker coordinates: every
    coordinate receives its contributions in the same (worker) order on
    every path, so parity holds to float tolerance."""
    for d in (3000, 20000):
        k1 = jax.random.fold_in(rng, d)
        vals = jax.random.normal(k1, (5, 64))
        idx = jnp.asarray(np.stack([
            np.sort(np.random.default_rng(d + i).choice(d, 64, replace=False))
            for i in range(5)]).astype(np.int32))
        _assert_sparse_parity(vals, idx, d, exact=False)


def test_sparse_agg_block_width_invariance():
    """The aggregate must not depend on the gridded launch's block."""
    vals, idx = _int_payload(6, 48, 40000, seed=3)
    o1 = aggregate_sparse_gridded(vals, idx, 40000, block=512)
    o2 = aggregate_sparse_gridded(vals, idx, 40000, block=1024)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_agg_kernel_plan_dispatch_and_rejects():
    """kernel_plan-style auto-dispatch boundaries + build-time ValueError
    on blocks the TPU tiling cannot serve."""
    assert agg_kernel_plan(8, SPARSE_SCATTER_MAX_D, k=64)[0] == "scatter"
    assert agg_kernel_plan(8, SPARSE_SCATTER_MAX_D + 1, k=64)[0] \
        == "sparse_gridded"
    assert agg_kernel_plan(DENSE_FUSED_MAX_M, 4096)[0] == "fused"
    assert agg_kernel_plan(DENSE_FUSED_MAX_M + 1, 4096)[0] == "dense"
    plan, P = agg_kernel_plan(10, 4096)
    assert plan == "fused" and P == 16   # m padded to a power of two
    with pytest.raises(ValueError, match="multiple of 128"):
        agg_kernel_plan(8, 65536, k=64, block=100)
    with pytest.raises(ValueError, match="VMEM"):
        agg_kernel_plan(8, 65536, k=64, block=8192)
    with pytest.raises(ValueError, match="multiple of 128"):
        agg_kernel_plan(8, 4096, block=100)


# ------------------- fused distance kernels (krum / row sort) -------------


@pytest.mark.parametrize("m,d", [(4, 64), (6, 600), (10, 1024), (13, 1500)])
@pytest.mark.parametrize("n_byz", [1, 2])
def test_krum_scores_vs_naive_ref(m, d, n_byz, rng):
    """Fused krum scores equal the naive O(m²) double-loop oracle and the
    selection equals the registry's krum_select."""
    flat = jax.random.normal(jax.random.fold_in(rng, m * 1000 + d), (m, d))
    scores = krum_scores_fused(flat, n_byz)
    ref = krum_scores_ref(np.asarray(flat), n_byz)
    np.testing.assert_allclose(np.asarray(scores), ref, rtol=2e-5)
    assert int(krum_select_fused(flat, n_byz)) == int(krum_select(flat, n_byz))


def test_krum_integer_payload_exact(rng):
    """Integer-valued stacks: squared distances and partial sums are
    exact in f32, so the fused scores match the oracle bit-for-bit."""
    r = np.random.default_rng(11)
    flat = jnp.asarray(r.integers(-5, 6, size=(8, 700)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(krum_scores_fused(flat, 2)),
        krum_scores_ref(np.asarray(flat), 2).astype(np.float32))


@pytest.mark.parametrize("m,d", [(2, 100), (5, 512), (8, 513), (16, 2000)])
def test_sort_workers_fused_exact(m, d, rng):
    """The tiled bitonic network is a pure permutation: bit-equal to
    jnp.sort over the worker axis, including +inf row padding."""
    x = jax.random.normal(jax.random.fold_in(rng, m * 31 + d), (m, d))
    np.testing.assert_array_equal(
        np.asarray(sort_workers_fused(x)), np.asarray(jnp.sort(x, axis=0)))


@pytest.mark.parametrize("m", [3, 4, 9, 12])
@pytest.mark.parametrize("trim_frac", [0.0, 0.2, 0.4])
def test_trimmed_mean_fused_matches_registry(m, trim_frac, rng):
    x = jax.random.normal(jax.random.fold_in(rng, m), (m, 777))
    np.testing.assert_array_equal(
        np.asarray(trimmed_mean_fused(x, trim_frac)),
        np.asarray(trimmed_mean(x, trim_frac)))


@pytest.mark.parametrize("m", [2, 3, 6, 11])
def test_coordinate_median_fused_matches_registry(m, rng):
    """Odd and even m (jnp.median's (low + high) / 2 midpoint)."""
    x = jax.random.normal(jax.random.fold_in(rng, 100 + m), (m, 640))
    np.testing.assert_array_equal(
        np.asarray(coordinate_median_fused(x)),
        np.asarray(coordinate_median(x)))


def test_fused_rules_reject_oversized_m(rng):
    big = jnp.zeros((DENSE_FUSED_MAX_M + 1, 256))
    with pytest.raises(ValueError, match="registry path"):
        krum_scores_fused(big, 2)
    with pytest.raises(ValueError, match="registry path"):
        sort_workers_fused(big)


@pytest.mark.parametrize("N,d", [(128, 256), (256, 512), (64, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(N, d, dtype, rng):
    x = jax.random.normal(rng, (N, d), dtype)
    w = 0.1 * jax.random.normal(jax.random.fold_in(rng, 1), (d,), jnp.float32)
    o = rmsnorm(x, w, block_rows=64)
    r = rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        o.astype(jnp.float32), r.astype(jnp.float32), atol=1e-2 if dtype == jnp.bfloat16 else 1e-5
    )


@pytest.mark.parametrize("given,want", [(True, True), (False, False),
                                        (None, True)])
def test_resolve_interpret(given, want):
    """An explicit choice wins; unset, a launch interprets exactly when
    the backend is not a TPU (the CPU here)."""
    from repro.kernels.mode import resolve_interpret

    assert jax.default_backend() == "cpu"
    assert resolve_interpret(given) is want
