"""Pure-jnp oracles for every Pallas kernel (the contract each kernel must
match; tests sweep shapes/dtypes and assert_allclose against these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """q,k,v: (B, H, S, Dh) (head-major layout the kernel uses).
    window=0 ⇒ no sliding window."""
    B, H, S, Dh = q.shape
    scale = 1.0 / jnp.sqrt(Dh).astype(jnp.float32)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    qpos = jnp.arange(S)
    kpos = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def topk_compress_ref(x, k):
    """Packed top-|x| payload in index-ascending order (the wire format of
    repro.compression.TopK): values (k,), indices (k,) int32."""
    _, idx = jax.lax.top_k(jnp.abs(x), k)
    idx = jnp.sort(idx)
    return x[idx], idx.astype(jnp.int32)


def topk_compress_sharded_ref(x, k, block=512):
    """Sharded oracle: the two-pass blocked contract of
    :func:`repro.kernels.topk_compress_sharded`, spelled out in numpy-style
    jnp (global threshold → sure/tie split → per-block tie budgets →
    blocked pack → compaction) with NO kernels.  Must equal
    :func:`topk_compress_ref` exactly — proving the blocked layout is a
    pure re-arrangement that changes neither the selected support nor the
    wire payload."""
    import numpy as np

    x = np.asarray(x, np.float32)
    d = x.shape[-1]
    mag = np.abs(x)
    # exact global threshold: the k-th largest magnitude (fp32 total order)
    t = np.sort(mag)[d - k]
    sure = mag > t                          # strictly inside the top-k band
    tie = mag == t                          # fill lowest-index-first
    n_sure = int(sure.sum())
    vals, idx = [], []
    budget_left = k - n_sure                # global tie budget
    for b0 in range(0, d, block):           # block order IS index order
        blk = slice(b0, min(b0 + block, d))
        tie_pos = np.nonzero(tie[blk])[0]
        tie_budget = min(budget_left, len(tie_pos))  # this block's budget
        keep = np.nonzero(sure[blk])[0].tolist()
        keep += tie_pos[:tie_budget].tolist()
        keep = sorted(keep)                 # per-block slice, index-ascending
        budget_left -= tie_budget
        idx += [b0 + j for j in keep]       # rebase to global coordinates
        vals += [x[b0 + j] for j in keep]
    assert len(idx) == k, "blocked budgets must pack exactly k survivors"
    return jnp.asarray(vals, jnp.float32), jnp.asarray(idx, jnp.int32)


def sparse_aggregate_ref(vals, idx, d, weights=None):
    """Segmented-merge contract of :func:`repro.kernels.aggregate_sparse`,
    spelled out sequentially: the m (k,) payloads ravel into one stream,
    the stream is stably sorted by coordinate (lowest-index-first;
    duplicate coordinates keep worker order), and every entry adds into
    the (d,) f32 accumulator **in that order** — one unbuffered
    ``np.add.at`` sweep.  Per-worker weights fold into the values before
    the merge.  No (m, d) array exists at any point."""
    import numpy as np

    v = np.asarray(vals, np.float32)
    if weights is not None:
        v = v * np.asarray(weights, np.float32)[:, None]
    vs = v.reshape(-1)
    ix = np.asarray(idx).reshape(-1)
    order = np.argsort(ix, kind="stable")
    out = np.zeros((d,), np.float32)
    np.add.at(out, ix[order], vs[order])
    return jnp.asarray(out)


def krum_scores_ref(flat, n_byz):
    """Naive O(m²) double-loop krum scores — the [BMGS17] definition the
    fused kernel and the registry ``krum_select`` must both minimize:
    score(i) = Σ of the k = max(m − n_byz − 2, 1) smallest ‖xᵢ − xⱼ‖²
    over j ≠ i, each distance summed coordinate-by-coordinate."""
    import numpy as np

    f = np.asarray(flat, np.float32)
    m = f.shape[0]
    k = max(m - int(n_byz) - 2, 1)
    scores = []
    for i in range(m):
        d2 = sorted(
            float(np.sum((f[i] - f[j]) ** 2)) for j in range(m) if j != i
        )
        scores.append(sum(d2[:k]))
    return jnp.asarray(scores, jnp.float32)


def rmsnorm_ref(x, w, eps=1e-6):
    """x: (N, d), w: (d,).  Gemma-style (1+w) scaling, fp32 accumulation."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))).astype(
        x.dtype
    )
