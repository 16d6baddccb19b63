"""Table 1 + §6 comparison — communication cost to the gradient stopping
criterion, in ROUNDS *and* EXACT BITS ON THE WIRE (uplink + downlink).

Rounds: our cubic Newton vs ByzantinePGD [YCKB19] (R=10, r=5, Q=10,
T_th=10, coordinate-wise trimmed mean — their settings).  Paper numbers:
ByzantinePGD ≈ 198–212 rounds, ours ≈ 2–16 (w8a robust regression);
non-Byzantine §6: 257 vs 7 ⇒ the 36× claim.

Bits: every transmission — BOTH arms, Newton and PGD — routes through
:mod:`repro.comm` channels, so each row reports each run's exact integer
:class:`~repro.comm.WireLedger` totals per direction (m uplink payloads
+ one broadcast per round, PGD escape-probe rounds included — no
hand-rolled ``rounds · m · 32 · d`` estimate and no lossy float metric
anywhere; the PGD arm builds through ``ExperimentSpec(solver=
"byzantine_pgd")``, the channel-routed :mod:`repro.solvers` loop).  :func:`run_compression` sweeps
δ-approximate compressors on the same stopping criterion (top-k at
k/d = 0.1 pays ~7.8× fewer uplink bits per round on w8a and must stay
within 2× the uncompressed round count), optionally compressing the
downlink broadcast too; :func:`run_bits_to_eps` turns the same runs into
a total-bits(up+down)-to-ε curve — the budget question "how many bits
until ‖∇f‖ ≤ ε?" the rounds-only Table 1 cannot answer.

Kernel: :func:`run_kernel_timing` times the fused Pallas top-k payload
kernel (single-tile launch ≤ 1408, the sharded grid-over-blocks launch
beyond) against the XLA ``lax.top_k``+gather path at model-scale d ∈
{1.4k, 16k, 131k, 1M}, asserting bit-exact payload parity on every
shape — off-TPU the kernel runs in interpret mode, so rows carry an
``interpret_mode`` flag and the wall times answer "does it run at this
scale" rather than "is it faster" there.
"""
from __future__ import annotations

import time

import jax

from repro.api import ExperimentSpec, problem_dim
from repro.kernels.mode import resolve_interpret

KERNEL_TIMING_DS = (1408, 16_384, 131_072, 1_000_000)

ATTACKS = ("gaussian", "flipped_label", "negative", "random_label")

# ≥3 compressors for the wire-cost sweep (acceptance floor: none/topk/sign)
COMPRESSOR_SWEEP = (None, "topk:0.1", "signnorm", "int8")


def _spec_name(spec):
    return "none" if spec is None else spec


def run(dataset="w8a", attacks=ATTACKS, alphas=(0.10, 0.15, 0.20),
        grad_tol=0.02, max_rounds=400, newton_budget=60, seed=0):
    m = 20  # the paper workloads partition over 20 machines
    rows = []

    def one(attack, alpha):
        beta = alpha + 2.0 / m if alpha > 0 else 0.1
        exp = ExperimentSpec(
            problem=f"{dataset}-robust", M=10.0, eta=1.0,
            aggregator=f"norm_trim:{beta!r}", attack=attack, alpha=alpha,
            seed=seed,
        ).build()
        _, h_n = exp.run(newton_budget, grad_tol=grad_tol)
        # the PGD arm builds through the same facade (solver axis), so
        # its wire cost is the run's own exact ledger — Yin et al.'s
        # settings (R=10, r=5, Q=10, coordinate-wise trimmed mean)
        pgd = ExperimentSpec(
            problem=f"{dataset}-robust", eta=1.0,
            solver="byzantine_pgd",
            aggregator=f"trimmed_mean:{max(alpha, 0.1)!r}",
            attack=attack, alpha=alpha, seed=seed,
        ).build()
        _, h_p = pgd.run(max_rounds, grad_tol=grad_tol)
        pgd_up = h_p["uplink_bits"]
        pgd_down = h_p["downlink_bits"]
        return {
            "attack": attack,
            "alpha": alpha,
            "newton_rounds": h_n["rounds"],
            "pgd_rounds": h_p["rounds"],
            "speedup": h_p["rounds"] / max(h_n["rounds"], 1),
            # exact ints from the run's WireLedger
            "newton_uplink_bits": h_n["uplink_bits"],
            "newton_downlink_bits": h_n["downlink_bits"],
            "newton_total_bits": h_n["total_bits"],
            "newton_bits_per_round": (
                h_n["total_bits"] // max(h_n["rounds"], 1)
            ),
            "pgd_uplink_bits": pgd_up,
            "pgd_downlink_bits": pgd_down,
            "pgd_total_bits": h_p["total_bits"],
            "bits_speedup": pgd_up / max(h_n["uplink_bits"], 1),
        }

    # non-Byzantine headline comparison (the 36× claim)
    rows.append(one("none", 0.0))
    for attack in attacks:
        for alpha in alphas:
            rows.append(one(attack, alpha))
    return rows


def run_compression(dataset="w8a", compressors=COMPRESSOR_SWEEP,
                    attack="none", alpha=0.0, grad_tol=0.02,
                    newton_budget=60, seed=0, downlink=None):
    """Rounds AND exact bits to the gradient stopping criterion, per
    compressor.

    Same workload/criterion as :func:`run`'s Newton arm; each row reports
    the channels' per-round uplink/downlink cost and the run's exact
    ledger totals.  ``downlink`` optionally compresses the broadcast too
    (e.g. ``"topk:0.1"``).  The acceptance bar is topk:0.1 within 2× of
    the uncompressed round count on w8a-robust at ≥4.7× fewer uplink
    bits.
    """
    d = problem_dim(f"{dataset}-robust")
    m = 20  # the paper workloads partition over 20 machines
    beta = alpha + 2.0 / m if alpha > 0 else 0.1
    rows = []
    for spec in compressors:
        exp = ExperimentSpec(
            problem=f"{dataset}-robust", M=10.0, eta=1.0,
            aggregator=f"norm_trim:{beta!r}", attack=attack, alpha=alpha,
            compressor=spec, downlink_compressor=downlink, seed=seed,
        ).build()
        _, h = exp.run(newton_budget, grad_tol=grad_tol)
        newton = exp.algo
        bps = newton.bits_per_step()
        comp = newton.uplink.compressor
        rows.append({
            "compressor": _spec_name(spec),
            "downlink": _spec_name(downlink),
            "rounds": h["rounds"],
            "reached_tol": h["grad_norm"][-1] <= grad_tol,
            "grad_norm": h["grad_norm"][-1],
            "uplink_bits_per_round": bps["uplink"],
            "downlink_bits_per_round": bps["downlink"],
            "payload_bits_per_worker": bps["uplink"] // m,
            "uplink_bits": h["uplink_bits"],
            "downlink_bits": h["downlink_bits"],
            "total_bits": h["total_bits"],
            "delta_bound": (
                comp.delta_bound(d) if comp is not None else 1.0
            ),
        })
    base = next((r for r in rows if r["compressor"] == "none"), None)
    for r in rows:
        # relative columns only exist when the sweep includes a baseline
        r["round_overhead"] = (
            r["rounds"] / max(base["rounds"], 1) if base else None
        )
        r["bits_saving"] = (
            base["uplink_bits"] / max(r["uplink_bits"], 1)
            if base else None
        )
        r["total_bits_saving"] = (
            base["total_bits"] / max(r["total_bits"], 1)
            if base else None
        )
    return rows


def run_kernel_timing(ds=KERNEL_TIMING_DS, ratio=0.1, repeats=3, seed=0):
    """Fused top-k kernel vs the XLA ``lax.top_k`` path: wall time per
    packed-payload call at model-scale d, with bit-exact parity asserted
    on every shape (same values, same int32 indices — so the timing can
    never drift away from the semantics it claims to speed up).

    Each row reports the auto-selected launch plan (``single_tile`` ≤
    1408, ``gridded`` beyond), the per-call microseconds for both paths,
    and whether the kernel executed in interpret mode (any backend other
    than TPU): interpret rows time the kernel's *semantics*, not its
    silicon performance.
    """
    import numpy as np

    from repro.kernels import kernel_plan, topk_compress
    from repro.kernels.ref import topk_compress_ref
    from repro.telemetry import get_telemetry

    tel = get_telemetry()
    rows = []
    for d in ds:
        k = max(1, int(round(ratio * d)))
        # each rung of the d ladder is one span (parity check + both
        # timed paths), so a --trace-dir run shows where the ladder's
        # wall time actually goes instead of ad-hoc prints
        with tel.span("bench.topk_kernel.d", d=d, k=k):
            x = jax.random.normal(jax.random.PRNGKey(seed), (d,))
            plan, tile = kernel_plan(d)
            kern = jax.jit(lambda z, kk=k: topk_compress(z, kk))
            xla = jax.jit(lambda z, kk=k: topk_compress_ref(z, kk))
            v1, i1 = kern(x)
            v2, i2 = xla(x)
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
            np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))

            def _time(f, z=x):
                f(z)[0].block_until_ready()      # compiled above; re-warm
                t0 = time.perf_counter()
                for _ in range(repeats):
                    f(z)[0].block_until_ready()
                return (time.perf_counter() - t0) / repeats * 1e6

            row = {
                "d": d,
                "k": k,
                "plan": plan,
                "tile": tile,
                "kernel_us": _time(kern),
                "xla_topk_us": _time(xla),
                "backend": jax.default_backend(),
                "interpret_mode": resolve_interpret(None),
            }
        if tel.enabled:
            tel.event("bench.topk_kernel.row", d=d, k=k, plan=plan,
                      kernel_us=row["kernel_us"],
                      xla_topk_us=row["xla_topk_us"],
                      interpret_mode=row["interpret_mode"])
        rows.append(row)
    return rows


AGG_ROOFLINE_MS = (8, 32, 128)

# registry krum materializes an (m, m, d) diff tensor — past this float32
# budget (~1 GB) the dense baseline is infeasible and its row is skipped
# loudly rather than silently downsized
KRUM_BASELINE_MAX_ELEMS = 2**28


def run_agg_roofline(ms=AGG_ROOFLINE_MS, ds=KERNEL_TIMING_DS, ratio=0.1,
                     repeats=3, seed=0, max_k=8192):
    """Aggregation roofline on the same 1.4k → 1M d ladder as
    :func:`run_kernel_timing`, swept over cluster sizes m: the fused
    robust-aggregation kernels vs their XLA dense baselines, parity
    asserted on every shape.

    Three rows per (m, d):

    * ``sparse_mean`` — :func:`repro.kernels.aggregate_sparse` summing m
      top-k payloads straight from the wire (O(m·k) center memory) vs
      the dense path (per-worker scatter to (m, d), then sum).  Payloads
      are integer-valued with distinct per-worker indices (the top-k
      wire format), so parity is exact equality.
    * ``trimmed_mean`` — the tiled bitonic-sort kernel vs the registry's
      ``jnp.sort``-based rule (bit-equal by construction).
    * ``krum`` — the blocked pairwise-distance kernel vs the registry
      ``krum_select``; the baseline's (m, m, d) diff tensor caps its
      feasible shapes (:data:`KRUM_BASELINE_MAX_ELEMS`) — infeasible
      rows keep the kernel timing and carry ``baseline_skipped=True``.

    Off-TPU every kernel runs in interpret mode (flagged per row): the
    numbers answer "does it run, bit-exactly, at this scale".
    """
    import numpy as np

    import jax.numpy as jnp

    from repro.core import aggregation as _agg
    from repro.kernels import (
        agg_kernel_plan,
        aggregate_sparse,
        krum_select_fused,
        trimmed_mean_fused,
    )
    from repro.telemetry import get_telemetry

    tel = get_telemetry()
    interpret = resolve_interpret(None)
    rng = np.random.default_rng(seed)
    rows = []

    def _time(f, *args):
        jax.block_until_ready(f(*args))          # warm (compile above)
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(f(*args))
        return (time.perf_counter() - t0) / repeats * 1e6

    for m in ms:
        for d in ds:
            k = max(1, min(int(round(ratio * d)), max_k, d))
            with tel.span("bench.agg_roofline.md", m=m, d=d, k=k):
                # -- sparse-domain aggregation ------------------------
                vals = jnp.asarray(
                    rng.integers(-8, 9, size=(m, k)), jnp.float32)
                # distinct per-worker indices via strided sampling (the
                # top-k wire guarantee), index-ascending like the wire
                stride = d // k
                idx = jnp.asarray(
                    np.arange(k)[None, :] * stride
                    + rng.integers(0, stride, size=(m, k)),
                    jnp.int32)
                sparse_fn = jax.jit(lambda v, i: aggregate_sparse(v, i, d))
                dense_fn = jax.jit(lambda v, i: jax.vmap(
                    lambda vi, ii: jnp.zeros((d,), vi.dtype).at[ii].set(vi)
                )(v, i).sum(0))
                got = sparse_fn(vals, idx)
                want = dense_fn(vals, idx)
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(want))
                plan, _ = agg_kernel_plan(m, d, k=k)
                rows.append({
                    "rule": "sparse_mean", "m": m, "d": d, "k": k,
                    "plan": plan,
                    "kernel_us": _time(sparse_fn, vals, idx),
                    "xla_dense_us": _time(dense_fn, vals, idx),
                    "center_bytes_sparse": m * k * 8 + 4 * d,
                    "center_bytes_dense": m * d * 4 + 4 * d,
                    "backend": jax.default_backend(),
                    "interpret_mode": interpret,
                })

                # -- fused dense rules: integer-valued (m, d) stack ---
                x = jnp.asarray(
                    rng.integers(-5, 6, size=(m, d)), jnp.float32)
                tm_kern = lambda z: trimmed_mean_fused(z, 0.2)
                tm_xla = jax.jit(lambda z: _agg.trimmed_mean(z, 0.2))
                np.testing.assert_array_equal(
                    np.asarray(tm_kern(x)), np.asarray(tm_xla(x)))
                rows.append({
                    "rule": "trimmed_mean", "m": m, "d": d,
                    "plan": agg_kernel_plan(m, d)[0],
                    "kernel_us": _time(tm_kern, x),
                    "xla_dense_us": _time(tm_xla, x),
                    "backend": jax.default_backend(),
                    "interpret_mode": interpret,
                })

                n_byz = max(1, m // 8)
                kr_kern = lambda z: krum_select_fused(z, n_byz)
                baseline_ok = m * m * d <= KRUM_BASELINE_MAX_ELEMS
                row = {
                    "rule": "krum", "m": m, "d": d, "n_byz": n_byz,
                    "plan": agg_kernel_plan(m, d)[0],
                    "kernel_us": _time(kr_kern, x),
                    "baseline_skipped": not baseline_ok,
                    "backend": jax.default_backend(),
                    "interpret_mode": interpret,
                }
                if baseline_ok:
                    kr_xla = jax.jit(
                        lambda z: _agg.krum_select(z, n_byz))
                    assert int(kr_kern(x)) == int(kr_xla(x))
                    row["xla_dense_us"] = _time(kr_xla, x)
                else:
                    print(f"agg_roofline: krum dense baseline skipped at "
                          f"m={m} d={d} (m²·d = {m * m * d} > "
                          f"{KRUM_BASELINE_MAX_ELEMS})")
                rows.append(row)
            if tel.enabled:
                for r in rows[-3:]:
                    tel.event("bench.agg_roofline.row", **{
                        kk: vv for kk, vv in r.items()
                        if isinstance(vv, (int, float, str, bool))})
    return rows


def run_bits_to_eps(dataset="a9a", compressors=COMPRESSOR_SWEEP,
                    eps_grid=(0.3, 0.1, 0.05, 0.02), newton_budget=60,
                    seed=0, downlink=None):
    """Total-bits-to-ε curves: cumulative exact wire bits (uplink +
    downlink) spent when ‖∇f‖ first drops below each ε.

    Returns one row per compressor with the full (bits, grad_norm)
    trajectory plus the bits-at-ε table (None where the budget never
    reached ε) — the x axis is the per-step ``bits_cumulative`` ledger
    series, so adaptive-k runs report their true varying per-step cost.
    """
    rows = []
    for spec in compressors:
        exp = ExperimentSpec(
            problem=f"{dataset}-robust", M=10.0, eta=1.0,
            aggregator="norm_trim:0.1", compressor=spec,
            downlink_compressor=downlink, seed=seed,
        ).build()
        _, h = exp.run(newton_budget)
        bits_at_eps = {}
        for eps in eps_grid:
            hit = next(
                (b for b, gn in zip(h["bits_cumulative"], h["grad_norm"])
                 if gn <= eps),
                None,
            )
            bits_at_eps[eps] = hit
        rows.append({
            "compressor": _spec_name(spec),
            "downlink": _spec_name(downlink),
            "bits_cumulative": h["bits_cumulative"],
            "grad_norm": h["grad_norm"],
            "bits_to_eps": bits_at_eps,
        })
    return rows
