"""Algorithm 1 — Byzantine-Robust Distributed Cubic-Regularized Newton.

This module is the *paper-faithful* runtime: m workers simulated on one
process, explicit per-worker gradients / Hessians (the paper's LIBSVM regime,
d ≤ a few hundred), the paper's Algorithm 2 inner solver, the Byzantine
attacks resolved from the :mod:`repro.api.attacks` registry, and a
:mod:`repro.api.aggregators` registry rule at the center (the paper's
norm-based thresholding by default; krum / trimmed-mean /
coordinate-median / mean as declared).

Every transmission goes through :mod:`repro.comm` — the unified
communication-channel layer (§1's third pillar / COMRADE): an **uplink**
:class:`~repro.comm.VectorChannel` carries the δ-compressed worker
updates s_i with per-worker EF/EF21 state and the Byzantine-injection
hook; an optional **downlink** channel compresses the center→worker
broadcast of the aggregated step; in two-round (Remark 5) mode the
gradient round is a second uplink channel with its own EF21 state, so
ε_g = 0 no longer costs full precision on the wire.  Exact integer wire
accounting comes from the channels' static ``bits_per_round`` feeding a
host-side :class:`~repro.comm.WireLedger` (never a lossy traced float).

The at-scale (mesh-sharded, matrix-free) variant for the assigned
architectures lives in :mod:`repro.core.distributed`.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .cubic import solve_cubic_gd_counted
from ..comm import VectorChannel, WireLedger
from ..compression import AdaptiveTopK
from ..telemetry import (
    RoundRecord,
    SuspicionTracker,
    compile_scope,
    get_telemetry,
    planted_byzantine_ids,
    record_retrace,
    rejected_from_keep,
)

# Rounds one device stretch may run: ``run()`` makes one dispatch and one
# pull a stretch, so a solve of up to this many rounds makes one of each.
STRETCH_ROUNDS = 32


def _float32_at_most(x: float) -> np.float32:
    """The largest float32 ≤ ``x``: a float32 norm ``n`` passes
    ``n <= it`` exactly when ``float(n) <= x``, the host's float64 test.
    (The nearest float32 may lie above ``x``, and pass a norm that the
    host's test would not.)"""
    t = np.float32(x)
    return np.nextafter(t, np.float32(-np.inf)) if float(t) > x else t


def _eval_parts(eval_fn):
    """``(function, data)`` of an evaluation, staged as ``function(*args,
    w, **kwargs)`` with ``data = (args, kwargs)``: a ``functools.partial``
    hands its bound arrays to the stretch as arguments (closed over, they
    would become constants of the executable, and a new closure a solve
    would compile anew); any other callable is staged as it is."""
    if isinstance(eval_fn, functools.partial):
        return eval_fn.func, (eval_fn.args, eval_fn.keywords)
    return eval_fn, ((), {})


def _row_names(with_eval: bool) -> list:
    """The numbers a device stretch records each round, in its rows."""
    return ["loss", "grad_norm", "uplink_delta", "cubic_iters"] + (
        ["eval"] if with_eval else [])


def _empty_history() -> dict:
    """The history a paper-runtime solve fills."""
    return {"loss": [], "grad_norm": [], "eval": [], "rounds": 0,
            "bits_cumulative": [], "uplink_delta": [], "cubic_iters": [],
            "k_trajectory": [], "saddle_escape_step": None,
            "truncated": False}


@dataclasses.dataclass(frozen=True)
class NewtonConfig:
    """Hyper-parameters of Algorithm 1 (paper's notation)."""

    M: float = 10.0          # cubic regularization weight
    gamma: float = 1.0       # sub-problem second/third-order emphasis (Remark 1)
    eta: float = 1.0         # step size η_k (paper uses 1 in experiments)
    beta: float = 0.0        # trim fraction (β > α required for resilience)
    solver_tol: float = 1e-6
    solver_iters: int = 500  # cap for Algorithm 2's while-loop
    exact_gradient: bool = False  # Remark 5: extra round ⇒ ε_g = 0
    momentum: float = 0.0    # beyond-paper: CR-with-momentum [WZLL20]
    # δ-approximate compression (repro.compression spec strings, e.g.
    # "topk:0.1", "signnorm", "adaptive_topk:0.05:0.5"; None ⇒ full
    # precision) for the three wire segments, each its own channel:
    compressor: Optional[str] = None           # uplink: worker updates s_i
    downlink_compressor: Optional[str] = None  # center→worker broadcast
    grad_compressor: Optional[str] = None      # Remark-5 gradient round
    error_feedback: str = "ef21"  # "none" | "ef" | "ef21" (tracking)
    ef_damping: float = 0.75      # θ; mid-plateau on w8a (see error_feedback.py)
    # center aggregation rule as a repro.api.aggregators spec string
    # ("norm_trim:0.25", "krum:2", "trimmed_mean:0.1", "coordinate_median",
    # "mean", or a fused-kernel variant like "krum_kernel:2"); None keeps
    # the legacy β-field behaviour (norm_trim(β) when β > 0, plain mean
    # otherwise)
    aggregator: Optional[str] = None
    # sparse-domain center: aggregate top-k wire payloads directly
    # (O(m·k) center memory, never densifying the m worker vectors).
    # None ⇒ auto — on whenever the uplink channel supports the sparse
    # receive (sparse compressor, no error feedback, no update attack)
    # AND the aggregator has a sparse path (mean / norm_trim).  True
    # demands it (build error when unsupported); False forces the dense
    # center.
    sparse_center: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class AttackConfig:
    name: str = "none"            # a repro.api.attacks rule name
    alpha: float = 0.0            # Byzantine fraction
    sigma: float = 10.0           # gaussian attack scale
    c: float = 0.9                # negative-update attack scale
    scale: float = 5.0            # saddle attack scale
    num_classes: int = 2


class DistributedCubicNewton:
    """Simulated cluster running Algorithm 1.

    ``loss_fn(w, X, y) -> scalar`` is the per-worker empirical loss; workers'
    data is stacked on a leading axis: ``X: (m, n, d)``, ``y: (m, n)``.
    One ``step`` = one communication round (two if ``exact_gradient``).

    ``runtime_label`` names the runtime in emitted round records;
    subclasses (the async runtime) override it.

    Every round runs inside a device stretch (:meth:`_stretch_impl`, one
    jitted ``lax.while_loop`` of up to :data:`STRETCH_ROUNDS` rounds):
    ``run()`` runs a solve as stretches, ``step()`` as a stretch of one.
    ``eval_fn`` is the evaluation ``step()`` stages with its round, so
    that a ``step()`` compiles the program ``run(eval_fn=eval_fn)`` runs.

    Channels (and their compressors / error-feedback wrappers) are
    resolved ONCE, lazily at the first step for the observed ``(d, m)``
    — never inside a trace.  ``self.ledger`` accumulates exact integer
    uplink/downlink bits host-side.
    """

    runtime_label = "paper"

    def __init__(
        self,
        loss_fn: Callable,
        config: NewtonConfig = NewtonConfig(),
        attack: AttackConfig = AttackConfig(),
        eval_fn: Optional[Callable] = None,
    ):
        # registries resolve ONCE here, never inside a trace (the api
        # import is lazy purely to keep the package import graph acyclic)
        from ..api.aggregators import default_aggregator_spec, make_aggregator
        from ..api.attacks import resolve_attack

        self.loss_fn = loss_fn
        self.config = config
        self.attack = attack
        self.aggregator = make_aggregator(
            config.aggregator
            if config.aggregator is not None
            else default_aggregator_spec(config.beta)
        )
        self._attack_rule = resolve_attack(attack)
        self._grad_fn = jax.grad(loss_fn)
        self._hess_fn = jax.hessian(loss_fn)
        # built once per runtime, so only the first solve compiles it;
        # the pooled data are arguments (closed over, they would become
        # constants of the executable)
        self._pooled = jax.jit(self._pooled_impl)
        self.eval_fn = eval_fn
        self._traces: dict = {}    # evaluation function -> stages on device
        self._consts: dict = {}    # (dtype, value) -> device scalar
        self.rounds_per_step = 2 if config.exact_gradient else 1
        self.ledger = WireLedger()
        # channels need (d, m); built once at the first step
        self._dims: Optional[tuple] = None
        self._use_sparse_center = False
        self.uplink: Optional[VectorChannel] = None
        self.downlink: Optional[VectorChannel] = None
        self.grad_uplink: Optional[VectorChannel] = None
        self._rebuild_jit()

    # -- channel construction (once per (d, m), never per trace) -------
    def _rebuild_jit(self):
        """(Re)create the jitted step — required whenever a channel's
        static shape (an adaptive compressor's k) changes.  Each rebuild
        is an explicit telemetry re-trace event carrying the shape key
        (the live per-channel ks) that triggered it."""
        if self._dims is not None:   # a re-build, not the initial build
            record_retrace(
                "newton.step.rebuild",
                **{f"k_{name}": ch.compressor.k
                   for name, ch in self.channels.items()
                   if isinstance(ch.compressor, AdaptiveTopK)},
            )
        # the round alone, outside a stretch, which run() and step() never
        # run: the benchmark lowers it, the tests run it as the reference
        self._step = jax.jit(self._step_impl)
        self._stretch = jax.jit(self._stretch_impl,
                                static_argnames="eval_call")
        self._carry0 = None    # (w0's shape and dtype, v0, state0)

    def _ensure_channels(self, d: int, m: int):
        if self._dims == (d, m):
            return
        cfg = self.config
        self.uplink = VectorChannel(
            "uplink", cfg.compressor, d, m,
            error_feedback=cfg.error_feedback, damping=cfg.ef_damping,
            attack_hook=self._attack_rule.update_hook(m),
        )
        self.downlink = VectorChannel(
            "downlink", cfg.downlink_compressor, d, 1,
            error_feedback=cfg.error_feedback, damping=cfg.ef_damping,
        )
        # Remark-5 gradient round: its own channel + EF21 state, so the
        # extra round no longer forces full precision on the wire.
        self.grad_uplink = VectorChannel(
            "uplink", cfg.grad_compressor, d, m,
            error_feedback=cfg.error_feedback, damping=cfg.ef_damping,
        ) if cfg.exact_gradient else None
        # sparse-domain center: resolved once the channels exist
        can_sparse = (self.uplink.supports_sparse_receive
                      and self.aggregator.supports_sparse)
        if cfg.sparse_center and not can_sparse:
            raise ValueError(
                "sparse_center=True needs a sparse uplink compressor "
                "(top-k family) with error_feedback='none', no update "
                "attack, and a mean/norm_trim aggregator — got "
                f"compressor={cfg.compressor!r}, "
                f"error_feedback={cfg.error_feedback!r}, "
                f"attack={self.attack.name!r}, "
                f"aggregator={self.aggregator.spec!r}"
            )
        self._use_sparse_center = (can_sparse if cfg.sparse_center is None
                                   else bool(cfg.sparse_center))
        if self._dims is not None:
            self._rebuild_jit()   # stale trace would bake the old channels in
        self._dims = (d, m)

    @property
    def channels(self):
        """The live channels (built at first step), keyed by segment."""
        chans = {"uplink": self.uplink, "downlink": self.downlink}
        if self.grad_uplink is not None:
            chans["grad_uplink"] = self.grad_uplink
        return chans

    def init_comm_state(self):
        """Fresh channel-state pytree (per-worker EF memories)."""
        return {
            "uplink": self.uplink.init_state(),
            "downlink": self.downlink.init_state(),
            "grad": (self.grad_uplink.init_state()
                     if self.grad_uplink is not None else jnp.zeros((0,))),
        }

    def _const(self, value):
        """``value`` as a device scalar (int32, float32 or bool), placed
        once per runtime: a stretch's bound, ε and key flag cross to the
        device once, not in every dispatch."""
        value = np.asarray(value, np.int32 if type(value) is int else None)
        k = (value.dtype.str, value.tobytes())
        if k not in self._consts:
            self._consts[k] = jax.device_put(value)
        return self._consts[k]

    def _fresh_carry(self, w0):
        """``(v, state)`` a device solve starts from: zeros, made once per
        channel set and shared by every solve (arrays are immutable), so
        a solve dispatches no eager op of its own."""
        aval = (jnp.shape(w0), jnp.result_type(w0))
        if self._carry0 is None or self._carry0[0] != aval:
            self._carry0 = (aval, jnp.zeros_like(w0), self.init_comm_state())
        return self._carry0[1:]

    def _pooled_impl(self, w, Xf, yf):
        """``(loss, ‖∇loss‖)`` on the pooled data, one forward pass."""
        loss, g = jax.value_and_grad(self.loss_fn)(w, Xf, yf)
        return loss, jnp.linalg.norm(g)

    def _pooled_eval(self, w, Xf, yf):
        """The pooled loss and gradient norm as host floats: one
        dispatch of the jitted pooled program and one pull."""
        with compile_scope("newton.pooled"):
            loss, gn = jax.device_get(self._pooled(w, Xf, yf))
        return float(loss), float(gn)

    # ------------------------------------------------------------------
    def _worker_update(self, w, X, y, global_g):
        """One worker: local g, H; solve the cubic sub-problem (Eq. 2).
        Returns ``(s, iterations of Algorithm 2)``."""
        cfg = self.config
        g = self._grad_fn(w, X, y) if global_g is None else global_g
        H = self._hess_fn(w, X, y)
        return solve_cubic_gd_counted(
            g,
            H,
            M=cfg.M,
            gamma=cfg.gamma,
            tol=cfg.solver_tol,
            max_iters=cfg.solver_iters,
        )

    def _step_impl(self, w, v, state, X, y, key):
        cfg = self.config
        m = X.shape[0]
        k_label, k_update, k_comp, k_grad, k_down = jax.random.split(key, 5)
        new_state = dict(state)

        # Data-level attacks corrupt Byzantine workers' labels *before* the
        # local computation (they "train on wrong labels", §6).
        y_used = self._attack_rule.corrupt_labels(k_label, y)

        global_g = None
        if cfg.exact_gradient:
            # Remark 5: round 1 ships local gradients through the gradient
            # channel (δ-compressed + EF21 when configured); the center
            # aggregates with the SAME registry rule as the update round
            # (Byzantine workers corrupt their gradient share too).
            per_g = jax.vmap(self._grad_fn, in_axes=(None, 0, 0))(w, X, y_used)
            per_g, new_state["grad"] = self.grad_uplink.transmit(
                per_g, state["grad"], key=k_grad
            )
            global_g, _ = self.aggregator(per_g)

        s, cubic_iters = jax.vmap(
            lambda Xi, yi: self._worker_update(w, Xi, yi, global_g)
        )(X, y_used)

        # Uplink: honest workers δ-compress s_i (EF/EF21 memory carries the
        # residual across rounds); the channel's Byzantine hook corrupts the
        # *reconstructed* vectors — Byzantine workers send arbitrary
        # payloads, so compression grants them no protection.  ``measure``
        # surfaces the achieved contraction δ̂ (one norm ratio, taken
        # BEFORE Byzantine injection) for the adaptive-k schedule.
        # per-worker δ̂ is forensic-only: staged into the trace ONLY when
        # telemetry is enabled at trace time, so the disabled program is
        # the exact pre-forensics HLO (the zero-cost contract's pin)
        forensics = get_telemetry().enabled
        worker_delta = None
        if self._use_sparse_center:
            # sparse-domain center: the wire payloads (m, k) go straight
            # to the aggregator's sparse path — the m dense (d,) vectors
            # are never materialized at the center (O(m·k) not O(m·d)).
            # Valid exactly when the channel has no EF state and no
            # update attack (supports_sparse_receive, checked at build).
            if forensics:
                (pv, pidx), new_state["uplink"], uplink_delta, \
                    worker_delta = self.uplink.transmit_sparse(
                        s, state["uplink"], key=k_comp, measure=True,
                        per_sender=True,
                    )
            else:
                (pv, pidx), new_state["uplink"], uplink_delta = \
                    self.uplink.transmit_sparse(
                        s, state["uplink"], key=k_comp, measure=True
                    )
            agg, keep = self.aggregator.sparse(pv, pidx, w.shape[0])
            # payload norms == reconstruction norms (distinct indices)
            update_norms = jnp.linalg.norm(pv, axis=-1)
        else:
            if forensics:
                s, new_state["uplink"], uplink_delta, worker_delta = \
                    self.uplink.transmit(
                        s, state["uplink"], key=k_comp, attack_key=k_update,
                        measure=True, per_sender=True,
                    )
            else:
                s, new_state["uplink"], uplink_delta = self.uplink.transmit(
                    s, state["uplink"], key=k_comp, attack_key=k_update,
                    measure=True
                )

            # Center: the resolved aggregation rule (Algorithm 1, step 6
            # is norm_trim; krum / trimmed_mean / coordinate_median /
            # mean come from the same registry).
            agg, keep = self.aggregator(s)
            update_norms = jnp.linalg.norm(s, axis=-1)
        # optional momentum on the aggregated direction (CRm, [WZLL20] —
        # cited in §2; the paper itself uses v ≡ agg, i.e. momentum = 0)
        v_new = cfg.momentum * v + agg

        # Downlink: the center broadcasts the aggregated step η·v through
        # its own channel (EF state lives at the center); every worker —
        # and the center's own iterate — applies the same reconstruction,
        # so the cluster stays in sync.
        delta, new_state["downlink"] = self.downlink.transmit(
            cfg.eta * v_new, state["downlink"], key=k_down
        )
        w_new = w + delta
        # the vmapped while_loop runs until its slowest worker is done:
        # the iterations the device executed this round
        info = {
            "update_norms": update_norms, "keep": keep,
            "uplink_delta": uplink_delta,
            "cubic_iters": jnp.max(cubic_iters),
        }
        if worker_delta is not None:
            info["worker_delta"] = worker_delta
        return w_new, v_new, new_state, info

    def _stretch_impl(self, w, v, state, X, y, full, eval_data, key, bound,
                      tol, key_is_round_key, *, eval_call):
        """Up to ``bound`` (≤ :data:`STRETCH_ROUNDS`) rounds in one
        ``lax.while_loop``, each as a round at a time would run it: split
        the key, the round, the pooled ``(loss, ‖∇loss‖)`` at the new
        iterate, the evaluation.  It stops after the first round whose norm is
        ≤ ``tol``.  Returns the state after the last round, the key to go
        on from, the stretch's *record* and the last round's ``info``.
        The record is float32 ``(len(names) + 1, STRETCH_ROUNDS)``: one
        row a number (:func:`_row_names`), a column a round, and a last
        row that starts with the rounds run and whether ``tol`` was met.
        ``key_is_round_key`` makes ``key`` itself the first round's key
        (``step()``).  The pooled data are ``full``, or the workers' rows
        when it is None."""
        Xf, yf = ((X.reshape(-1, X.shape[-1]), y.reshape(-1))
                  if full is None else full)
        names = _row_names(eval_call is not None)
        rows0 = {n: jnp.zeros(STRETCH_ROUNDS, jnp.int32 if n == "cubic_iters"
                              else jnp.float32) for n in names}
        info0 = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(self._step_impl, w, v, state, X, y, key)[3])

        def body(c):
            w, v, state, key, t, _, rows, _ = c
            next_key, sub = jax.random.split(key)
            sub = jnp.where(key_is_round_key & (t == 0), key, sub)
            # the barriers keep the round and the evaluations from fusing
            # with each other, with the last round or with the data the
            # loop holds fixed: each reads as its own separately jitted
            # program (``_step``, ``_pooled``), to the last bit
            w, v, state, X_, y_, sub = jax.lax.optimization_barrier(
                (w, v, state, X, y, sub))
            w, v, state, info = self._step_impl(w, v, state, X_, y_, sub)
            w_, Xf_, yf_, eval_data_ = jax.lax.optimization_barrier(
                (w, Xf, yf, eval_data))
            loss, gn = self._pooled_impl(w_, Xf_, yf_)
            row = {"loss": loss, "grad_norm": gn,
                   "uplink_delta": info["uplink_delta"],
                   "cubic_iters": info["cubic_iters"]}
            if eval_call is not None:
                args, kwargs = eval_data_
                row["eval"] = eval_call(*args, w_, **kwargs)
            rows = {n: rows[n].at[t].set(row[n].astype(rows[n].dtype))
                    for n in names}
            return w, v, state, next_key, t + 1, gn <= tol, rows, info

        w, v, state, key, ran, hit, rows, info = jax.lax.while_loop(
            lambda c: (c[4] < bound) & ~c[5], body,
            (w, v, state, key, jnp.int32(0), jnp.bool_(False), rows0, info0))
        # one array, so the host pulls a stretch in one transfer; float32
        # holds the iteration counts and the round count exactly
        tail = jnp.zeros(STRETCH_ROUNDS).at[:2].set(
            jnp.stack([ran, hit]).astype(jnp.float32))
        record = jnp.stack([rows[n].astype(jnp.float32) for n in names]
                           + [tail])
        return w, v, state, key, record, info

    def _staged_eval(self, eval_fn, w):
        """``(eval_call, eval_data)`` a stretch stages for ``eval_fn``: its
        parts when it traces to a scalar (probed once per function), else
        ``(None, ((), {}))``, and the host calls it after each round."""
        unstaged = (None, ((), {}))
        if eval_fn is None:
            return unstaged
        call, data = _eval_parts(eval_fn)
        if call not in self._traces:
            try:
                out = jax.eval_shape(
                    lambda w, data: call(*data[0], w, **data[1]), w, data)
                self._traces[call] = getattr(out, "shape", None) == ()
            except (jax.errors.JAXTypeError, jax.errors.JAXIndexError):
                self._traces[call] = False    # it needs concrete values
        return (call, data) if self._traces[call] else unstaged

    # ------------------------------------------------------------------
    def step(self, w, X, y, key, v=None, state=None):
        """One round.  Returns (w, v, state, info) where ``state`` is the
        channel-state pytree (per-worker EF memories; see
        :meth:`init_comm_state`).  The round is a device stretch of one,
        with ``key`` as its key and ``self.eval_fn`` staged when it
        traces: the program ``run()`` runs, compiled here."""
        self._ensure_channels(w.shape[0], X.shape[0])
        v = jnp.zeros_like(w) if v is None else v
        state = self.init_comm_state() if state is None else state
        call, data = self._staged_eval(self.eval_fn, w)
        # every (re)compile of the step is attributed to this scope by
        # the telemetry compile-counter (host-side contextvar, never
        # traced) — the compile-count regression pins read it
        with compile_scope("newton.step"):
            w, v, state, _, _, info = self._stretch(
                w, v, state, X, y, None, data, key, self._const(1),
                self._const(np.float32(-np.inf)), self._const(True),
                eval_call=call)
        return w, v, state, info

    # -- wire accounting ------------------------------------------------
    def bits_per_step(self) -> dict:
        """Exact bits ONE step costs per direction (static Python ints;
        channels must exist — i.e. after the first step or
        :meth:`_ensure_channels`).  Two-round mode adds the gradient
        channel uplink and the full-precision gradient broadcast."""
        up = self.uplink.bits_per_round()
        down = self.downlink.bits_per_round()
        if self.grad_uplink is not None:
            up += self.grad_uplink.bits_per_round()
            down += 32 * self.uplink.d  # center broadcasts the averaged g
        return {"uplink": up, "downlink": down}

    def center_bytes_per_round(self) -> int:
        """Bytes the center's aggregation path touches per round (static
        Python int, like :meth:`bits_per_step`): what the receiver
        materializes between the wire and the (d,) aggregate.  Sparse
        center: the m (value, index) payloads (4 B each entry) plus the
        aggregate — O(m·k + d).  Dense center: m reconstructed f32
        vectors plus the aggregate — O(m·d).  Re-read per round: an
        adaptive uplink moves k between rounds."""
        m, d = self.uplink.n_senders, self.uplink.d
        if self._use_sparse_center:
            k = min(self.uplink.compressor.k, d)
            return m * k * 8 + 4 * d
        return m * d * 4 + 4 * d

    def _agg_kernel_label(self) -> str:
        """Which center path this configuration runs — the round record's
        ``agg_kernel`` field: ``"sparse"`` (payload-domain aggregation),
        ``"fused"`` (a kernel-backed dense rule), or ``"dense"``."""
        if self._use_sparse_center:
            return "sparse"
        if getattr(self.aggregator, "use_kernel", False):
            return "fused"
        return "dense"

    def _maybe_adapt(self, grad_norm: float,
                     measured_delta: Optional[float] = None) -> bool:
        """Feed adaptive compressors the host-side signals (gradient-norm
        plateau + the uplink channel's measured per-round δ); rebuild the
        jitted step when any k changed (static shapes moved).  Returns
        whether a rebuild happened (the round record's ``k_changed``)."""
        changed = False
        for name, ch in self.channels.items():
            comp = ch.compressor
            if isinstance(comp, AdaptiveTopK):
                changed |= comp.schedule_update(
                    grad_norm=grad_norm,
                    measured_delta=(measured_delta
                                    if name == "uplink" else None),
                )
        if changed:
            self._rebuild_jit()
        return changed

    def _uplink_k(self) -> Optional[int]:
        """The uplink's live adaptive k (None on non-adaptive wires)."""
        comp = self.uplink.compressor if self.uplink is not None else None
        return comp.k if isinstance(comp, AdaptiveTopK) else None

    def _worker_round_fields(self, info: dict, m: int, bps: dict,
                             tracker: SuspicionTracker) -> dict:
        """The schema-v4 per-worker round fields (host-side; called only
        when telemetry is enabled).  Uplink bits split evenly: every
        worker ships the same static payload per round."""
        keep = [float(k) for k in info["keep"]]
        norms = [float(n) for n in info["update_norms"]]
        fields = {
            "worker_bits": [bps["uplink"] // m] * m,
            "worker_keep": keep,
            "worker_norms": norms,
            "suspicion": tracker.update(keep=keep, norms=norms),
        }
        if info.get("worker_delta") is not None:
            fields["worker_delta"] = [float(x) for x in info["worker_delta"]]
        if self._attack_rule.kind != "none":
            fields["byzantine_true"] = planted_byzantine_ids(
                m, self._attack_rule.alpha
            )
        return fields

    def run(
        self,
        w0,
        X,
        y,
        n_steps: int,
        key=None,
        eval_fn: Optional[Callable] = None,
        grad_tol: Optional[float] = None,
        full_data=None,
        deadline: Optional[float] = None,
        saddle_value: Optional[float] = None,
    ):
        """Run Algorithm 1 for ``n_steps`` (or until ‖∇f‖ ≤ grad_tol on the
        pooled data).  Returns (w, history dict); the history carries the
        exact integer uplink/downlink wire totals from the ledger plus the
        per-step cumulative total (the bits-to-ε curve's x axis), the
        per-round measured δ̂, Algorithm 2's iterations a round
        (``cubic_iters``: the maximum over workers, what the vmapped loop
        runs on the device), and the adaptive-k trajectory (``None``
        entries on non-adaptive wires) — so sweep stores can pivot on
        them.

        The rounds run as device stretches: one ``newton.stretch`` span a
        stretch (``max_rounds``, its round bound), one dispatch and one
        pull, up to :data:`STRETCH_ROUNDS` rounds with the ε test, the
        pooled evaluation and a tracing ``eval_fn`` inside.  Where the host
        acts between rounds — telemetry on (round records, suspicion), an
        adaptive compressor (its k moves the round's shapes), an
        ``eval_fn`` that does not trace — each stretch is one round, and
        the host does that work after it.

        ``deadline`` (a ``time.monotonic()`` timestamp) cooperatively
        truncates the solve at a stretch boundary past it — always after
        at least one round — with ``hist["truncated"] = True``; the sweep
        runner's per-cell wall-time budget.  A solve may pass it by up to
        one stretch (:data:`STRETCH_ROUNDS` rounds); a deadline already
        past when the solve starts gives exactly one round.

        ``saddle_value`` (the problem's known f at its strict saddle, if
        any) defines the saddle-escape flag: the round whose loss first
        drops below it is the escape round (telemetry round records +
        ``hist["saddle_escape_step"]``)."""
        tel = get_telemetry()
        with tel.span("newton.solve"):
            self._ensure_channels(w0.shape[0], X.shape[0])
            key = key if key is not None else jax.random.PRNGKey(0)
            tol = self._const(np.float32(-np.inf) if grad_tol is None
                              else _float32_at_most(grad_tol))
            call, data = self._staged_eval(eval_fn, w0)
            host_eval = eval_fn if call is None else None
            host_rounds = tel.enabled or host_eval is not None or any(
                isinstance(ch.compressor, AdaptiveTopK)
                for ch in self.channels.values())
            ledger = self.ledger
            ledger.reset()
            hist = _empty_history()
            if tel.enabled:
                # f(w0) anchors the first round's model decrease
                Xf, yf = full_data if full_data is not None else (
                    X.reshape(-1, X.shape[-1]), y.reshape(-1))
                prev_loss = self._pooled_eval(w0, Xf, yf)[0]
                tracker = SuspicionTracker(X.shape[0])
            w = w0
            v, state = self._fresh_carry(w0)
            done = 0
            while done < n_steps:
                late = deadline is not None and time.monotonic() >= deadline
                if late and done:
                    hist["truncated"] = True
                    if tel.enabled:
                        tel.event("newton.truncated", step=done)
                    break
                bound = (1 if late or host_rounds
                         else min(STRETCH_ROUNDS, n_steps - done))
                k_live = self._uplink_k()   # the k this stretch transmits at
                bps = self.bits_per_step()  # adaptive k moves it
                with tel.span("newton.stretch", max_rounds=bound), \
                        compile_scope("newton.step"):
                    w, v, state, key, record, info = self._stretch(
                        w, v, state, X, y, full_data, data, key,
                        self._const(bound), tol, self._const(False),
                        eval_call=call)
                    record = np.asarray(record)
                ran, hit = int(record[-1, 0]), bool(record[-1, 1])
                rows = {n: r[:ran].tolist()
                        for n, r in zip(_row_names(call is not None), record)}
                rows["cubic_iters"] = [int(i) for i in rows["cubic_iters"]]
                for i, loss in enumerate(rows["loss"]):
                    ledger.record(uplink=bps["uplink"],
                                  downlink=bps["downlink"],
                                  rounds=self.rounds_per_step, label="round")
                    hist["bits_cumulative"].append(ledger.total_bits)
                    if (saddle_value is not None
                            and hist["saddle_escape_step"] is None
                            and loss < saddle_value):
                        hist["saddle_escape_step"] = done + i
                for n, r in rows.items():
                    hist[n] += r
                hist["k_trajectory"] += [k_live] * ran
                if host_rounds:     # the stretch ran the one round ``done``
                    (loss,), (gn,) = rows["loss"], rows["grad_norm"]
                    (delta_hat,) = rows["uplink_delta"]
                    if host_eval is not None:
                        hist["eval"].append(float(host_eval(w)))
                    k_changed = not hit and self._maybe_adapt(
                        gn, measured_delta=delta_hat)
                    if tel.enabled:
                        tel.round(RoundRecord(
                            step=done, runtime=self.runtime_label, loss=loss,
                            grad_norm=gn, model_decrease=prev_loss - loss,
                            uplink_delta=delta_hat, k=k_live,
                            k_changed=k_changed,
                            saddle_escape=hist["saddle_escape_step"] == done,
                            rejected=rejected_from_keep(info["keep"]),
                            attack=self.attack.name, alpha=self.attack.alpha,
                            wire_uplink_bits=bps["uplink"],
                            wire_downlink_bits=bps["downlink"],
                            center_bytes=self.center_bytes_per_round(),
                            agg_kernel=self._agg_kernel_label(),
                            **self._worker_round_fields(info, X.shape[0], bps,
                                                        tracker),
                        ), name="newton.round")
                        prev_loss = loss
                done += ran
                if hit:
                    break
            hist.update(ledger.snapshot())
            return w, hist
