"""Asynchronous round runtime: Algorithm 1 under partial participation,
staleness, and packet faults.

:class:`AsyncCubicNewton` extends the paper-faithful synchronous runtime
(:class:`repro.core.newton.DistributedCubicNewton`) with an event-driven
round loop:

* each round a seeded **cohort** of workers computes and sends its
  EF-compressed update (``participation:<p>`` of m, sampled without
  replacement per round by the :class:`~repro.async_rt.EventScheduler`);
* a sent packet lands ``lag ∈ {0, …, staleness}`` rounds later in the
  center's :class:`~repro.async_rt.MessageQueue`; it may be **dropped**
  (paid on the wire, never delivered) or **duplicated** (paid twice,
  delivered twice, EF-committed once);
* the center's per-worker Channel/EF21 state is **versioned per
  arrival**: a packet carries the candidate state row its send produced,
  and the center commits it the first time that send arrives — so a
  straggler's next update is compressed against the state the center
  actually believes, and dropped packets never advance it;
* arrivals are aggregated by a :class:`~repro.async_rt.StalenessWeighted`
  wrapper over the configured registry rule (base rule's keep mask, then
  ``decay**age`` weighting), momentum/downlink/iterate update as in the
  synchronous step;
* exact wire accounting is preserved: every packet (including drops and
  duplicates) records its payload bits on the :class:`WireLedger` at
  send time, every executed round records one round + the downlink
  broadcast when anything arrived.

**Degenerate configs run the synchronous program.**  When
``participation == 1.0, staleness == 0, drop == duplicate == 0`` the
round semantics are exactly Algorithm 1, so :meth:`run` delegates to the
parent's jitted step — the identical jaxpr, hence *bit-exact* with
``runtime="paper"`` (two differently-structured XLA programs would not
be; sharing the trace is what makes the acceptance test exact).  This
also keeps the sparse-domain center available in degenerate mode; the
buffered path forces the dense center (arrival stacks re-order workers,
which the payload-domain receive cannot represent).

Device-side randomness (compressors, attacks) keeps the synchronous
runtime's per-round key-split structure; all scheduling randomness is
host-side numpy Philox (see :mod:`~repro.async_rt.scheduler`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..core.newton import AttackConfig, DistributedCubicNewton, NewtonConfig
from ..telemetry import (
    RoundRecord,
    SuspicionTracker,
    compile_scope,
    get_telemetry,
    planted_byzantine_ids,
    rejected_from_keep,
)
from .aggregate import StalenessWeighted
from .scheduler import EventScheduler, Message, MessageQueue


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """The async runtime's scheduling axes (all host-side semantics)."""

    participation: float = 1.0   # per-round cohort fraction p ∈ (0, 1]
    staleness: int = 0           # max rounds a packet lags (uniform lag)
    drop: float = 0.0            # P(a sent packet never arrives)
    duplicate: float = 0.0       # P(a sent packet is delivered twice)
    staleness_decay: float = 0.5  # arrival weight decay**age ∈ (0, 1]
    seed: int = 0                # the event scheduler's seed

    @property
    def degenerate(self) -> bool:
        """True when async semantics collapse to the synchronous round
        (full participation, no lag, no faults) — the config the
        bit-exactness guarantee covers."""
        return (self.participation >= 1.0 and self.staleness == 0
                and self.drop == 0.0 and self.duplicate == 0.0)


class AsyncCubicNewton(DistributedCubicNewton):
    """Algorithm 1 on the asynchronous round runtime (see module doc)."""

    runtime_label = "async"

    def __init__(
        self,
        loss_fn,
        config: NewtonConfig = NewtonConfig(),
        attack: AttackConfig = AttackConfig(),
        async_config: AsyncConfig = AsyncConfig(),
    ):
        self.async_config = async_config
        super().__init__(loss_fn, config, attack)
        if config.exact_gradient:
            raise ValueError(
                "the async runtime has no two-round (Remark 5) mode: the "
                "gradient round's global barrier is exactly what "
                "asynchrony removes — set exact_gradient=False"
            )
        self.staleness_agg = StalenessWeighted(
            self.aggregator, async_config.staleness_decay
        )

    # -- jitted pieces ---------------------------------------------------
    def _rebuild_jit(self):
        super()._rebuild_jit()
        # the async loop splits the synchronous step into two fixed-shape
        # jitted halves (compute+uplink over all m; downlink apply) with
        # the host-side buffer/aggregation seam between them
        self._ct = jax.jit(self._compute_transmit_impl)
        self._down = jax.jit(self._downlink_impl)

    def _compute_transmit_impl(self, w, uplink_state, X, y, key):
        """All m workers' cubic solves + uplink transmit, one trace.

        Mirrors the synchronous step's key-split structure exactly; the
        host selects the cohort's rows from the full (m, d) result, so
        the trace never depends on the (varying) cohort size.  Returns
        the reconstructed updates, the CANDIDATE uplink state (committed
        per arrival, not here), and the measured δ̂.
        """
        k_label, k_update, k_comp, _k_grad, _k_down = jax.random.split(key, 5)
        y_used = self._attack_rule.corrupt_labels(k_label, y)
        s, _ = jax.vmap(
            lambda Xi, yi: self._worker_update(w, Xi, yi, None)
        )(X, y_used)
        if get_telemetry().enabled:
            # forensics: also stage the per-sender δ̂ (trace-time gate —
            # the disabled program is the exact pre-forensics HLO)
            s_hat, new_state, delta, worker_delta = self.uplink.transmit(
                s, uplink_state, key=k_comp, attack_key=k_update,
                measure=True, per_sender=True,
            )
        else:
            s_hat, new_state, delta = self.uplink.transmit(
                s, uplink_state, key=k_comp, attack_key=k_update,
                measure=True,
            )
            worker_delta = None
        return s_hat, new_state, delta, worker_delta

    def _downlink_impl(self, v_new, downlink_state, key):
        """Center broadcast of the aggregated step (η·v), own channel."""
        *_rest, k_down = jax.random.split(key, 5)
        delta, new_state = self.downlink.transmit(
            self.config.eta * v_new, downlink_state, key=k_down
        )
        return delta, new_state

    # -- the round loop --------------------------------------------------
    def run(self, w0, X, y, n_steps, key=None, eval_fn=None, grad_tol=None,
            full_data=None, deadline=None, saddle_value=None):
        if self.async_config.degenerate:
            # the synchronous program IS the degenerate async program:
            # delegating to the parent's jitted step shares the jaxpr,
            # which is the only way "bit-exact with runtime='paper'" is
            # guaranteed (structurally different XLA programs are not)
            w, hist = super().run(
                w0, X, y, n_steps, key=key, eval_fn=eval_fn,
                grad_tol=grad_tol, full_data=full_data, deadline=deadline,
                saddle_value=saddle_value,
            )
            hist["async_degenerate"] = True
            return w, hist
        return self._run_async(
            w0, X, y, n_steps, key=key, eval_fn=eval_fn, grad_tol=grad_tol,
            full_data=full_data, deadline=deadline,
            saddle_value=saddle_value,
        )

    def _run_async(self, w0, X, y, n_steps, *, key, eval_fn, grad_tol,
                   full_data, deadline, saddle_value):
        import time as _time

        acfg = self.async_config
        key = key if key is not None else jax.random.PRNGKey(0)
        if full_data is None:
            full_data = (X.reshape(-1, X.shape[-1]), y.reshape(-1))
        Xf, yf = full_data
        m = X.shape[0]
        self._ensure_channels(w0.shape[0], m)
        if self._use_sparse_center:
            if self.config.sparse_center:
                raise ValueError(
                    "sparse_center=True needs the degenerate async config "
                    "(participation=1.0, staleness=0, no faults): the "
                    "buffered path aggregates re-ordered arrival stacks, "
                    "which the payload-domain center cannot represent"
                )
            self._use_sparse_center = False   # auto resolved: dense center

        sched = EventScheduler(
            acfg.seed, m, participation=acfg.participation,
            staleness=acfg.staleness, drop=acfg.drop,
            duplicate=acfg.duplicate,
        )
        queue = MessageQueue()
        ledger = self.ledger
        ledger.reset()
        hist = {"loss": [], "grad_norm": [], "eval": [], "rounds": 0,
                "bits_cumulative": [], "uplink_delta": [],
                "k_trajectory": [], "saddle_escape_step": None,
                "truncated": False, "async_degenerate": False,
                "cohort_size": [], "n_arrivals": [], "queue_depth": [],
                "staleness_mean": []}
        tel = get_telemetry()
        prev_loss = self._pooled_eval(w0, Xf, yf)[0] if tel.enabled else None
        tracker = SuspicionTracker(m) if tel.enabled else None
        w = w0
        v = jnp.zeros_like(w0)
        state = self.init_comm_state()
        stateful_uplink = self.uplink.feedback is not None
        committed_version = [-1] * m

        for t in range(n_steps):
            if deadline is not None and hist["loss"] \
                    and _time.monotonic() >= deadline:
                hist["truncated"] = True
                if tel.enabled:
                    tel.event("newton.truncated", step=t)
                break
            key, sub = jax.random.split(key)
            k_live = self._uplink_k()
            cohort = sched.cohort(t)
            with compile_scope("async.compute"):
                s_hat, cand_state, delta_hat, worker_delta = self._ct(
                    w, state["uplink"], X, y, sub
                )
            # wire accounting at SEND time: every packet pays its payload
            # bits (drops included — the sender transmitted; duplicates
            # pay twice), re-read per round so an adaptive k bills each
            # send at the size it actually shipped
            bps = self.bits_per_step()
            msg_bits = bps["uplink"] // m
            paid_bits = [0] * m   # exact per-worker bits paid this round
            for i in cohort:
                i = int(i)
                copies = 2 if sched.duplicated(t, i) else 1
                paid_bits[i] = msg_bits * copies
                for c in range(copies):
                    ledger.record(uplink=msg_bits, rounds=0, label="uplink")
                    if sched.dropped(t, i, copy=c):
                        continue
                    queue.push(t + sched.lag(t, i, copy=c), Message(
                        worker=i, send_round=t, version=t, copy=c,
                        payload=s_hat[i],
                        ef_row=(cand_state[i] if stateful_uplink else None),
                    ))

            arrivals = queue.pop_due(t)
            ages = [t - msg.send_round for msg in arrivals]
            # commit the channel/EF state rows carried by first arrivals:
            # the center's belief of each worker's compressor state only
            # advances when that worker's send actually lands
            uplink_state = state["uplink"]
            for msg in arrivals:
                if msg.version > committed_version[msg.worker]:
                    if stateful_uplink:
                        uplink_state = uplink_state.at[msg.worker].set(
                            msg.ef_row
                        )
                    committed_version[msg.worker] = msg.version
            state["uplink"] = uplink_state

            rejected_workers = []
            # per-worker forensic view of this round (schema v4): None
            # entries are workers whose send did not arrive this round
            worker_keep = [None] * m
            worker_staleness = [None] * m
            worker_norms = [None] * m
            if arrivals:
                stack = jnp.stack([msg.payload for msg in arrivals])
                agg, keep = self.staleness_agg(stack, ages)
                # the keep mask indexes the ARRIVAL stack; map rejects
                # back to worker ids for the round record
                rejected_workers = sorted({
                    arrivals[i].worker for i in rejected_from_keep(keep)
                })
                if tel.enabled:
                    arrival_norms = jnp.linalg.norm(
                        stack.reshape(stack.shape[0], -1), axis=-1
                    )
                    for idx, msg in enumerate(arrivals):
                        i, age = msg.worker, t - msg.send_round
                        k_i, n_i = float(keep[idx]), float(arrival_norms[idx])
                        # duplicates: keep the freshest / most-kept view
                        if worker_keep[i] is None or k_i > worker_keep[i]:
                            worker_keep[i] = k_i
                            worker_norms[i] = n_i
                        if (worker_staleness[i] is None
                                or age < worker_staleness[i]):
                            worker_staleness[i] = age
                v = self.config.momentum * v + agg
                with compile_scope("async.downlink"):
                    delta, state["downlink"] = self._down(
                        v, state["downlink"], sub
                    )
                w = w + delta
                ledger.record(downlink=bps["downlink"], rounds=1,
                              label="round")
            else:
                # an empty round still happened on the clock (and in the
                # ledger's round count) but broadcasts nothing
                ledger.record(rounds=1, label="round")

            hist["bits_cumulative"].append(ledger.total_bits)
            delta_hat = float(delta_hat)
            hist["uplink_delta"].append(delta_hat)
            hist["k_trajectory"].append(k_live)
            hist["cohort_size"].append(len(cohort))
            hist["n_arrivals"].append(len(arrivals))
            hist["queue_depth"].append(queue.depth)
            hist["staleness_mean"].append(
                sum(ages) / len(ages) if ages else None
            )
            loss, gn = self._pooled_eval(w, Xf, yf)
            hist["loss"].append(loss)
            hist["grad_norm"].append(gn)
            if eval_fn is not None:
                hist["eval"].append(float(eval_fn(w)))
            hit_tol = grad_tol is not None and gn <= grad_tol
            k_changed = False
            if not hit_tol:
                k_changed = self._maybe_adapt(gn, measured_delta=delta_hat)
            escaped = (saddle_value is not None
                       and hist["saddle_escape_step"] is None
                       and loss < saddle_value)
            if escaped:
                hist["saddle_escape_step"] = t
            if tel.enabled:
                cohort_set = {int(i) for i in cohort}
                wdelta = [
                    (float(worker_delta[i]) if i in cohort_set else None)
                    for i in range(m)
                ] if worker_delta is not None else None
                suspicion = tracker.update(keep=worker_keep,
                                           norms=worker_norms)
                tel.round(RoundRecord(
                    step=t, runtime=self.runtime_label, loss=loss,
                    grad_norm=gn,
                    model_decrease=(None if prev_loss is None
                                    else prev_loss - loss),
                    uplink_delta=delta_hat, k=k_live, k_changed=k_changed,
                    saddle_escape=escaped,
                    rejected=rejected_workers,
                    attack=self.attack.name, alpha=self.attack.alpha,
                    wire_uplink_bits=msg_bits * len(cohort),
                    wire_downlink_bits=(bps["downlink"] if arrivals else 0),
                    center_bytes=self.center_bytes_per_round(),
                    agg_kernel=self._agg_kernel_label(),
                    cohort_size=len(cohort), n_arrivals=len(arrivals),
                    queue_depth=queue.depth,
                    participation=acfg.participation,
                    arrival_staleness=ages,
                    worker_bits=paid_bits,
                    worker_delta=wdelta,
                    worker_keep=worker_keep,
                    worker_norms=worker_norms,
                    worker_staleness=worker_staleness,
                    suspicion=suspicion,
                    byzantine_true=(
                        planted_byzantine_ids(m, self._attack_rule.alpha)
                        if self._attack_rule.kind != "none" else None
                    ),
                ), name="newton.round")
                tel.observe("async.queue_depth", queue.depth)
                for age in ages:
                    tel.observe("async.staleness", age)
                prev_loss = loss
            if hit_tol:
                break
        hist.update(ledger.snapshot())
        return w, hist
