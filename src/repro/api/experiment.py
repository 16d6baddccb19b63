"""`ExperimentSpec` — the one declarative description every entry point
builds through.

A frozen, JSON-round-trippable record of a full experiment: problem +
runtime selector, solver hyper-parameters, the three channel compressor
specs, the aggregator spec, the attack spec, and the seed.  All fields
are plain JSON scalars, so

    ExperimentSpec.from_dict(spec.to_dict()) == spec      (exactly)

and a sweep is just a list of dicts.  ``validate()`` runs every
build-time check (β > α resilience precondition, spec-string grammar
against the three registries, EF-vs-compressor compatibility, the
top-k kernel's launch-plan/tile sanity) and raises
:class:`~repro.api.errors.SpecError` with an actionable message;
``build()`` validates and returns a ready :class:`Experiment` runner
covering both the paper-faithful and mesh runtimes.

Entry points that drive an external model through the mesh runtime
(``repro.launch.train`` / ``repro.launch.dryrun``) use ``problem =
"external"`` and take only the validated configs
(:meth:`to_distributed_config`), keeping all config construction inside
this module.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

from ..compression.registry import make_compressor
from .aggregators import default_aggregator_spec, make_aggregator
from .attacks import make_attack, to_attack_config
from .errors import SpecError
from .problems import fixed_workers, make_problem, problem_dim

_PAPER_SOLVER_ITERS = 500   # Algorithm 2 while-loop cap (paper runtime)
_MESH_SOLVER_ITERS = 4      # fixed inner iterations (static mesh program)

#: async-runtime axes and their degenerate-synchronous defaults.  At
#: these values the async runtime runs the synchronous program (bit-
#: exact), so ``to_dict`` omits default-valued axes — pre-async spec
#: dicts (and their sweep-store spec hashes) are unchanged byte for
#: byte, and every existing store entry stays addressable.
_ASYNC_AXIS_DEFAULTS = {
    "participation": 1.0,
    "staleness": 0,
    "drop": 0.0,
    "duplicate": 0.0,
    "staleness_decay": 0.5,
}

#: the default solver is likewise omitted from ``to_dict`` — every spec
#: dict (and sweep-store hash) minted before the solver axis existed
#: stays byte-identical, so pre-existing store entries remain addressable
_SOLVER_DEFAULT = "cubic_newton"


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """Declarative experiment description (all fields JSON scalars)."""

    # -- problem / runtime selector --------------------------------------
    problem: str = "synthetic-logistic:4000:40"
    runtime: str = "paper"          # "paper" | "mesh" | "async"
    m_workers: int = 20
    # -- solver (Algorithm 1 / 2) ----------------------------------------
    M: float = 10.0
    gamma: float = 1.0
    eta: float = 1.0
    solver_tol: float = 1e-6
    solver_iters: Optional[int] = None   # None → 500 (paper) / 4 (mesh)
    exact_gradient: bool = False         # Remark 5: two-round, ε_g = 0
    momentum: float = 0.0
    # -- the three wire segments (repro.compression spec strings) --------
    compressor: Optional[str] = None           # uplink: worker updates
    downlink_compressor: Optional[str] = None  # center→worker broadcast
    grad_compressor: Optional[str] = None      # Remark-5 gradient round
    error_feedback: Optional[str] = None       # None → auto (see below)
    ef_damping: float = 0.75
    # -- solver axis (repro.solvers spec string; see _SOLVER_DEFAULT) -----
    solver: str = "cubic_newton"    # | "byzantine_pgd[:R:Q]"
    #                                 | "compressed_sgd[:radius:gtol]"
    # -- resilience scenario ---------------------------------------------
    aggregator: str = "mean"        # repro.api.aggregators spec string
    attack: str = "none"            # repro.api.attacks spec string
    alpha: float = 0.0              # Byzantine fraction
    num_classes: int = 2
    seed: int = 0
    # -- async-runtime axes (runtime="async"; see _ASYNC_AXIS_DEFAULTS) --
    participation: float = 1.0      # per-round cohort fraction ∈ (0, 1]
    staleness: int = 0              # max rounds a packet lags (uniform)
    drop: float = 0.0               # P(packet never arrives) ∈ [0, 1]
    duplicate: float = 0.0          # P(packet delivered twice) ∈ [0, 1]
    staleness_decay: float = 0.5    # arrival weight decay**age ∈ (0, 1]

    # ------------------------------------------------------------ serde
    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # default-valued async axes (and the default solver) are omitted:
        # pre-existing spec dicts (and their sweep-store hashes) stay
        # byte-identical
        for key, default in _ASYNC_AXIS_DEFAULTS.items():
            if d[key] == default:
                del d[key]
        if d["solver"] == _SOLVER_DEFAULT:
            del d["solver"]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ExperimentSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise SpecError(
                f"unknown ExperimentSpec fields {sorted(unknown)}; "
                f"known fields: {sorted(known)}"
            )
        return cls(**d)

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    @classmethod
    def from_json(cls, s: str) -> "ExperimentSpec":
        return cls.from_dict(json.loads(s))

    def replace(self, **kw) -> "ExperimentSpec":
        return dataclasses.replace(self, **kw)

    # --------------------------------------------------------- resolution
    @property
    def any_compressor(self) -> bool:
        return any((self.compressor, self.downlink_compressor,
                    self.grad_compressor))

    def resolved_error_feedback(self) -> str:
        """``None`` means auto: EF21 on the compressed paper runtime (the
        NewtonConfig default that the round-count results rely on), off
        on the mesh runtime (stateful steps are opt-in at scale)."""
        if self.error_feedback is not None:
            return self.error_feedback
        if self.runtime in ("paper", "async") and self.any_compressor:
            return "ef21"
        return "none"

    def _beta(self) -> float:
        """β mirrored into the legacy config field (norm_trim only)."""
        agg = make_aggregator(self.aggregator)
        return getattr(agg, "beta", 0.0)

    # --------------------------------------------------------- validation
    def validate(self) -> "ExperimentSpec":
        if self.runtime not in ("paper", "mesh", "async"):
            raise SpecError(
                f"runtime must be 'paper', 'mesh', or 'async', "
                f"got {self.runtime!r}"
            )
        # async axes: range checks always, non-defaults only on async
        if not 0.0 < self.participation <= 1.0:
            raise SpecError(
                f"participation={self.participation!r}: the per-round "
                f"cohort fraction must lie in (0, 1]"
            )
        if not isinstance(self.staleness, int) or self.staleness < 0:
            raise SpecError(
                f"staleness={self.staleness!r}: the max packet lag must "
                f"be an int ≥ 0 (rounds)"
            )
        for field in ("drop", "duplicate"):
            if not 0.0 <= getattr(self, field) <= 1.0:
                raise SpecError(
                    f"{field}={getattr(self, field)!r}: packet-fault "
                    f"probabilities must lie in [0, 1]"
                )
        if not 0.0 < self.staleness_decay <= 1.0:
            raise SpecError(
                f"staleness_decay={self.staleness_decay!r}: the arrival "
                f"weight decay must lie in (0, 1]"
            )
        if self.runtime != "async":
            for field, default in _ASYNC_AXIS_DEFAULTS.items():
                if getattr(self, field) != default:
                    raise SpecError(
                        f"{field}={getattr(self, field)!r} is an async-"
                        f"runtime axis, but runtime={self.runtime!r} — "
                        f"set runtime='async' (or drop the override)"
                    )
        if self.runtime == "async" and self.exact_gradient:
            raise SpecError(
                "exact_gradient=True (the Remark-5 two-round mode) needs "
                "a per-round global barrier for the gradient round, which "
                "the async runtime removes — use runtime='paper' for the "
                "two-round experiments"
            )
        if self.m_workers < 2:
            raise SpecError(
                f"m_workers={self.m_workers}: need ≥ 2 workers for "
                f"aggregation to mean anything"
            )

        # solver axis (grammar first — pure, no registries); the
        # first-order baselines ship flat-vector gradient rounds, so they
        # run on the paper runtime only, and the Newton-only axes are
        # rejected rather than silently ignored
        from ..solvers import FIRST_ORDER_SOLVERS, parse_solver_spec

        solver_head, _ = parse_solver_spec(self.solver)
        if solver_head in FIRST_ORDER_SOLVERS:
            if self.runtime != "paper":
                raise SpecError(
                    f"solver {self.solver!r} is a first-order baseline "
                    f"over flat-vector gradient rounds — it runs on "
                    f"runtime='paper' only, got runtime={self.runtime!r}"
                )
            if self.exact_gradient:
                raise SpecError(
                    f"exact_gradient=True is the Newton Remark-5 two-"
                    f"round mode; solver {self.solver!r} already ships "
                    f"gradients every round — drop exact_gradient"
                )
            if solver_head == "byzantine_pgd" and self.momentum != 0.0:
                raise SpecError(
                    f"momentum={self.momentum!r}: ByzantinePGD [Yin et "
                    f"al. 2019] has no momentum term — use "
                    f"solver='compressed_sgd' for momentum-SGD, or drop "
                    f"the momentum override"
                )
        if not 0.0 <= self.alpha < 0.5:
            raise SpecError(
                f"alpha={self.alpha!r}: the Byzantine fraction must lie in "
                f"[0, 0.5) — no aggregator survives a corrupted majority"
            )
        for field in ("M", "gamma", "eta"):
            if getattr(self, field) <= 0:
                raise SpecError(f"{field} must be positive, "
                                f"got {getattr(self, field)!r}")
        if not 0.0 <= self.momentum < 1.0:
            raise SpecError(f"momentum must be in [0, 1), "
                            f"got {self.momentum!r}")

        # problem spec (grammar + dim for the kernel-tile check); problems
        # that pin the cluster size must agree with m_workers, or the
        # resilience checks below would run against the wrong m
        dim = None if self.problem == "external" else problem_dim(self.problem)
        fixed_m = (None if self.problem == "external"
                   else fixed_workers(self.problem))
        if fixed_m is not None and self.m_workers != fixed_m:
            raise SpecError(
                f"problem {self.problem!r} partitions over a fixed "
                f"m={fixed_m} machines, but the spec says "
                f"m_workers={self.m_workers} — set m_workers={fixed_m}, or "
                f"use a synthetic problem to vary the cluster size"
            )

        # aggregator spec + resilience precondition
        agg = make_aggregator(self.aggregator)
        atk = make_attack(self.attack, self.alpha,
                          num_classes=self.num_classes)
        if atk.kind == "label" and self.runtime == "mesh":
            raise SpecError(
                f"attack {self.attack!r} corrupts worker labels, but the "
                f"mesh runtime's batches carry no label channel — use an "
                f"update-level attack (gaussian/negative/saddle)"
            )
        if self.alpha > 0 and agg.name != "mean":
            # ("mean" under attack is the deliberate non-robust baseline
            # every comparison plots against, so it is exempt)
            reason = agg.check_resilience(self.alpha, self.m_workers)
            if reason is not None:
                raise SpecError(
                    f"aggregator {agg.spec!r} cannot resist the configured "
                    f"attack: {reason}"
                )

        # channel specs
        if self.grad_compressor is not None and not self.exact_gradient:
            raise SpecError(
                "grad_compressor compresses the Remark-5 gradient round, "
                "which only exists with exact_gradient=True — enable it or "
                "drop grad_compressor"
            )
        for field in ("compressor", "downlink_compressor", "grad_compressor"):
            spec = getattr(self, field)
            if spec is None:
                continue
            try:
                make_compressor(spec, dim or 1024)
            except ValueError as e:
                raise SpecError(f"{field}={spec!r}: {e}") from None
            if spec.partition(":")[0].endswith("_kernel"):
                # the kernel path serves any d (single-tile launch up to
                # 1408, the sharded grid beyond).  The remaining build-time
                # check guards the kernel module's CONFIGURED tiling, not
                # d: if DEFAULT_BLOCK ever drifts to something the TPU
                # cannot serve (non-128-lane multiple, VMEM-oversized
                # tiles), the spec fails here with an actionable message
                # instead of deep inside a trace at run time.
                from ..kernels import kernel_plan

                try:
                    kernel_plan(dim or 1024)
                except ValueError as e:
                    raise SpecError(f"{field}={spec!r}: {e}") from None

        # error feedback
        ef = self.resolved_error_feedback()
        if ef not in ("none", "ef", "ef21"):
            raise SpecError(
                f"error_feedback={self.error_feedback!r}: expected "
                f"'none', 'ef', or 'ef21'"
            )
        if ef != "none" and self.error_feedback is not None \
                and not self.any_compressor:
            raise SpecError(
                f"error_feedback={self.error_feedback!r} tracks a "
                f"compressor's residual, but all three channel compressors "
                f"are None — set compressor=... (e.g. 'topk:0.1') or drop "
                f"the error_feedback override"
            )

        # runtime/problem compatibility
        if self.runtime == "mesh" and self.problem != "external" \
                and not self.problem.startswith("quadratic"):
            raise SpecError(
                f"mesh-runtime builds need a pytree problem "
                f"('quadratic:<d>') or problem='external' (supply your own "
                f"loss through to_distributed_config), got {self.problem!r}"
            )
        if self.runtime in ("paper", "async") and (
                self.problem.startswith("quadratic")
                or self.problem == "external"):
            raise SpecError(
                f"problem {self.problem!r} is mesh-only; the "
                f"{self.runtime} runtime takes a flat-vector problem "
                f"from the catalog"
            )
        return self

    # --------------------------------------------------------- config gen
    def to_newton_config(self):
        """Validated spec → :class:`repro.core.NewtonConfig`."""
        self.validate()
        from ..core.newton import NewtonConfig  # runtime import: no cycle

        return NewtonConfig(
            M=self.M, gamma=self.gamma, eta=self.eta, beta=self._beta(),
            solver_tol=self.solver_tol,
            solver_iters=self.solver_iters or _PAPER_SOLVER_ITERS,
            exact_gradient=self.exact_gradient, momentum=self.momentum,
            compressor=self.compressor,
            downlink_compressor=self.downlink_compressor,
            grad_compressor=self.grad_compressor,
            error_feedback=self.resolved_error_feedback(),
            ef_damping=self.ef_damping,
            aggregator=self.aggregator,
        )

    def to_attack_config(self):
        """Validated spec → :class:`repro.core.AttackConfig`."""
        return to_attack_config(self.attack, self.alpha,
                                num_classes=self.num_classes)

    def to_distributed_config(self):
        """Validated spec → :class:`repro.core.DistributedNewtonConfig`."""
        self.validate()
        from ..core.distributed import DistributedNewtonConfig

        return DistributedNewtonConfig(
            M=self.M, gamma=self.gamma, eta=self.eta, beta=self._beta(),
            solver_iters=self.solver_iters or _MESH_SOLVER_ITERS,
            two_round=self.exact_gradient,
            compressor=self.compressor,
            downlink_compressor=self.downlink_compressor,
            error_feedback=self.resolved_error_feedback(),
            ef_damping=self.ef_damping,
            aggregator=self.aggregator,
        )

    # ------------------------------------------------------------- build
    def build(self) -> "Experiment":
        """Validate, materialize the problem, and wire up the runtime."""
        self.validate()
        return Experiment(self)


class Experiment:
    """A built, ready-to-run experiment (both runtimes, one interface).

    ``run(n_steps, grad_tol=...)`` returns ``(iterate, history)``; the
    history always carries ``loss`` plus the exact-int wire-ledger
    totals.  The resolved pieces stay inspectable: ``.problem`` (data),
    ``.algo`` (paper runtime's :class:`DistributedCubicNewton`), or
    ``.step``/``.config`` (mesh runtime).
    """

    def __init__(self, spec: ExperimentSpec):
        self.spec = spec
        self.problem = make_problem(spec.problem, spec.m_workers, spec.seed)
        if spec.solver != _SOLVER_DEFAULT:
            from ..solvers import make_solver

            # first-order solver on the paper runtime: same .algo duck
            # type (run / bits_per_step / _ensure_channels) as the
            # Newton runtimes, channels and registries included
            self.config = None
            self.algo = make_solver(spec, self.problem.loss_fn)
            self.step = None
        elif spec.runtime in ("paper", "async"):
            self.config = spec.to_newton_config()
            if spec.runtime == "async":
                from ..async_rt import AsyncConfig, AsyncCubicNewton

                self.algo = AsyncCubicNewton(
                    self.problem.loss_fn, self.config,
                    spec.to_attack_config(),
                    AsyncConfig(
                        participation=spec.participation,
                        staleness=spec.staleness,
                        drop=spec.drop, duplicate=spec.duplicate,
                        staleness_decay=spec.staleness_decay,
                        seed=spec.seed,
                    ),
                )
            else:
                from ..core.newton import DistributedCubicNewton

                self.algo = DistributedCubicNewton(
                    self.problem.loss_fn, self.config,
                    spec.to_attack_config(), eval_fn=self.problem.eval_fn,
                )
            self.step = None
        else:
            import jax

            from ..core.distributed import (
                make_stateful_train_step,
                make_train_step,
            )

            self.config = spec.to_distributed_config()
            self.algo = None
            self._stateful = self.config.error_feedback != "none"
            maker = (make_stateful_train_step if self._stateful
                     else make_train_step)
            built = maker(
                self.problem.loss_fn, self.config, spec.m_workers,
                attack_name=spec.attack, attack_alpha=spec.alpha,
            )
            if self._stateful:
                raw_step, self._init_comm_state = built
                self.step = jax.jit(raw_step, donate_argnums=(3,))
            else:
                raw_step, self._init_comm_state = built, None
                self.step = jax.jit(raw_step)
            self._raw_step = raw_step

    # -- running ---------------------------------------------------------
    def run(self, n_steps: int = 10, *, grad_tol: Optional[float] = None,
            eval_fn=None, key=None, deadline: Optional[float] = None):
        """Run the experiment; returns ``(iterate, history)``.

        ``deadline`` is a ``time.monotonic()`` timestamp: the run loop
        cooperatively stops at the first round boundary past it (the
        sweep runner's per-cell wall-time budget), recording
        ``history["truncated"] = True``.
        """
        if self.algo is not None:
            return self.algo.run(
                self.problem.w0, self.problem.X_workers,
                self.problem.y_workers, n_steps, key=key,
                eval_fn=eval_fn if eval_fn is not None
                else self.problem.eval_fn,
                grad_tol=grad_tol, deadline=deadline,
                saddle_value=self.problem.saddle_value,
            )
        return self._run_mesh(n_steps, key=key, deadline=deadline)

    def _run_mesh(self, n_steps: int, key=None,
                  deadline: Optional[float] = None):
        import time as _time

        import jax

        from ..comm import WireLedger
        from ..telemetry import (RoundRecord, SuspicionTracker,
                                 compile_scope, get_telemetry,
                                 planted_byzantine_ids, rejected_from_keep)

        params = self.problem.w0
        batch = self.problem.batch
        key = key if key is not None else jax.random.PRNGKey(self.spec.seed)
        ledger = WireLedger()
        wire = self._raw_step.wire_bits(params)
        state = (self._init_comm_state(params) if self._stateful else None)
        hist = {"loss": [], "bits_cumulative": [], "uplink_delta": [],
                "truncated": False}
        tel = get_telemetry()
        prev_loss = None
        m = self.spec.m_workers
        tracker = SuspicionTracker(m) if tel.enabled else None
        for t in range(n_steps):
            if deadline is not None and hist["loss"] \
                    and _time.monotonic() >= deadline:
                hist["truncated"] = True
                if tel.enabled:
                    tel.event("mesh.truncated", step=t)
                break
            key, sub = jax.random.split(key)
            # (re)compiles of the mesh step are attributed to this scope
            # by the telemetry compile-counter (host-side contextvar)
            with compile_scope("mesh.step"):
                if self._stateful:
                    params, metrics, state = self.step(params, batch, sub,
                                                       state)
                else:
                    params, metrics = self.step(params, batch, sub)
            ledger.record(uplink=wire["uplink"], downlink=wire["downlink"],
                          rounds=2 if self.config.two_round else 1,
                          label="round")
            loss = float(metrics["loss"])
            hist["loss"].append(loss)
            hist["uplink_delta"].append(float(metrics["uplink_delta"]))
            hist["bits_cumulative"].append(ledger.total_bits)
            if tel.enabled:
                # schema-v4 forensics from the metrics the mesh step
                # already surfaces host-side (no new traced outputs; the
                # tree-stacked wire has no per-worker δ̂ view, so
                # worker_delta stays absent on runtime="mesh")
                keep_l = [float(k) for k in metrics["kept"]]
                norms_l = [float(n) for n in metrics["update_norms"]]
                attacked = (self.spec.attack != "none"
                            and self.spec.alpha > 0)
                tel.round(RoundRecord(
                    step=t, runtime="mesh", loss=loss,
                    model_decrease=(None if prev_loss is None
                                    else prev_loss - loss),
                    uplink_delta=float(metrics["uplink_delta"]),
                    rejected=rejected_from_keep(metrics["kept"]),
                    attack=self.spec.attack, alpha=self.spec.alpha,
                    wire_uplink_bits=wire["uplink"],
                    wire_downlink_bits=wire["downlink"],
                    worker_bits=[wire["uplink"] // m] * m,
                    worker_keep=keep_l,
                    worker_norms=norms_l,
                    suspicion=tracker.update(keep=keep_l, norms=norms_l),
                    byzantine_true=(planted_byzantine_ids(
                        m, self.spec.alpha) if attacked else None),
                ), name="mesh.round")
                prev_loss = loss
        hist["rounds"] = ledger.rounds
        hist.update(ledger.snapshot())
        self._last_metrics = metrics
        return params, hist

    # -- introspection ---------------------------------------------------
    def bits_per_step(self) -> dict:
        if self.algo is not None:
            self.algo._ensure_channels(self.problem.dim,
                                       self.problem.m_workers)
            return self.algo.bits_per_step()
        return self._raw_step.wire_bits(self.problem.w0)
