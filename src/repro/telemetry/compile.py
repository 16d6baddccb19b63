"""Compile-counter: every JAX re-trace / backend compile is an event.

The ROADMAP's recompile-hygiene item needs *numbers*: adaptive-k
re-traces the jitted step on every k move, and every sweep cell builds
its own jit — compile time, not step time, dominates big grids.  This
module hooks :mod:`jax.monitoring`'s duration events (the instrumented
seam around JAX's compilation cache):

* ``…/jaxpr_trace_duration``       — one per re-trace,
* ``…/backend_compile_duration``   — one per actual XLA compile
  (a compilation-cache hit traces but does not backend-compile).

Attribution: the monitoring callback carries no function identity, so
runtimes label their compile sites with :func:`compile_scope` — a
contextvar the listener reads while the (synchronous) compile runs.
``DistributedCubicNewton.step`` runs under ``compile_scope
("newton.step")``, the pooled loss-and-gradient-norm program that
``run()`` evaluates every round (compiled once per runtime) under
``"newton.pooled"``, the mesh facade
under ``"mesh.step"``, so
``counter.backend_compiles("newton.step")`` is exactly "how many times
did the paper runtime's step recompile" — the number the regression
pins assert.

One module-level listener dispatches to the active counters (JAX offers
no public unregister), registered lazily on first activation; with no
active counter it is a len()-check per *compile*, nothing per step.

Explicit re-trace triggers (an adaptive-k move rebuilding a jit) should
additionally call :func:`record_retrace` with their shape key, so the
event stream says *why* a re-trace happened, not just that it did.
"""
from __future__ import annotations

import contextvars
import threading
from contextlib import contextmanager
from typing import Optional

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_WATCHED = {TRACE_EVENT: "jaxpr_trace", BACKEND_EVENT: "backend_compile"}

_scope: contextvars.ContextVar = contextvars.ContextVar(
    "repro_compile_scope", default=None)

_active: list = []
_listener_installed = False
_install_lock = threading.Lock()


@contextmanager
def compile_scope(label: str):
    """Attribute any compile that happens inside this block to ``label``.

    Purely host-side (a contextvar set/reset): it never enters a trace
    and costs ~100ns per use, so runtimes wrap every step call."""
    token = _scope.set(label)
    try:
        yield
    finally:
        _scope.reset(token)


def current_scope() -> Optional[str]:
    return _scope.get()


def _listener(event: str, duration_s: float, **kw) -> None:
    if not _active or event not in _WATCHED:
        return
    label = _scope.get()
    short = _WATCHED[event]
    for counter in list(_active):
        counter._record(short, duration_s, label)


def _ensure_listener() -> None:
    global _listener_installed
    with _install_lock:
        if _listener_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_listener)
        _listener_installed = True


class CompileCounter:
    """Count (and optionally emit) compiles while active.

    Use as a context manager for scoped assertions::

        with CompileCounter() as cc:
            run_something()
        assert cc.backend_compiles("newton.step") == 3

    or give the global telemetry handle one (``emit_to=tel``) so every
    compile becomes a schema'd ``compile`` event with its duration and
    attributed scope.
    """

    def __init__(self, emit_to=None):
        self._emit_to = emit_to
        self._lock = threading.Lock()
        # {(event_short, scope_label_or_None): [count, total_seconds]}
        self._by_key: dict[tuple, list] = {}

    # -- lifecycle -------------------------------------------------------
    def activate(self) -> "CompileCounter":
        _ensure_listener()
        if self not in _active:
            _active.append(self)
        return self

    def deactivate(self) -> None:
        try:
            _active.remove(self)
        except ValueError:
            pass

    def __enter__(self) -> "CompileCounter":
        return self.activate()

    def __exit__(self, *exc) -> None:
        self.deactivate()

    # -- recording -------------------------------------------------------
    def _record(self, event_short: str, duration_s: float,
                label: Optional[str]) -> None:
        with self._lock:
            slot = self._by_key.setdefault((event_short, label), [0, 0.0])
            slot[0] += 1
            slot[1] += duration_s
        if self._emit_to is not None:
            self._emit_to.compile_event(event=event_short,
                                        dur_s=duration_s, scope=label)

    # -- queries ---------------------------------------------------------
    def _count(self, event_short: str, scope) -> int:
        with self._lock:
            if scope is _ANY:
                return sum(v[0] for (e, _), v in self._by_key.items()
                           if e == event_short)
            return self._by_key.get((event_short, scope), [0, 0.0])[0]

    def backend_compiles(self, scope=None) -> int:
        """XLA backend compiles attributed to ``scope`` (``None`` counts
        unattributed compiles; pass ``scope=ANY`` for the grand total)."""
        return self._count("backend_compile", scope)

    def retraces(self, scope=None) -> int:
        """Jaxpr traces attributed to ``scope`` (cache hits retrace
        without backend-compiling; see module doc)."""
        return self._count("jaxpr_trace", scope)

    def compile_seconds(self, scope=None) -> float:
        """Total backend-compile seconds attributed to ``scope``
        (``ANY`` for the scope-blind total)."""
        with self._lock:
            return sum(v[1] for (e, s), v in self._by_key.items()
                       if e == "backend_compile"
                       and (scope is _ANY or s == scope))

    def snapshot(self) -> dict:
        """``{scope: {"backend_compiles": n, "retraces": n,
        "compile_s": s}}`` over every scope seen (None key =
        unattributed)."""
        out: dict = {}
        with self._lock:
            for (event, scope), (n, secs) in self._by_key.items():
                slot = out.setdefault(scope, {"backend_compiles": 0,
                                              "retraces": 0,
                                              "compile_s": 0.0})
                if event == "backend_compile":
                    slot["backend_compiles"] += n
                    slot["compile_s"] += secs
                else:
                    slot["retraces"] += n
        return out


class _Any:
    def __repr__(self):
        return "ANY"


#: pass to ``backend_compiles``/``retraces`` for the scope-blind total
ANY = _ANY = _Any()


def record_retrace(trigger: str, **shape_key) -> None:
    """Announce an *explicit* re-trace trigger (e.g. an adaptive-k move
    rebuilding its jit) on the global telemetry stream, with the shape
    key that caused it.  No-op when telemetry is disabled."""
    from .core import get_telemetry

    tel = get_telemetry()
    if not tel.enabled:
        return
    tel.event("compile.retrace", trigger=trigger, **shape_key)
