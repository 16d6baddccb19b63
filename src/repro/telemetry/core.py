"""The process-global :class:`Telemetry` handle.

Design contract (the HLO-identity test pins it):

* **Disabled is the default and costs nothing.**  Every emit method
  checks one boolean and returns; ``span()`` hands back a shared no-op
  context manager (a bare profiler annotation while a JAX profiler
  session records, so spans reach its trace); :func:`device_event`
  stages *nothing* into a trace — the lowered HLO with telemetry
  disabled is bit-identical to a build without the telemetry
  integration at all.
* **Instrumentation is host-side.**  Both runtimes already surface
  every per-round quantity as concrete metrics on the host, so round
  records, wire events, spans, and compile events are plain Python on
  the driver loop.  :func:`device_event` — a ``jax.debug.callback``
  staged only when telemetry is enabled *at trace time* — exists for
  the few values that genuinely live on the device (it changes the
  lowered program, which is exactly why it is opt-in per trace).
* **Two sinks**: a schema-versioned append-only JSONL event stream and
  a Chrome-trace/Perfetto ``trace.json`` (see :mod:`.sinks`).  Both are
  optional — ``enable()`` with no directory keeps metrics in memory
  (the serving path's latency histograms without file I/O).

Enable explicitly (``get_telemetry().enable(out_dir=…)``), per driver
flag (``--telemetry-dir``), or for unmodified entry points via the
environment: ``REPRO_TELEMETRY_DIR=results/telemetry`` enables the
global handle at first use.
"""
from __future__ import annotations

import atexit
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

from .records import RoundRecord
from .schema import SCHEMA_VERSION
from .sinks import ChromeTraceSink, JsonlSink

ENV_DIR = "REPRO_TELEMETRY_DIR"


class _NoopSpan:
    """Shared do-nothing context manager — the disabled ``span()``."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()

_annotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported at the first span: the
    package itself imports no JAX, and no profiler session can be active
    before JAX is."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


def _percentile(sorted_vals, q: float):
    """Nearest-rank percentile on a pre-sorted list (q in [0, 100])."""
    if not sorted_vals:
        return None
    idx = max(0, min(len(sorted_vals) - 1,
                     int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class Telemetry:
    """Counters, gauges, histograms, spans, and structured events.

    One instance is the process-global handle (:func:`get_telemetry`);
    fresh instances are cheap and used by tests.  All state is
    host-side; nothing here is ever traced.
    """

    def __init__(self):
        self._enabled = False
        self._lock = threading.Lock()
        self._local = threading.local()
        self._t0 = 0.0
        self._jsonl: Optional[JsonlSink] = None
        self._trace: Optional[ChromeTraceSink] = None
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, list] = {}
        self._compile_counter = None
        self.out_dir: Optional[str] = None

    # ------------------------------------------------------------ state
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, out_dir: Optional[str] = None, *,
               jsonl: bool = True, trace: bool = True) -> "Telemetry":
        """Turn the handle on.  With ``out_dir``, attach the JSONL sink
        (``events.jsonl``, append-only) and the Chrome-trace sink
        (``trace.json``, rewritten on flush); without it, metrics
        aggregate in memory only.  Idempotent; returns self."""
        with self._lock:
            if out_dir is not None:
                self.out_dir = out_dir
                if jsonl and self._jsonl is None:
                    self._jsonl = JsonlSink(os.path.join(out_dir,
                                                         "events.jsonl"))
                if trace and self._trace is None:
                    self._trace = ChromeTraceSink(os.path.join(out_dir,
                                                               "trace.json"))
            if not self._enabled:
                self._t0 = time.perf_counter()
                self._enabled = True
                atexit.register(self.flush)
        self._attach_compile_counter()
        return self

    def disable(self) -> None:
        """Flush and turn the handle off (sinks are kept for re-enable)."""
        self.flush()
        self._detach_compile_counter()
        self._enabled = False

    def _attach_compile_counter(self):
        from .compile import CompileCounter

        if self._compile_counter is None:
            self._compile_counter = CompileCounter(emit_to=self)
            self._compile_counter.activate()

    def _detach_compile_counter(self):
        if self._compile_counter is not None:
            self._compile_counter.deactivate()
            self._compile_counter = None

    # ------------------------------------------------------------- time
    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _base(self, kind: str, name: str) -> dict:
        return {"v": SCHEMA_VERSION, "kind": kind, "name": name,
                "ts": round(self._now(), 6), "wall": round(time.time(), 6)}

    def _emit(self, event: dict) -> None:
        if self._jsonl is not None:
            self._jsonl.emit(event)

    # ------------------------------------------------------------ emits
    def event(self, name: str, **fields) -> None:
        """A free-form instant event (both sinks)."""
        if not self._enabled:
            return
        ev = self._base("event", name)
        ev.update(fields)
        self._emit(ev)
        if self._trace is not None:
            self._trace.instant(name, ev["ts"], fields or None)

    def count(self, name: str, n=1, **fields) -> None:
        """Increment a counter; the event carries the running total."""
        if not self._enabled:
            return
        with self._lock:
            total = self._counters.get(name, 0) + n
            self._counters[name] = total
        ev = self._base("counter", name)
        ev["value"] = total
        ev.update(fields)
        self._emit(ev)
        if self._trace is not None:
            self._trace.counter(name, ev["ts"], total)

    def gauge(self, name: str, value, **fields) -> None:
        """Set a gauge to its latest value."""
        if not self._enabled:
            return
        value = float(value)
        with self._lock:
            self._gauges[name] = value
        ev = self._base("gauge", name)
        ev["value"] = value
        ev.update(fields)
        self._emit(ev)
        if self._trace is not None:
            self._trace.counter(name, ev["ts"], value)

    def observe(self, name: str, value, **fields) -> None:
        """One histogram observation (p50/p99 via :meth:`histogram`)."""
        if not self._enabled:
            return
        value = float(value)
        with self._lock:
            self._hists.setdefault(name, []).append(value)
        ev = self._base("hist", name)
        ev["value"] = value
        ev.update(fields)
        self._emit(ev)

    def round(self, record: RoundRecord, name: str = "round") -> None:
        """Emit one :class:`RoundRecord` (kind ``round``)."""
        if not self._enabled:
            return
        ev = self._base("round", name)
        ev.update(record.to_fields())
        self._emit(ev)
        if self._trace is not None:
            self._trace.instant(
                f"{name}.saddle_escape" if record.saddle_escape else name,
                ev["ts"],
                {"step": ev["step"], "loss": ev.get("loss"),
                 "grad_norm": ev.get("grad_norm")},
            )

    def wire(self, *, ledger_id: int, uplink: int, downlink: int,
             rounds: int, label: Optional[str] = None,
             seq: Optional[int] = None, pid: Optional[int] = None) -> None:
        """One ledger-record call: exact integer bits on the wire.
        ``seq`` is the ledger's per-generation sequence id and ``pid``
        the emitting process — together they make validation
        order-insensitive across async channels and pool workers (v1/v2
        streams without them still validate sum-only)."""
        if not self._enabled:
            return
        ev = self._base("wire", "wire")
        ev.update(ledger_id=int(ledger_id), uplink=int(uplink),
                  downlink=int(downlink), rounds=int(rounds))
        if label:
            ev["label"] = label
        if seq is not None:
            ev["seq"] = int(seq)
        if pid is not None:
            ev["pid"] = int(pid)
        self._emit(ev)

    def ledger_snapshot(self, *, ledger_id: int, snapshot: dict,
                        n_records: Optional[int] = None,
                        pid: Optional[int] = None) -> None:
        """End-of-run ledger totals (must equal the sum of this
        ledger generation's wire events — the validator checks).
        ``n_records`` (the generation's record count) lets the
        validator assert seq completeness; ``pid`` disambiguates
        colliding per-process ledger_ids."""
        if not self._enabled:
            return
        ev = self._base("ledger", "ledger")
        ev["ledger_id"] = int(ledger_id)
        if n_records is not None:
            ev["n_records"] = int(n_records)
        if pid is not None:
            ev["pid"] = int(pid)
        ev.update({k: int(v) for k, v in snapshot.items()})
        self._emit(ev)

    def compile_event(self, *, event: str, dur_s: float,
                      scope: Optional[str] = None, **fields) -> None:
        """One JAX compilation-cache event (from the compile counter)."""
        if not self._enabled:
            return
        ev = self._base("compile", "compile")
        ev.update(event=event, dur_s=float(dur_s))
        if scope is not None:
            ev["scope"] = scope
        ev.update(fields)
        self._emit(ev)
        if self._trace is not None:
            now = ev["ts"]
            self._trace.span(f"compile.{event}", max(0.0, now - dur_s),
                             dur_s, {"scope": scope} if scope else None)

    # ------------------------------------------------------------ spans
    @contextmanager
    def _span_cm(self, name: str, attrs: dict):
        t0 = self._now()
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        parent = stack[-1] if stack else None
        stack.append(name)
        status = "ok"
        try:
            with _trace_annotation()(name, **attrs):
                yield self
        except BaseException:
            status = "error"
            raise
        finally:
            stack.pop()
            dur = self._now() - t0
            ev = self._base("span", name)
            ev["ts"] = round(t0, 6)
            ev["dur_s"] = round(dur, 6)
            if parent is not None:
                ev["parent"] = parent
            if attrs or status != "ok":
                ev["args"] = {**{k: str(v) for k, v in attrs.items()},
                              **({"status": status}
                                 if status != "ok" else {})}
            self._emit(ev)
            if self._trace is not None:
                self._trace.span(name, t0, dur, ev.get("args"))

    def span(self, name: str, **attrs):
        """``with tel.span("sweep.cell", hash=h): …`` — a timed scope
        emitted to both sinks, its JSONL event naming the enclosing span
        (``parent``).  Whenever a JAX profiler session is active the span
        is also a ``jax.profiler.TraceAnnotation``, so it lands in the
        profiler's trace on the device ops' clock — with telemetry
        disabled too, as a bare annotation.  Disabled with no profiler
        session: the shared no-op, free."""
        if self._enabled:
            return self._span_cm(name, attrs)
        annotation = _trace_annotation()
        if annotation.is_enabled():
            return annotation(name, **attrs)
        return _NOOP_SPAN

    def current_span(self) -> Optional[str]:
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    # ---------------------------------------------------------- queries
    def counter_value(self, name: str):
        return self._counters.get(name)

    def gauge_value(self, name: str):
        return self._gauges.get(name)

    def histogram(self, name: str) -> Optional[dict]:
        """Summary of one histogram: count/min/max/mean/p50/p90/p99."""
        vals = sorted(self._hists.get(name, ()))
        if not vals:
            return None
        return {"count": len(vals), "min": vals[0], "max": vals[-1],
                "mean": sum(vals) / len(vals),
                "p50": _percentile(vals, 50), "p90": _percentile(vals, 90),
                "p99": _percentile(vals, 99)}

    def snapshot(self) -> dict:
        """All in-memory metrics as one plain dict."""
        with self._lock:
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "histograms": {k: self.histogram(k)
                                   for k in self._hists}}

    def flush(self) -> None:
        if self._trace is not None:
            self._trace.flush()
        if self._jsonl is not None:
            self._jsonl.flush()


# ----------------------------------------------------------- the global
_GLOBAL: Optional[Telemetry] = None
_GLOBAL_LOCK = threading.Lock()


def get_telemetry() -> Telemetry:
    """The process-global handle (created on first use; auto-enabled
    when ``REPRO_TELEMETRY_DIR`` is set, so unmodified entry points —
    the quickstart example, pytest runs — can opt in from the shell)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                tel = Telemetry()
                env_dir = os.environ.get(ENV_DIR)
                if env_dir:
                    tel.enable(env_dir)
                _GLOBAL = tel
    return _GLOBAL


def device_event(name: str, tel: Optional[Telemetry] = None, **arrays):
    """Stage a host callback that emits device values as an event.

    Call **at trace time** inside jitted code.  When telemetry is
    disabled this is a hard no-op — nothing is staged, the lowered HLO
    is bit-identical to code without the call (the HLO-identity test
    pins this).  When enabled, a ``jax.debug.callback`` ships the named
    arrays to the host and emits one ``event`` with their values —
    use it only for values that are not already surfaced as metrics.
    """
    tel = tel if tel is not None else get_telemetry()
    if not tel.enabled:
        return

    import jax
    import numpy as np

    names = tuple(arrays)

    def _cb(*vals):
        fields = {}
        for n, v in zip(names, vals):
            a = np.asarray(v)
            fields[n] = a.item() if a.ndim == 0 else a.tolist()
        tel.event(name, **fields)

    jax.debug.callback(_cb, *arrays.values())
