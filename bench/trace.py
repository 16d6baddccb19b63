"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

A TPU trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` holds every operation the chip ran and whose line
``XLA Modules`` holds every program execution (named ``<module>(<id>)``).
Host threads are lines of the plane ``/host:CPU``; the harness marks the
traced window there with the annotation ``WINDOW``.

Busy time is the union of the operation intervals inside the window; the
idle gaps between them are named by what the host was doing meanwhile:
the host event that overlaps the gap longest.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_trace(log_dir: str) -> str:
    """The one ``.xplane.pb`` file the profiler wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def union_length(intervals) -> int:
    """Total length covered by ``(start, end)`` intervals (overlaps once)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: int, hi: int):
    """The uncovered stretches of ``[lo, hi]`` as ``(start, end)``."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, hi)))
        cursor = max(cursor, e)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(s, e) for s, e in out if e > s]


def _name_gaps(gap_list, host_events, min_ns=50_000):
    """``{host activity: seconds}`` over the idle gaps.  Each gap of at
    least ``min_ns`` is named by the host event that overlaps it longest;
    the shorter ones are summed under one name."""
    import numpy as np

    host = [(s, e, n) for s, e, n in host_events if not n.startswith("bench.")]
    hs = np.array([h[0] for h in host], dtype=np.int64)
    he = np.array([h[1] for h in host], dtype=np.int64)
    named = {}
    for s, e in gap_list:
        if e - s < min_ns:
            name = f"gaps under {min_ns // 1000} us"
        elif not host:
            name = "no host event"
        else:
            overlap = np.minimum(e, he) - np.maximum(s, hs)
            j = int(np.argmax(overlap))
            name = host[j][2] if overlap[j] > 0 else "no host event"
        named[name] = named.get(name, 0.0) + (e - s) * 1e-9
    return named


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` → ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def reduce_trace(path: str, top: int = 10, window: str = WINDOW) -> dict:
    """Everything the readers need from one trace file.

    Returns ``window_s`` (the annotated window), ``busy_s`` (union of the
    operation intervals in it, averaged over chips), ``chips``,
    ``modules`` (``{module name: [seconds, executions]}``), ``ops``
    (``{op name: seconds}``, the top ones) and ``idle_gaps``
    (``[[host activity, seconds], ...]``, the longest first).
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host_events = []
    lo = hi = None
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == window:
                    lo, hi = s, e
                else:
                    host_events.append((s, e, ev.name))
    if lo is None:
        raise RuntimeError(f"{path}: no {window!r} annotation on {HOST_PLANE}")

    busy, modules, ops, chips, gap_list = [], {}, {}, 0, []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        chips += 1
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if e <= s:
                    continue
                if line.name == OPS_LINE:
                    intervals.append((s, e))
                    name = op_name(ev.name)
                    ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
                else:
                    name = ev.name.split("(", 1)[0]
                    slot = modules.setdefault(name, [0.0, 0])
                    slot[0] += (e - s) * 1e-9
                    slot[1] += 1
        busy.append(union_length(intervals) * 1e-9)
        if chips == 1:
            gap_list = gaps(intervals, lo, hi)
    if not chips:
        raise RuntimeError(f"{path}: no /device:TPU plane — not a chip trace")

    named = _name_gaps(gap_list, host_events)
    idle = sorted(([k, v] for k, v in named.items()), key=lambda kv: -kv[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / chips,
        "chips": chips,
        "modules": modules,
        "ops": [[k, v] for k, v in top_ops],
        "idle_gaps": idle[:top],
    }
