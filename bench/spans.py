#!/usr/bin/env python3
"""The program's own spans in a traced window, on the device trace's clock.

    python3 bench/spans.py --workload <name> --seed <n> [--seconds 3] [--keep FILE]

The paper runtime's run loop opens ``newton.*`` spans (``repro.telemetry``
spans, which enter a ``jax.profiler.TraceAnnotation`` whenever a profiler
session records, telemetry on or off):

* ``newton.solve``: one ``run()`` call;
* ``newton.round`` (attribute ``step``): one round, with three children:
  ``newton.round.step`` (dispatch of the jitted round),
  ``newton.round.wait`` (the host blocked on the round's one pull) and
  ``newton.round.pooled`` (the pooled gradient norm and loss, their
  per-call compiles and pulls).  The round's self time is the host
  loop's own work.

They lie on the host plane of the ``.xplane.pb``, on the clock of the
device's operations.  :func:`reduce_spans` reduces them to each span's
time, count and self time, the device's idle time inside each, and the
time of the ``while`` operations (Algorithm 2's loop) inside each
module's executions; :func:`layer_numbers` turns that and the rounds'
Algorithm 2 iterations (``hist["cubic_iters"]``) into five per-layer
numbers.

Run as a script, it sets a cell up as a ``--trace 1`` benchmark run does,
records the first ``TRACE_SECONDS`` of a window of ``--seconds``, and
prints one JSON line: the span table, the five numbers, the share of the
traced window the solves cover, and the traced solves' compile seconds
by ``compile_scope``.  The benchmark's own runs do not call this.
"""
import argparse
import importlib.util
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
PROGRAM_SPANS = ("newton.",)    # prefixes of the program's own span names
SOLVE, ROUND = "newton.solve", "newton.round"
STEP, POOLED = "newton.round.step", "newton.round.pooled"


def _load_trace():
    """``bench/trace.py``, loaded by path as ``bench/run.py`` loads it."""
    path, name = BENCH / "trace.py", "bench_trace"
    if name in sys.modules and Path(sys.modules[name].__file__) == path:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


trace = _load_trace()


def merge(intervals):
    """``(start, end)`` intervals as sorted disjoint ones covering the same."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def intersection_length(a, b) -> int:
    """Length covered by both sets of intervals (each set's overlaps once)."""
    a, b = merge(a), merge(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_name(name: str) -> str:
    """A span's name without a ``#key=value#`` suffix of its attributes
    (``ProfileData`` moves them into the event's stats already)."""
    return name.split("#", 1)[0]


def span_table(events) -> dict:
    """``{name: [seconds, count, self seconds]}`` of spans ``(start, end,
    name)`` recorded on one thread, where spans nest.  A span's self time
    is its length less that of its direct children."""
    table, open_ = {}, []          # open_: [start, end, name, child ns]

    def close(start, end, name, child):
        slot = table.setdefault(name, [0.0, 0, 0.0])
        slot[0] += (end - start) * 1e-9
        slot[1] += 1
        slot[2] += (end - start - child) * 1e-9

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while open_ and open_[-1][1] <= s:
            close(*open_.pop())
        if open_:
            open_[-1][3] += min(e, open_[-1][1]) - s
        open_.append([s, e, name, 0])
    while open_:
        close(*open_.pop())
    return table


def op_code(hlo_text: str) -> str:
    """``%while.28 = (s32[], f32[8]{0}) while(...), body=...`` → ``while``:
    the HLO opcode, which follows the result shape."""
    rest, depth = hlo_text.split(" = ", 1)[-1], 0
    for i, c in enumerate(rest):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0]
    return ""


def while_in_modules(loops, runs) -> dict:
    """``{module: ns}``: the time of the ``while`` operation intervals
    ``loops`` inside each module's execution intervals ``runs``
    (``{module: [(start, end), ...]}``)."""
    return {name: intersection_length(loops, iv) for name, iv in runs.items()}


def reduce_spans(path: str, window: str = trace.WINDOW) -> dict:
    """The program's spans in one trace file, inside the ``window``
    annotation: ``window_s``; ``idle_s``, the first chip's idle seconds;
    ``spans``, ``{span: [seconds, count, self seconds]}``;
    ``idle_in_spans``, ``{span: the first chip's idle seconds inside
    it}``; ``while_s``, ``{module: seconds of its while operations}``,
    averaged over chips.  A program without spans gives empty tables."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    program, lo, hi = {}, None, None
    for plane in pd.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == window:
                    lo, hi = s, e
                elif ev.name.startswith(PROGRAM_SPANS):
                    program.setdefault(line.name, []).append((s, e, span_name(ev.name)))
    if lo is None:
        raise RuntimeError(f"{path}: no {window!r} annotation on {trace.HOST_PLANE}")

    chips, gap_list, while_ns = 0, [], {}
    for plane in pd.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        chips += 1
        ops, loops, runs = [], [], {}
        for line in plane.lines:
            if line.name not in (trace.OPS_LINE, trace.MODULES_LINE):
                continue
            for ev in line.events:
                s, e = trace._clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if e <= s:
                    continue
                if line.name == trace.MODULES_LINE:
                    runs.setdefault(ev.name.split("(", 1)[0], []).append((s, e))
                else:
                    ops.append((s, e))
                    if op_code(ev.name) == "while":
                        loops.append((s, e))
        for name, ns in while_in_modules(loops, runs).items():
            while_ns[name] = while_ns.get(name, 0) + ns
        if chips == 1:
            gap_list = trace.gaps(ops, lo, hi)
    if not chips:
        raise RuntimeError(f"{path}: no /device:TPU plane — not a chip trace")

    spans, idle_in = {}, {}
    for events in program.values():
        clipped = [(*trace._clip(s, e, lo, hi), n) for s, e, n in events]
        clipped = [(s, e, n) for s, e, n in clipped if e > s]
        for name, row in span_table(clipped).items():
            spans[name] = [a + b for a, b in zip(spans.get(name, [0.0, 0, 0.0]), row)]
        for name in {n for _, _, n in clipped}:
            iv = [(s, e) for s, e, n in clipped if n == name]
            idle_in[name] = idle_in.get(name, 0.0) + intersection_length(gap_list, iv) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": sum(e - s for s, e in gap_list) * 1e-9,
        "spans": spans,
        "idle_in_spans": idle_in,
        "while_s": {k: v * 1e-9 / chips for k, v in while_ns.items() if v > 0},
    }


def layer_numbers(red: dict, step_module: str, cubic_iters) -> dict:
    """The five per-layer numbers from a reduction, the round's module name
    and the traced rounds' Algorithm 2 iterations (one int a round, or
    ``None`` where the program does not report them).  A number whose
    spans or counter are missing is left out.

    * ``pooled_ms_per_round``: ``newton.round.pooled`` time over its count;
    * ``round_host_ms``: ``newton.round`` self time plus
      ``newton.round.step``, over the rounds;
    * ``idle_in_pooled_share``: the device's idle time inside
      ``newton.round.pooled`` over all of its idle time, in percent;
    * ``cubic_iters_per_round``: iterations over rounds;
    * ``cubic_iter_device_us``: ``while`` time inside the round's module
      over the iterations.
    """
    spans, out = red["spans"], {}
    if POOLED in spans:
        out["pooled_ms_per_round"] = 1e3 * spans[POOLED][0] / spans[POOLED][1]
        if red["idle_s"] > 0:
            out["idle_in_pooled_share"] = 100.0 * red["idle_in_spans"][POOLED] / red["idle_s"]
    if ROUND in spans:
        host = spans[ROUND][2] + spans.get(STEP, [0.0])[0]
        out["round_host_ms"] = 1e3 * host / spans[ROUND][1]
    if cubic_iters:
        out["cubic_iters_per_round"] = sum(cubic_iters) / len(cubic_iters)
        if step_module in red["while_s"]:
            out["cubic_iter_device_us"] = 1e6 * red["while_s"][step_module] / sum(cubic_iters)
    return out


class Recording:
    """An experiment whose ``run`` keeps each solve's history and the
    compile seconds it took, by ``compile_scope``."""

    def __init__(self, exp):
        self.exp, self.solves = exp, []

    def run(self, **kw):
        from repro.telemetry import CompileCounter

        with CompileCounter() as cc:
            w, hist = self.exp.run(**kw)
        compile_s = {str(scope): v["compile_s"] for scope, v in cc.snapshot().items()
                     if v["compile_s"]}
        self.solves.append((hist, compile_s))
        return w, hist


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--keep", help="copy the trace file here")
    args = ap.parse_args(argv)
    from run import ROOT, load, load_cell, start_jax

    sys.path.insert(0, str(ROOT / "src"))
    jax = start_jax()
    cell = load_cell(args.workload)
    rt = load(f"runtimes/{cell.config['runtime']}")
    exp = rt.build(cell)
    rt.compile_round(exp)
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        with rt.cache_writes_off():
            rt.warm_up(exp, cell, args.seed)
            module = rt.step_module_name(exp)
            rec = Recording(exp)
            rt.window(rec, cell, args.seed, args.seconds, trace_dir)
        path = trace.find_trace(trace_dir)
        if args.keep:
            shutil.copy(path, args.keep)
        red = reduce_spans(path)
        base = trace.reduce_trace(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    n = red["spans"].get(SOLVE, [0.0, 0])[1]
    traced = rec.solves[:n]
    iters = [i for hist, _ in traced for i in hist.get("cubic_iters", [])]
    compile_s = {}
    for _, by_scope in traced:
        for scope, secs in by_scope.items():
            compile_s[scope] = compile_s.get(scope, 0.0) + secs
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "device": jax.devices()[0].device_kind,
        "step_module": module, "traced_solves": n,
        "traced_rounds": sum(len(h["loss"]) for h, _ in traced),
        "numbers": layer_numbers(red, module, iters),
        "solve_cover": red["spans"].get(SOLVE, [0.0])[0] / red["window_s"],
        "compile_s": compile_s,
        "busy_s": base["busy_s"], **red,
        "idle_gaps": base["idle_gaps"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
