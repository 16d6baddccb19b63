#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``).  The configuration names
its runtime, whose module (``bench/runtimes/<runtime>.py``) builds the
program, warms it up, measures for ``--seconds`` and compares what the
window produced with the plain reference (``bench/reference/``) under the
cell's limits (``bench/limits/<cell>.json``).  With ``--trace 1`` the
window is profiled and each per-layer metric is read by its own reader
(``bench/layers/<metric>.py``).  Everything is found by name: a new cell,
mix or metric is new files and an entry in ``BENCHMARK.json``.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` when
traced) and, last, ``compared``: each number compared with its limit.
The run exits non-zero and prints no result when JAX finds no TPU or
fewer chips than the cell asks for, or when the program (``src/``) is
not beside ``bench/``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# bench/ holds a module named like a standard one (trace.py): keep the
# script's own directory off the import path and load bench files by path
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)


class BenchError(RuntimeError):
    pass


def load(rel: str, bench: Path = BENCH):
    """The bench module at ``bench/<rel>.py``."""
    path = bench / f"{rel}.py"
    if not path.is_file():
        raise BenchError(f"{path} is missing")
    name = "bench_" + rel.replace("/", "_").replace(".", "_").replace("-", "_")
    if name in sys.modules and Path(sys.modules[name].__file__) == path:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a workload of ``BENCHMARK.json`` to its files by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise BenchError(f"no workload {name!r}; known: {sorted(by_name)}")
    wl = by_name[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)]
    limits_path = root / "bench" / "limits" / f"{name}.json"
    limits = json.loads(limits_path.read_text())["limits"] if limits_path.is_file() else {}
    return Cell(name, wl["chips"], config, traffic, e2e, per_layer, limits)


def start_jax():
    """JAX with the program's compile cache (``repro.launch.cache``), at
    JAX's default threshold: a program is written to the cache only when
    it took a second or more to compile, as in every entry point of the
    program."""
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    import jax

    return jax


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devs[0].platform!r}); "
                         f"this benchmark runs on the chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def judge(cell: Cell, compared: dict):
    """``(correct, {name: {"value", "limit"}})`` for the limited numbers."""
    checks = {k: {"value": compared.get(k), "limit": v["limit"]}
              for k, v in cell.limits.items()}
    correct = bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def result_line(cell: Cell, record: dict, device: dict, trace: bool,
                bench: Path = BENCH) -> dict:
    """The JSON result from a runtime module's record."""
    device = dict(device, memory_peak_bytes=record.get("memory_peak_bytes"))
    metrics = {}
    if trace:
        tr = record["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        peaks = json.loads((bench / "peaks.json").read_text())["devices"]
        if device["kind"] not in peaks:
            raise BenchError(f"no peaks for device kind {device['kind']!r} in peaks.json")
        record = dict(record, peak=peaks[device["kind"]], chips=cell.chips)
        for m in cell.per_layer:
            value = load(f"layers/{m['name']}", bench).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in record["metrics"]:
                metrics[m["name"]] = {"value": record["metrics"][m["name"]],
                                      "unit": m["unit"]}
    correct, checks = judge(cell, record["compared"])
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": record["trace"]["ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    out["compared"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not beside bench/ ({src / 'repro'} is missing)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        cell = load_cell(args.workload)
        jax = start_jax()
        device = device_info(jax, cell.chips)
        runtime = load(f"runtimes/{cell.config['runtime']}")
        record = runtime.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                                  T_START, load)
        out = result_line(cell, record, device, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    phases = record.get("setup_phases", {})
    print("set-up, seconds from process start: "
          + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()), file=sys.stderr)
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
