"""A configuration, a traffic mix and a per-layer metric are added by new
files and entries in BENCHMARK.json, with no existing file edited."""
import hashlib
import json
import shutil

from conftest import BENCH, ROOT


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_files_are_found_by_name(run, tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)

    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "a9a-robust.json").read_text())
    cfg["name"] = "a9a-robust-copy"
    (bench / "configs" / "a9a-robust-copy.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "saddle-normtrim.json").read_text())
    mix.update(name="negative-normtrim", grad_tol=0.03,
               spec=dict(mix["spec"], attack="negative:0.9"),
               reference=dict(alpha=0.2, beta=0.3, attack="negative", c=0.9, topk=None))
    (bench / "traffic" / "negative-normtrim.json").write_text(json.dumps(mix))
    (bench / "layers" / "solves_per_s.py").write_text(
        'LAYER = "entry and facade"\nUNIT = "1/s"\nMOVES = "time_to_eps_s"\n\n\n'
        'def read(record):\n'
        '    win = record["window"]\n'
        '    return len(win["solves"]) / win["seconds"]\n')
    (bench / "limits" / "a9a-robust-copy.negative-normtrim.json").write_text(
        json.dumps({"readings": "test", "limits": {"iterate_gap": {"limit": 0.01}}}))

    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "a9a-robust-copy", "source": "test",
                            "file": "bench/configs/a9a-robust-copy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "a9a-robust-copy.negative-normtrim",
                              "config": "a9a-robust-copy",
                              "traffic": "negative-normtrim", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "solves_per_s", "unit": "1/s", "better": "higher",
                              "source": "host_clock", "layer": "entry and facade",
                              "moves": "time_to_eps_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = run.load_cell("a9a-robust-copy.negative-normtrim", root=tmp_path)
    assert cell.config["name"] == "a9a-robust-copy"
    assert cell.traffic["spec"]["attack"] == "negative:0.9"
    assert cell.limits == {"iterate_gap": {"limit": 0.01}}
    assert "solves_per_s" in [m["name"] for m in cell.per_layer]
    reader = run.load("layers/solves_per_s", bench=bench)
    record = {"window": {"solves": [1, 2, 3], "seconds": 1.5}}
    assert reader.read(record) == 2.0
    # the new metric joins every cell that reports what it moves
    old = run.load_cell("w8a-logistic.gauss-normtrim", root=tmp_path)
    assert "solves_per_s" in [m["name"] for m in old.per_layer]

    after = _digests(tmp_path)
    assert all(after[p] == d for p, d in before.items())


def test_readers_agree_with_benchmark_json(run):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        reader = run.load(f"layers/{m['name']}")
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (m["layer"], m["unit"], m["moves"])
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.limits, f"{w['name']} has no limits file"
        assert cell.config["chips"] == w["chips"]


def test_readers_return_nothing_without_a_trace(run):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert run.load(f"layers/{m['name']}").read({}) is None
