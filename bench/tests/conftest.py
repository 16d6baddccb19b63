"""Shared pieces of the benchmark's CPU tests (``python -m pytest bench/tests``).

The tests load the harness by path, as ``bench/run.py`` does, and run the
paper cells at a tiny size on the CPU: a workload the size of a unit
test is added to the program's catalog for the test's duration only.
"""
import copy
import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

TINY_DATA = {"loss": "logistic", "n_train": 480, "n_test": 120, "dim": 12,
             "m_workers": 20, "seed": 3}


def _load_run():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_run"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def run():
    return _load_run()


@pytest.fixture
def tiny_cell(run, monkeypatch):
    """``tiny_cell(workload)``: the workload's traffic and limits on a
    problem of 480 x 12 rows (logistic or robust as its configuration)."""
    from repro.configs import PAPER_WORKLOADS, PaperWorkload

    def make(workload):
        cell = run.load_cell(workload)
        cell = copy.deepcopy(cell)
        data = dict(TINY_DATA, loss=cell.config["data"]["loss"])
        name = f"tiny-{data['loss']}"
        kind = "logistic" if data["loss"] == "logistic" else "robust_regression"
        monkeypatch.setitem(PAPER_WORKLOADS, name, PaperWorkload(
            name, kind, data["dim"], data["n_train"], data["n_test"],
            m_workers=data["m_workers"]))
        cell.config["data"] = data
        cell.config["spec"] = {"problem": name, "m_workers": data["m_workers"]}
        if cell.traffic["reference"].get("topk"):
            cell.traffic["reference"]["topk"] = max(1, round(0.1 * data["dim"]))
        return cell

    return make
