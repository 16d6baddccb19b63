"""The plain reference follows the program round for round at a tiny size."""
import jax
import numpy as np
import pytest

from conftest import BENCH

CELLS = ["w8a-logistic.gauss-normtrim", "a9a-robust.saddle-normtrim"]


@pytest.mark.parametrize("workload", CELLS)
def test_reference_matches_experiment_run(run, tiny_cell, workload):
    cell = tiny_cell(workload)
    rt = run.load("runtimes/paper")
    ref_mod = run.load("reference/paper")
    exp = rt.build(cell)
    key = rt.solve_key(2**31 + 5, 0)
    w, hist = exp.run(n_steps=5, key=key)
    ref = rt.reference_for(cell, ref_mod)
    with jax.default_matmul_precision("highest"):
        out = ref.solve(key, rounds=5)
    wp, wr = np.asarray(w), np.asarray(out["w"])
    assert np.linalg.norm(wp - wr) <= 1e-4 * np.linalg.norm(wr)
    np.testing.assert_allclose(hist["grad_norm"], out["grad_norm"], rtol=1e-4)
    np.testing.assert_allclose(hist["loss"], out["loss"], rtol=1e-5)
    bpr = ref_mod.bits_per_round(ref.m, ref.d, cell.traffic["reference"].get("topk"))
    assert hist["total_bits"] == 5 * bpr
    assert exp.bits_per_step()["uplink"] + exp.bits_per_step()["downlink"] == bpr


def test_reference_data_is_the_programs(run, tiny_cell):
    cell = tiny_cell(CELLS[1])
    rt = run.load("runtimes/paper")
    exp = rt.build(cell)
    X, y = run.load("reference/paper").make_data(cell.config["data"],
                                                 cell.config["data"]["seed"])
    np.testing.assert_array_equal(np.asarray(exp.problem.X_workers), np.asarray(X))
    np.testing.assert_array_equal(np.asarray(exp.problem.y_workers), np.asarray(y))


def test_full_size_bits_per_round(run):
    # the exact wire of the three cells at their published sizes
    ref_mod = run.load("reference/paper")
    assert ref_mod.bits_per_round(20, 300, None) == 201_600
    assert ref_mod.bits_per_round(20, 123, None) == 82_656
    assert ref_mod.bits_per_round(20, 300, 30) == 34_200
