"""`correct` is decided by the comparison with the plain reference, and
that comparison fails the control and each fault a paper cell can have.

Each test drives the rest of a run (set-up, window, comparison, result
line) on the CPU at a tiny size, past the harness's look for a chip, with
the cell's own traffic and limits.  The faults (``bench/faults.py``) are
planted in the program underneath the timed path.
"""
import jax.numpy as jnp
import pytest


CELLS = ["w8a-logistic.gauss-normtrim", "a9a-robust.saddle-normtrim"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 11


def _result(run, cell, seconds=1.0):
    rt = run.load("runtimes/paper")
    record = rt.run_cell(cell, SEED, seconds, False, 0.0, run.load)
    return run.result_line(cell, record, CPU, False)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(run, tiny_cell, workload):
    out = _result(run, tiny_cell(workload))
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(run, tiny_cell, workload, fault):
    cell = tiny_cell(workload)
    with run.load("faults").planted(fault):
        out = _result(run, cell)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("workload", CELLS)
def test_bfloat16_control_is_not_correct(run, tiny_cell, workload):
    """The reference in bfloat16 in the program's place fails the limits."""
    cell = tiny_cell(workload)
    rt = run.load("runtimes/paper")
    ref_mod = run.load("reference/paper")
    ref16 = rt.reference_for(cell, ref_mod, dtype=jnp.bfloat16)
    ref32 = rt.reference_for(cell, ref_mod)
    bpr = ref_mod.bits_per_round(ref32.m, ref32.d, cell.traffic["reference"].get("topk"))
    solves = rt.control_solves(cell, ref16, SEED, bpr)
    correct, checks = run.judge(cell, rt.compare(cell, SEED, solves, ref_mod, ref32))
    assert correct is False, checks
