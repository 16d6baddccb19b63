"""The reduction of the program's own spans (``bench/spans.py``) on
hand-built intervals and on recorded chip traces.

``data/tiny_solve.xplane.pb`` was recorded on one TPU v5 lite chip with
the Python tracer off: one ``Experiment.run(3)`` of the paper runtime
(``synthetic-logistic:120:12``, m = 4, ``norm_trim:0.3`` against a
Gaussian attack, α = 0.25) inside the ``bench.traced_window`` annotation,
after a warm-up solve, so its pooled loss and gradient compile inside the
trace as in every solve.  Its rounds ran 77, 85 and 91 Algorithm 2
iterations.  Only the lines the reductions read were kept: the Python
thread's on ``/host:CPU``, and ``XLA Ops`` and ``XLA Modules`` on the chip.
"""
import pytest

from conftest import BENCH

WINDOW_FIXTURE = BENCH / "tests" / "data" / "tiny_window.xplane.pb"
SOLVE_FIXTURE = BENCH / "tests" / "data" / "tiny_solve.xplane.pb"
SOLVE_ITERS = [77, 85, 91]


@pytest.fixture(scope="module")
def spans(run):
    return run.load("spans")


def test_span_table_self_time_by_hand(spans):
    # one solve of two rounds; the second round has no wait
    events = [(0, 100, "newton.solve"),
              (10, 60, "newton.round"), (10, 20, "newton.round.step"),
              (20, 40, "newton.round.wait"), (45, 55, "newton.round.pooled"),
              (60, 95, "newton.round"), (60, 65, "newton.round.step"),
              (70, 90, "newton.round.pooled")]
    t = {k: [round(v[0] * 1e9), v[1], round(v[2] * 1e9)]
         for k, v in spans.span_table(events).items()}
    assert t["newton.solve"] == [100, 1, 15]
    assert t["newton.round"] == [85, 2, 20]
    assert t["newton.round.pooled"] == [30, 2, 30]
    assert t["newton.round.step"] == [15, 2, 15]
    assert spans.span_table([]) == {}


@pytest.mark.parametrize("a, b, both", [
    ([(0, 10), (20, 30)], [(5, 25)], 10),
    ([(0, 10), (5, 15)], [(0, 100)], 15),        # a's overlap counts once
    ([(0, 10)], [(10, 20)], 0),
    ([], [(0, 5)], 0),
])
def test_idle_inside_spans_by_hand(spans, a, b, both):
    assert spans.intersection_length(a, b) == both
    assert spans.intersection_length(b, a) == both


def test_while_inside_modules_by_hand(spans):
    loops = [(10, 20), (40, 55), (90, 95)]
    runs = {"jit__step_impl": [(0, 30), (35, 50)], "jit_norm": [(50, 100)]}
    assert spans.while_in_modules(loops, runs) == {"jit__step_impl": 20, "jit_norm": 10}


@pytest.mark.parametrize("text, code", [
    ("%while.28 = (s32[]{:T(128)}, f32[20,300]{1,0:T(8,128)}) while((s32[]{:T(128)}, "
     "f32[20,300]{1,0:T(8,128)}) %tuple.13), condition=%cond, body=%body", "while"),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", "fusion"),
    ("%copy-done.1 = f32[512,512]{1,0:T(8,128)S(1)} copy-done((f32[512,512]{1,0}, u32[]) "
     "%copy-start.1)", "copy-done"),
    ("%while_like_fusion = f32[] fusion(f32[] %p), calls=%while", "fusion"),
])
def test_op_code(spans, text, code):
    assert spans.op_code(text) == code


def test_layer_numbers_by_hand(spans):
    red = {"window_s": 1.0, "idle_s": 0.5,
           "spans": {"newton.round": [0.08, 4, 0.01], "newton.round.step": [0.004, 4, 0.004],
                     "newton.round.pooled": [0.06, 4, 0.06]},
           "idle_in_spans": {"newton.round.pooled": 0.4},
           "while_s": {"jit__step_impl": 0.002}}
    out = spans.layer_numbers(red, "jit__step_impl", [100, 100, 150, 150])
    assert out == pytest.approx({"pooled_ms_per_round": 15.0, "round_host_ms": 3.5,
                                 "idle_in_pooled_share": 80.0,
                                 "cubic_iters_per_round": 125.0,
                                 "cubic_iter_device_us": 4.0})
    empty = {"window_s": 1.0, "idle_s": 0.5, "spans": {}, "idle_in_spans": {}, "while_s": {}}
    assert spans.layer_numbers(empty, "jit__step_impl", None) == {}
    assert spans.layer_numbers(empty, "jit__step_impl", []) == {}


def test_reduce_recorded_window_without_spans(spans, run):
    """A program with no spans gives empty tables; the while loop of the
    recorded module is found by its opcode and lies inside its runs."""
    red = spans.reduce_spans(str(WINDOW_FIXTURE))
    base = run.load("trace").reduce_trace(str(WINDOW_FIXTURE))
    assert red["spans"] == {} and red["idle_in_spans"] == {}
    assert red["window_s"] == pytest.approx(base["window_s"])
    assert red["idle_s"] + base["busy_s"] == pytest.approx(base["window_s"], rel=1e-9)
    assert 0 < red["while_s"]["jit_tiny"] <= base["modules"]["jit_tiny"][0]
    assert set(red["while_s"]) == {"jit_tiny"}


def test_reduce_recorded_solve(spans, run):
    red = spans.reduce_spans(str(SOLVE_FIXTURE))
    base = run.load("trace").reduce_trace(str(SOLVE_FIXTURE))
    t = red["spans"]
    assert t["newton.solve"][1] == 1
    assert t["newton.round"][1] == 3
    for child in ("newton.round.step", "newton.round.wait", "newton.round.pooled"):
        assert t[child][1] == 3
        assert t[child][2] == pytest.approx(t[child][0])      # leaves: all self time
    children = sum(t[c][0] for c in ("newton.round.step", "newton.round.wait",
                                     "newton.round.pooled"))
    assert t["newton.round"][2] == pytest.approx(t["newton.round"][0] - children)
    assert t["newton.solve"][2] == pytest.approx(t["newton.solve"][0] - t["newton.round"][0])
    assert t["newton.solve"][0] >= 0.95 * red["window_s"]
    assert red["idle_s"] + base["busy_s"] == pytest.approx(red["window_s"], rel=1e-9)
    assert 0 < red["idle_in_spans"]["newton.round.pooled"] <= t["newton.round.pooled"][0]
    assert red["idle_in_spans"]["newton.solve"] <= red["idle_s"]
    (module,) = red["while_s"]
    assert module.startswith("jit__step_impl")
    assert 0 < red["while_s"][module] <= base["modules"][module][0]
    out = spans.layer_numbers(red, module, SOLVE_ITERS)
    assert set(out) == {"pooled_ms_per_round", "round_host_ms", "idle_in_pooled_share",
                        "cubic_iters_per_round", "cubic_iter_device_us"}
    assert out["cubic_iters_per_round"] == pytest.approx(sum(SOLVE_ITERS) / 3)
    assert 0 < out["idle_in_pooled_share"] <= 100
    assert spans.layer_numbers(red, "jit_other", None).keys() == {
        "pooled_ms_per_round", "round_host_ms", "idle_in_pooled_share"}


def test_recording_keeps_histories_and_compile_scopes(spans, run, tiny_cell, tmp_path):
    """The script's traced window on the CPU at a tiny size: each solve's
    history carries its rounds' Algorithm 2 iterations, and the per-call
    compiles of the pooled loss and gradient are attributed to their
    scope; off the chip the span reduction refuses the trace."""
    cell = tiny_cell("a9a-robust.saddle-normtrim")
    rt = run.load("runtimes/paper")
    exp = rt.build(cell)
    rt.compile_round(exp)
    with rt.cache_writes_off():
        rt.warm_up(exp, cell, 2**31 + 3)
        rec = spans.Recording(exp)
        win = rt.window(rec, cell, 2**31 + 3, 1.0, str(tmp_path))
    assert len(rec.solves) == win["started"]
    for hist, compile_s in rec.solves:
        assert len(hist["cubic_iters"]) == len(hist["loss"]) > 0
        assert all(isinstance(i, int) and i > 0 for i in hist["cubic_iters"])
        assert compile_s["newton.pooled"] > 0
    with pytest.raises(RuntimeError, match="not a chip trace"):
        spans.reduce_spans(run.load("trace").find_trace(str(tmp_path)))
