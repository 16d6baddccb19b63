import importlib.util

from conftest import BENCH


def _flops():
    spec = importlib.util.spec_from_file_location("bench_flops", BENCH / "flops.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_paper_round_flops_by_hand():
    # 2 workers of 3 rows, d = 4: Hessian 2*3*16 = 96 and gradient
    # 4*3*4 = 48 each; pooled loss and gradient 6*6*4 = 144
    assert _flops().paper_round_flops(2, 3, 6, 4) == 2 * (96 + 48) + 144


def test_paper_round_flops_w8a():
    # 20 workers of 2,487 rows at d = 300: the Hessians dominate
    f = _flops().paper_round_flops(20, 2487, 49740, 300)
    assert f == 20 * (2 * 2487 * 300 * 300 + 4 * 2487 * 300) + 6 * 49740 * 300
    assert f == 9_102_420_000
