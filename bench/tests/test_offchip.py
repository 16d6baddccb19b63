"""Off the chip the command fails and prints no result."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd, workload="a9a-robust.saddle-normtrim"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_no_tpu_exits_nonzero_without_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
