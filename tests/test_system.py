"""End-to-end behaviour tests for the paper's system.

The full story in one place: the paper-faithful pipeline (synthetic LIBSVM
twin → 20 workers → Algorithm 1 under attack → robust convergence) and the
framework pipeline (train driver on a reduced assigned arch, serve driver
decode), exactly as the examples run them.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import PAPER_WORKLOADS
from repro.core import AttackConfig, DistributedCubicNewton, NewtonConfig
from repro.data import paper_dataset
from repro.launch.cache import CHECKOUT_CACHE, use_compile_cache
from repro.launch.serve import run_serving
from repro.launch.train import run_training


def logistic_loss(w, X, y):
    z = X @ w
    yy = 2.0 * y - 1.0
    return jnp.mean(jnp.log1p(jnp.exp(-yy * z))) + 0.5 / X.shape[0] * (w @ w)


def test_paper_pipeline_a9a_twin():
    """The §6 protocol end-to-end at reduced rounds: m=20 machines,
    β = α + 2/m, flipped-label attack, accuracy recovers."""
    wl = PAPER_WORKLOADS["a9a-logistic"]
    data = paper_dataset(wl, seed=0)
    m = wl.m_workers
    alpha = 0.15
    algo = DistributedCubicNewton(
        logistic_loss,
        NewtonConfig(M=wl.M, eta=wl.eta, beta=alpha + 2 / m),
        AttackConfig(name="flipped_label", alpha=alpha),
    )
    w0 = jnp.zeros(wl.dim)
    w, hist = algo.run(w0, data["X_workers"], data["y_workers"], 8)
    acc = float(((data["X_test"] @ w > 0) == (data["y_test"] > 0.5)).mean())
    assert acc > 0.8
    assert hist["loss"][-1] < hist["loss"][0]


def test_train_driver_end_to_end():
    _, hist, _ = run_training(
        arch="deepseek-moe-16b", preset="smoke", steps=6, m_workers=4,
        per_worker_batch=2, seq_len=64, solver_iters=2, log_every=5,
    )
    assert hist[-1] < hist[0]


def test_train_driver_under_attack():
    _, hist, _ = run_training(
        arch="codeqwen1.5-7b", preset="smoke", steps=6, m_workers=4,
        per_worker_batch=2, seq_len=64, solver_iters=2,
        attack="gaussian", alpha=0.25, beta=0.5, log_every=5,
    )
    assert hist[-1] < hist[0]


# the host-mesh run and the one-device run of the same steps, in a child
# that sees four CPU devices (the device count is fixed at JAX start-up)
_MESH_VS_ONE = """
import json
import numpy as np
from repro.launch.train import run_training
kw = dict(arch="mamba2-780m", preset="smoke", steps=2, m_workers=4,
          per_worker_batch=2, seq_len=32, solver_iters=2, log_every=5)
p4, l4, k4 = run_training(**kw)
_, l1, k1 = run_training(sharded=False, **kw)
print(json.dumps({
    "devices": len(jax.tree.leaves(p4)[0].sharding.device_set),
    "losses": [l4, l1],
    "kept": [[k.tolist() for k in k4], [k.tolist() for k in k1]],
}))
"""


def test_train_host_mesh_matches_one_device():
    """With several devices run_training puts the workers on the data axis
    of a host mesh; losses and keep masks match the one-device run."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(sys.path)}
    out = subprocess.run(
        [sys.executable, "-c", "import jax\n" + _MESH_VS_ONE],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    (l4, l1), (k4, k1) = rec["losses"], rec["kept"]
    assert len(l4) == 2 and jnp.allclose(jnp.array(l4), jnp.array(l1),
                                         rtol=1e-4)
    assert len(k4) == 2 and k4 == k1


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed .jax_cache/ at the checkout root."""
    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        where = use_compile_cache()
        if env_dir is None:
            assert where == str(CHECKOUT_CACHE)
            assert (CHECKOUT_CACHE.parent / "pyproject.toml").is_file()
            assert jax.config.jax_compilation_cache_dir == where
        else:
            assert where == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_serve_driver_end_to_end():
    toks = run_serving(arch="gemma3-27b", preset="smoke", batch=2,
                       prompt_len=8, gen=8)
    assert toks.shape == (2, 8)
