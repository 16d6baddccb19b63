"""End-to-end training driver: Byzantine-robust cubic-Newton on an LM.

Runs on the devices of one host.  With one device every array lives on
it.  With more, the workers go on the ``data`` axis of a
``(n_devices, 1)`` host mesh, and the parameter, batch and worker-tree
shardings are the ones :mod:`repro.launch.specs` gives the dry run.

Example (the examples/train_lm.py quickstart uses this):

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m \
        --preset smoke --steps 50 --m-workers 4 --attack negative \
        --alpha 0.25 --beta 0.5

All solver/channel/resilience configuration builds through one validated
:class:`repro.api.ExperimentSpec` (β > α, spec-string grammar, EF/
compressor compatibility are checked before anything traces);
``--aggregator`` takes any registry spec (``norm_trim:0.5``, ``krum:1``,
``trimmed_mean:0.25``, ``coordinate_median``, ``mean``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np
from jax.sharding import NamedSharding

from ..api import ExperimentSpec, default_aggregator_spec
from ..checkpoint import save_checkpoint
from ..comm import WireLedger
from ..configs import get_config
from ..core.distributed import (
    make_robust_sgd_step,
    make_stateful_train_step,
    make_train_step,
)
from ..data import WorkerBatcher
from ..models import build_model
from ..telemetry import (RoundRecord, compile_scope, get_telemetry,
                         rejected_from_keep)
from .cache import use_compile_cache
from .mesh import make_host_mesh
from .sharding import batch_specs, param_shardings
from .specs import comm_state_shardings, worker_shardings


def scale_config(cfg, preset: str):
    if preset == "full":
        return cfg
    if preset == "smoke":
        return cfg.reduced()
    if preset == "100m":
        # ~100M-param member of the same family (train_lm example target)
        return dataclasses.replace(
            cfg.reduced(),
            name=cfg.name + "-100m",
            num_layers=max(len(cfg.hybrid_pattern) or 0, 8),
            d_model=768,
            num_heads=12,
            num_kv_heads=max(1, 12 // max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))),
            head_dim=64,
            d_ff=3072 if cfg.d_ff else 0,
            vocab_size=min(cfg.vocab_size, 32768),
            dtype="float32",
        )
    raise ValueError(preset)


def run_training(
    arch: str = "mamba2-780m",
    preset: str = "smoke",
    steps: int = 50,
    m_workers: int = 4,
    per_worker_batch: int = 2,
    seq_len: int = 128,
    eta: float = 1.0,
    M: float = 10.0,
    beta: float = 0.25,
    solver_iters: int = 4,
    attack: str = "none",
    alpha: float = 0.0,
    aggregator: str | None = None,
    optimizer: str = "cubic_newton",
    lr: float = 0.3,
    two_round: bool = False,
    compressor: str | None = None,
    downlink_compressor: str | None = None,
    error_feedback: str = "none",
    seed: int = 0,
    ckpt_dir: str | None = None,
    log_every: int = 10,
    telemetry_dir: str | None = None,
    sharded: bool | None = None,
):
    """Train for ``steps`` rounds; returns ``(params, losses, kept)`` with
    the per-step loss and the center's per-step (m,) keep mask (empty
    for ``robust_sgd``).

    ``sharded`` (default: whenever the host has more than one device)
    puts the workers on the ``data`` axis of a host mesh; ``False``
    keeps every array on the default device."""
    tel = get_telemetry()
    if telemetry_dir is not None:
        tel.enable(telemetry_dir)
    cfg = scale_config(get_config(arch), preset)
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    n_params = model.param_count(params)
    if sharded is None:
        sharded = len(jax.devices()) > 1
    mesh = make_host_mesh() if sharded else None
    if mesh is not None and m_workers % mesh.shape["data"]:
        raise ValueError(
            f"m_workers={m_workers} must be a multiple of the host's "
            f"{mesh.shape['data']} devices to put workers on the data axis"
        )
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"m={m_workers} attack={attack}@{alpha} optimizer={optimizer} "
          f"devices={mesh.size if mesh is not None else 1}")

    constrain_worker = constrain_update = None
    if mesh is not None:
        p_shard = param_shardings(params, mesh)
        w_shard, constrain_worker, constrain_update = worker_shardings(
            params, mesh)
        params = jax.device_put(params, p_shard)

        def place_batch(batch):
            return jax.device_put(batch, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), batch_specs(batch, mesh)))

        def pin(raw):
            """Keep the returned params in their input layout, so every
            step after the first reuses the first step's executable."""
            def pinned(params, *rest):
                out = raw(params, *rest)
                return (jax.lax.with_sharding_constraint(out[0], p_shard),
                        *out[1:])
            return pinned
    else:
        def place_batch(batch):
            return batch

        def pin(raw):
            return raw

    ledger = WireLedger()
    comm_state = None
    wire_bits = None
    if optimizer == "cubic_newton":
        # one declarative spec — validated (β > α, spec grammars, EF/
        # compressor compatibility) before anything traces — is the only
        # config constructor on this path.
        spec = ExperimentSpec(
            problem="external", runtime="mesh", m_workers=m_workers,
            M=M, eta=eta, solver_iters=solver_iters,
            exact_gradient=two_round, compressor=compressor,
            downlink_compressor=downlink_compressor,
            error_feedback=error_feedback,
            aggregator=aggregator if aggregator is not None
            else default_aggregator_spec(beta),
            attack=attack, alpha=alpha, seed=seed,
        )
        ncfg = spec.to_distributed_config()
        if error_feedback != "none":
            # stateful channels: the (m, d)-tree EF memory is threaded (and
            # donated) through the step so long runs keep error feedback.
            raw_step, init_comm_state = make_stateful_train_step(
                model.loss_fn, ncfg, m_workers,
                attack_name=attack, attack_alpha=alpha,
                constrain_worker=constrain_worker,
                constrain_update=constrain_update,
            )
            comm_state = init_comm_state(params)
            if mesh is not None:
                comm_state = jax.device_put(comm_state, comm_state_shardings(
                    comm_state, w_shard, p_shard))
            step = jax.jit(pin(raw_step), donate_argnums=(3,))
        else:
            raw_step = make_train_step(
                model.loss_fn, ncfg, m_workers,
                attack_name=attack, attack_alpha=alpha,
                constrain_worker=constrain_worker,
                constrain_update=constrain_update,
            )
            step = jax.jit(pin(raw_step))
        wire_bits = raw_step.wire_bits(params)  # exact static ints
    else:
        step = jax.jit(pin(make_robust_sgd_step(
            model.loss_fn, lr, m_workers, beta=beta,
            constrain_worker=constrain_worker)))

    batcher = WorkerBatcher(cfg, m_workers, m_workers * per_worker_batch, seq_len, seed)
    history = []
    kept = []
    prev_loss = None
    t0 = time.time()
    for it in range(steps):
        key, sub = jax.random.split(key)
        batch = place_batch(batcher(it))
        # the compile-counter attributes every (re)trace of the mesh step
        # to this scope (host-side contextvar, never traced)
        with compile_scope("mesh.step"):
            if comm_state is not None:
                params, metrics, comm_state = step(params, batch, sub, comm_state)
            else:
                params, metrics = step(params, batch, sub)
        if wire_bits is not None:
            ledger.record(uplink=wire_bits["uplink"],
                          downlink=wire_bits["downlink"],
                          rounds=2 if two_round else 1, label="round")
        loss = float(metrics["loss"])
        history.append(loss)
        if "kept" in metrics:
            kept.append(np.asarray(metrics["kept"]))
        if tel.enabled:
            tel.round(RoundRecord(
                step=it, runtime="mesh", loss=loss,
                model_decrease=(None if prev_loss is None
                                else prev_loss - loss),
                uplink_delta=(float(metrics["uplink_delta"])
                              if "uplink_delta" in metrics else None),
                rejected=(rejected_from_keep(metrics["kept"])
                          if "kept" in metrics else ()),
                attack=attack, alpha=alpha,
                wire_uplink_bits=(wire_bits or {}).get("uplink"),
                wire_downlink_bits=(wire_bits or {}).get("downlink"),
            ), name="train.round")
            prev_loss = loss
        if it % log_every == 0 or it == steps - 1:
            dt = time.time() - t0
            wire = (f" wire_up={ledger.uplink_bits} wire_down={ledger.downlink_bits}"
                    if wire_bits is not None else "")
            print(f"[train] step={it:5d} loss={loss:.4f} "
                  f"update_norm={float(metrics.get('update_norm', 0.0)):.3e} "
                  f"({dt/(it+1):.2f}s/step){wire}")
        if ckpt_dir and (it + 1) % 100 == 0:
            save_checkpoint(ckpt_dir, params, it + 1, {"loss": loss})
    if ckpt_dir:
        save_checkpoint(ckpt_dir, params, steps, {"loss": history[-1]})
    if wire_bits is not None:
        print(f"[train] wire ledger (exact ints): {ledger.snapshot()}")
    if telemetry_dir is not None:
        tel.flush()
        print(f"[train] telemetry → {telemetry_dir}")
    return params, history, kept


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-780m")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--m-workers", type=int, default=4)
    ap.add_argument("--per-worker-batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--eta", type=float, default=1.0)
    ap.add_argument("--M", type=float, default=10.0)
    ap.add_argument("--beta", type=float, default=0.25)
    ap.add_argument("--solver-iters", type=int, default=4)
    ap.add_argument("--attack", default="none",
                    help="attack spec (none/gaussian[:sigma]/negative[:c]/"
                         "saddle[:scale])")
    ap.add_argument("--alpha", type=float, default=0.0)
    ap.add_argument("--aggregator", default=None,
                    help="aggregator spec (norm_trim:<beta>/krum:<n>/"
                         "trimmed_mean:<f>/coordinate_median/mean); "
                         "default norm_trim:<--beta>")
    ap.add_argument("--optimizer", default="cubic_newton",
                    choices=["cubic_newton", "robust_sgd"])
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--two-round", action="store_true")
    ap.add_argument("--compressor", default=None,
                    help="uplink spec, e.g. topk:0.1 / signnorm / int8")
    ap.add_argument("--downlink-compressor", default=None,
                    help="center→worker broadcast spec")
    ap.add_argument("--error-feedback", default="none",
                    choices=["none", "ef", "ef21"],
                    help="mesh-scale EF (threads channel state through the step)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--telemetry-dir", default=None,
                    help="write telemetry (per-round records, wire events, "
                         "compile spans, trace.json) into this directory")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    _, hist, _ = run_training(**{k.replace("-", "_"): v for k, v in vars(args).items()})
    print(json.dumps({"final_loss": hist[-1], "first_loss": hist[0]}))


if __name__ == "__main__":
    main()
