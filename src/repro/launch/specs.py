"""input_specs(): ShapeDtypeStruct stand-ins + shardings for every
(architecture × input-shape × mesh) combination — the dry-run's contract.

Nothing here allocates device memory: params/caches come from
``jax.eval_shape``; batches are synthesized ShapeDtypeStructs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..api import ExperimentSpec
from ..configs.base import INPUT_SHAPES, InputShape, ModelConfig
from ..core.distributed import (
    DistributedNewtonConfig,
    make_stateful_train_step,
    make_train_step,
)
from ..models import build_model
from .mesh import num_workers, worker_axes
from ..models import runtime
from .sharding import (
    batch_specs,
    cache_specs,
    channels_last_constraint,
    decode_token_spec,
    param_shardings,
    param_specs,
    tp_only_constraint,
    worker_tree_shardings,
)


class DryrunProblem(NamedTuple):
    step_fn: Callable
    args: tuple              # ShapeDtypeStruct pytrees
    in_shardings: tuple
    label: str
    skipped: str | None      # reason, if this (arch, shape) is skipped


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_struct(cfg: ModelConfig, m: int, per_worker: int, seq_len: int):
    """Training-batch ShapeDtypeStructs with a leading worker axis."""
    text = seq_len - (cfg.num_prefix_tokens if cfg.family == "vlm" else 0)
    b = {
        "tokens": _sds((m, per_worker, text), jnp.int32),
        "targets": _sds((m, per_worker, text), jnp.int32),
    }
    if cfg.family == "vlm":
        b["prefix_emb"] = _sds(
            (m, per_worker, cfg.num_prefix_tokens, cfg.d_model), jnp.float32
        )
    if cfg.family == "audio":
        b["enc_emb"] = _sds(
            (m, per_worker, cfg.encoder_len, cfg.d_model), jnp.float32
        )
    return b


def flat_batch_struct(cfg: ModelConfig, batch: int, seq_len: int):
    """Prefill batch (no worker axis)."""
    text = seq_len - (cfg.num_prefix_tokens if cfg.family == "vlm" else 0)
    b = {
        "tokens": _sds((batch, text), jnp.int32),
        "targets": _sds((batch, text), jnp.int32),
    }
    if cfg.family == "vlm":
        b["prefix_emb"] = _sds((batch, cfg.num_prefix_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        b["enc_emb"] = _sds((batch, cfg.encoder_len, cfg.d_model), jnp.float32)
    return b


def skip_reason(cfg: ModelConfig, shape: InputShape) -> str | None:
    """DESIGN.md §4 long_500k policy."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return (
            "pure full-attention arch: 500k decode cache is quadratic-history "
            "/ exceeds HBM; run the swa variant instead (DESIGN.md §4)"
        )
    if shape.name == "long_500k" and cfg.family == "audio":
        return "enc-dec audio model: 500k decode out of family scope"
    return None


def worker_shardings(params_shape, mesh, grouped: bool = False):
    """The mesh runtime's worker layout: ``(w_shard, constrain_worker,
    constrain_update)`` — the (m, …) worker-tree shardings and the two
    in-step constraints that keep worker-stacked trees on their data
    rows and the aggregated update TP-only (see
    :func:`repro.launch.sharding.worker_tree_specs`)."""
    w_shard = worker_tree_shardings(params_shape, mesh, grouped=grouped)
    u_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P(*s.spec[1:])), w_shard)

    def constrain_worker(tree):
        return jax.lax.with_sharding_constraint(tree, w_shard)

    def constrain_update(tree):
        return jax.lax.with_sharding_constraint(tree, u_shard)

    return w_shard, constrain_worker, constrain_update


def comm_state_shardings(comm_struct, w_shard, p_shard):
    """Channel EF state rides like the trees it corrects: the uplink's
    (m, …) memory as the worker trees, the downlink's as the params;
    a stateless segment's empty carry stays as it is."""

    def shard(sub, base):
        if not jax.tree_util.tree_leaves(sub):
            return sub
        return jax.tree_util.tree_map(lambda _, sh: sh, sub, base)

    return {"uplink": shard(comm_struct["uplink"], w_shard),
            "downlink": shard(comm_struct["downlink"], p_shard)}


def make_problem(
    cfg: ModelConfig,
    shape: InputShape,
    mesh,
    newton: DistributedNewtonConfig | None = None,
    worker_groups: int = 1,
) -> DryrunProblem:
    """``worker_groups`` > 1 coalesces data rows into m = rows/groups bigger
    workers — the per-worker update regains FSDP sharding (memory knob for
    the biggest archs; see sharding.worker_tree_specs)."""
    label = f"{cfg.name}×{shape.name}"
    reason = skip_reason(cfg, shape)
    if reason:
        return DryrunProblem(None, None, None, label, reason)

    model = build_model(cfg)
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_shard = param_shardings(params_shape, mesh)
    layer_gather = tp_only_constraint(mesh)
    chan_last = channels_last_constraint(mesh)

    def _hooked(fn):
        """Trace ``fn`` with the per-layer ZeRO-3 gather + channels-last
        activation constraints live."""

        def wrapped(*a):
            with runtime.layer_param_constraint(layer_gather, chan_last):
                return fn(*a)

        return wrapped

    if shape.kind == "train":
        grouped = worker_groups > 1
        m = num_workers(mesh) // worker_groups
        assert m >= 2, "need ≥2 workers for trimming to mean anything"
        # default config builds through the validated facade
        newton = newton or ExperimentSpec(
            problem="external", runtime="mesh", aggregator="norm_trim:0.125"
        ).to_distributed_config()
        w_shard, constrain_worker, constrain_update = worker_shardings(
            params_shape, mesh, grouped=grouped)

        stateful = (newton.error_feedback != "none"
                    and (newton.compressor is not None
                         or newton.downlink_compressor is not None))
        if stateful:
            # channel-state plumbing: the (m, …) EF tree rides along as an
            # extra donated arg, sharded like the worker update trees
            # (uplink) / the aggregated update (downlink).
            raw_step, init_comm_state = make_stateful_train_step(
                model.loss_fn, newton, m,
                constrain_worker=constrain_worker,
                constrain_update=constrain_update,
            )
            comm_struct = jax.eval_shape(init_comm_state, params_shape)
            cs_shard = comm_state_shardings(comm_struct, w_shard, p_shard)

            def step_fn(params, batch, comm_state):
                return raw_step(params, batch, jax.random.PRNGKey(0), comm_state)

            step_fn = _hooked(step_fn)
            batch = batch_struct(cfg, m, shape.global_batch // m, shape.seq_len)
        else:
            raw_step = make_train_step(
                model.loss_fn, newton, m,
                constrain_worker=constrain_worker,
                constrain_update=constrain_update,
            )

            def step_fn(params, batch):
                return raw_step(params, batch, jax.random.PRNGKey(0))

            step_fn = _hooked(step_fn)
            batch = batch_struct(cfg, m, shape.global_batch // m, shape.seq_len)
        if grouped:
            # m replicated; the (bigger) per-worker batch shards over the
            # data(+pod) rows instead.
            w = worker_axes(mesh)
            b_shard = jax.tree_util.tree_map(
                lambda leaf: NamedSharding(
                    mesh, P(None, w, *((None,) * (len(leaf.shape) - 2)))
                ),
                batch,
            )
        else:
            b_shard = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), batch_specs(batch, mesh)
            )
        if stateful:
            return DryrunProblem(
                step_fn, (params_shape, batch, comm_struct),
                (p_shard, b_shard, cs_shard), label, None,
            )
        return DryrunProblem(step_fn, (params_shape, batch), (p_shard, b_shard), label, None)

    if shape.kind == "prefill":

        @_hooked
        def step_fn(params, batch):
            logits, _ = model.forward(
                params,
                batch["tokens"],
                prefix_emb=batch.get("prefix_emb"),
                enc_emb=batch.get("enc_emb"),
            )
            return logits

        batch = flat_batch_struct(cfg, shape.global_batch, shape.seq_len)
        w = worker_axes(mesh)
        b_shard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, P(w, *((None,) * (len(s.shape) - 1)))),
            batch,
        )
        return DryrunProblem(step_fn, (params_shape, batch), (p_shard, b_shard), label, None)

    # decode ---------------------------------------------------------------
    B = shape.global_batch
    cache_shape = jax.eval_shape(lambda: model.init_cache(B, shape.seq_len))
    c_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        cache_specs(cache_shape, mesh, B),
        is_leaf=lambda x: isinstance(x, P),
    )
    tok = _sds((B,), jnp.int32)
    pos = _sds((), jnp.int32)
    t_shard = NamedSharding(mesh, decode_token_spec(mesh, B))
    s_shard = NamedSharding(mesh, P())

    @_hooked
    def step_fn(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return DryrunProblem(
        step_fn,
        (params_shape, cache_shape, tok, pos),
        (p_shard, c_shard, t_shard, s_shard),
        label,
        None,
    )
