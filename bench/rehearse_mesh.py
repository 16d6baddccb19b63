#!/usr/bin/env python3
"""Compile rehearsal of the four-chip mesh round, with no chip attached.

    LIBTPU_INIT_ARGS=--xla_tpu_enable_async_collective_fusion=false \\
    JAX_PLATFORMS=cpu python3 bench/rehearse_mesh.py [--layers 8] [--seq-len 2048] \\
        [--batches 4,2,1] [--out FILE]
    python3 bench/rehearse_mesh.py --attached ...   # on a host with four chips

Without that flag (or ``--xla_tpu_enable_latency_hiding_scheduler=false``)
the TPU compiler's scheduler refuses this round at mamba2-780m's widths
with a ``RET_CHECK`` in ``hlo_schedule.cc`` (PERF.md, Open questions).

Compiles the mesh training step that ``launch/train.py`` assembles
(``build_model``, ``make_train_step``, ``param_shardings``,
``worker_shardings``, ``batch_specs``) for a described ``v5e:2x2``:
mamba2-780m at its published widths, cut in depth only, m = 4 workers on
the data axis of a (4, 1) mesh, the ``negative:0.9`` attack at α = 0.25
against ``norm_trim:0.5``.  For each per-worker batch, largest first, it
prints the bytes ``memory_analysis`` gives per chip, and names the largest
batch that fits one chip's 16 GB.  A compile that passes is not a chip
run: its bytes are an upper bound (on the chip, peaks have read about a
sixth of them), and it gives no time.  With ``--attached`` the same
compile is made for the four chips of the host it runs on, nothing run.
"""
import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HBM_BYTES = 16e9


def compile_step(devices, layers: int, per_worker: int, seq_len: int, m: int = 4):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding

    from repro.api import ExperimentSpec
    from repro.configs import get_config
    from repro.core.distributed import make_train_step
    from repro.launch.sharding import batch_specs, param_shardings
    from repro.launch.specs import worker_shardings
    from repro.models import build_model

    cfg = dataclasses.replace(get_config("mamba2-780m"), num_layers=layers)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(devices[:m]).reshape(m, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    p_shard = param_shardings(shapes, mesh)
    _, constrain_worker, constrain_update = worker_shardings(shapes, mesh)
    spec = ExperimentSpec(problem="external", runtime="mesh", m_workers=m,
                          aggregator="norm_trim:0.5", attack="negative:0.9",
                          alpha=0.25)
    raw = make_train_step(model.loss_fn, spec.to_distributed_config(), m,
                          attack_name="negative:0.9", attack_alpha=0.25,
                          constrain_worker=constrain_worker,
                          constrain_update=constrain_update)

    def pinned(params, *rest):
        out = raw(params, *rest)
        return (jax.lax.with_sharding_constraint(out[0], p_shard), *out[1:])

    tokens = jax.ShapeDtypeStruct((m, per_worker, seq_len), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    b_shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                     batch_specs(batch, mesh))
    params_in = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, p_shard)
    batch_in = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        batch, b_shard)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, jax.sharding.PartitionSpec()))
    compiled = jax.jit(pinned).lower(params_in, batch_in, key).compile()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    text = compiled.as_text()
    return {
        "layers": layers, "per_worker_batch": per_worker, "seq_len": seq_len,
        "params": n_params,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "per_chip_bytes": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        "collectives": {op: text.count(f" {op}(") for op in
                        ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--batches", default="4,2,1")
    ap.add_argument("--out")
    ap.add_argument("--attached", action="store_true",
                    help="compile for the chips of this host, not a described v5e:2x2")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    devices = (jax.devices() if args.attached else
               topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices)
    chosen = None
    for b in (int(x) for x in args.batches.split(",")):
        row = compile_step(devices, args.layers, b, args.seq_len)
        row["fits"] = row["per_chip_bytes"] < HBM_BYTES
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        if row["fits"] and chosen is None:
            chosen = b
    print(json.dumps({"per_worker_batch": chosen}), flush=True)
    return 0 if chosen is not None else 1


if __name__ == "__main__":
    sys.exit(main())
