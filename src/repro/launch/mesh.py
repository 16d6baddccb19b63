"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required for the smoke tests, which must see one
CPU device, while the dry-run process sees 512 forced host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the shardings are constraints that GSPMD propagates
    # through the step, as the sharding rules assume
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Mesh over the devices of this host: ``(n // model_axis,
    model_axis)`` on the ``("data", "model")`` axes — the training
    training loop's worker layout on a one-host TPU slice."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def worker_axes(mesh) -> tuple:
    """Mesh axes that enumerate the paper's 'worker machines'."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_workers(mesh) -> int:
    import math

    return math.prod(mesh.shape[a] for a in worker_axes(mesh))
