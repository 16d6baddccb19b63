"""The one place that decides whether a Pallas launch is interpreted.

Every kernel wrapper takes ``interpret=None`` and resolves it here: on a
TPU backend the kernel lowers through Mosaic to a ``tpu_custom_call``;
on any other backend (the CPU test suite) it runs in Pallas interpret
mode.  An explicit ``True``/``False`` from the caller wins, which is how
the tests force the interpreter and how a compile for a described TPU
topology forces the Mosaic lowering.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or ``True`` exactly when JAX's default
    backend is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
