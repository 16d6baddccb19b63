"""Faults planted underneath the timed path of a paper-runtime cell.

Each wraps the program's round (``DistributedCubicNewton._step_impl``,
from which every runtime instance jits its step) so that the window runs
a broken round while everything above it stays the program's own.  The
CPU tests (``bench/tests/test_correctness.py``) check at a tiny size that
each comes out not correct; ``bench/readings.py --faults`` reads them on
the chip at a cell's own size.  The exchange between chips is not among
them: the paper cells run on one chip.
"""
import contextlib


def state_unchanged(orig):
    """The round returns its state as it got it."""
    def step(self, w, v, state, X, y, key):
        info = orig(self, w, v, state, X, y, key)[3]
        return w, v, state, info
    return step


def half_batch(orig):
    """Each worker computes on the first half of its rows only."""
    def step(self, w, v, state, X, y, key):
        n = X.shape[1] // 2
        return orig(self, w, v, state, X[:, :n], y[:, :n], key)
    return step


def answer_altered(orig):
    """The round's new iterate is altered where it is produced."""
    def step(self, w, v, state, X, y, key):
        w_new, v_new, state, info = orig(self, w, v, state, X, y, key)
        return w_new.at[0].add(0.01), v_new, state, info
    return step


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, answer_altered)}


@contextlib.contextmanager
def planted(name: str):
    """The program's round broken by fault ``name`` for the block; build
    the experiment inside it."""
    from repro.core.newton import DistributedCubicNewton

    orig = DistributedCubicNewton._step_impl
    DistributedCubicNewton._step_impl = FAULTS[name](orig)
    try:
        yield
    finally:
        DistributedCubicNewton._step_impl = orig
