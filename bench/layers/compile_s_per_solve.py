"""Seconds per solve started that the window spends getting compiled
programs, as the program's compile counter (``CompileCounter``, fed by
``jax.monitoring``'s backend-compile event) reads them.  Every ``run()``
call builds its pooled loss and gradient jits anew and compiles them: JAX
caches only programs that take a second or more to compile, and a run
writes nothing to the cache from its warm-up on.
"""
LAYER = "entry and facade"
UNIT = "s"
MOVES = "time_to_eps_s"


def read(record):
    win = record.get("window") or {}
    if not win.get("started"):
        return None
    return win["compile_s"] / win["started"]
