"""Device milliseconds per execution of the jitted round.

The round's program is found in the trace by the module name that its
lowering gives (``step_module`` in the record), not by a literal name.
"""
LAYER = "round step"
UNIT = "ms"
MOVES = "time_to_eps_s"


def read(record):
    tr, name = record.get("trace"), record.get("step_module")
    if not tr or name not in tr["modules"]:
        return None
    seconds, executions = tr["modules"][name]
    return 1e3 * seconds / executions
