"""Device time of one flush group: mean duration, in ms, of the executions
of the program the engine's flush-group step compiles under (``jit_run``)."""

PROGRAM = "jit_run"


def read(rec):
    s = rec.trace
    runs = [] if s is None else s.modules.get(PROGRAM, [])
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
