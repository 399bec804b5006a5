"""Share of the device's busy time, in percent, spent in instructions
whose ``tf_op`` carries one of the program's scopes (an operator class,
``fit.*``, ``pallas.*``): the guard that the names have not rotted.
Unions of intervals on both sides, so it cannot pass 100.  None for a
program that writes no scope names (benchmark/program_trace.py)."""
import program_trace


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.has_scopes():
        return None
    return 100.0 * tr.scope_ns("") / tr.busy_ns()
