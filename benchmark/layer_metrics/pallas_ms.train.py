"""Device milliseconds per step under any ``pallas.<kernel>`` scope
(the names ``PALLAS_LAUNCHES`` carries): the union of the intervals of
the Pallas kernels' instructions, forward and backward.  0 for a step
that runs none; None for a program that writes no scope names
(benchmark/program_trace.py)."""
import program_trace


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.has_scopes():
        return None
    return program_trace.per_step(facts, tr.scope_ns("pallas."))
