"""Milliseconds per step in which the device ran nothing while the
program's ``metric.readback`` span was open (``EvalMetric._totals``
waiting for the step and fetching its two scalars).  None for a program
without the span, or a trace without device operations
(benchmark/program_trace.py)."""
import program_trace


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None:
        return None
    return program_trace.per_step(facts,
                                  tr.idle_inside_ns("metric.readback"))
