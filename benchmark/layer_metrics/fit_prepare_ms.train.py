"""Host milliseconds per step inside the program's ``fit.prepare`` span
(``FusedFitStep.step`` from its entry to the launch: eligibility,
placing inputs, gathering parameters and optimizer state, the program
lookup), summed over the traced window.  None for a program without the
span (benchmark/program_trace.py)."""
import program_trace


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None:
        return None
    return program_trace.per_step(facts, tr.span_ns("fit.prepare"))
