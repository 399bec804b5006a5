"""Host milliseconds per step inside the program's
``fit.fused_dispatch`` span (the jit call of the fit program: argument
flattening and the PJRT enqueue), summed over the traced window.  None
for a program without the span (benchmark/program_trace.py)."""
import program_trace


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None:
        return None
    return program_trace.per_step(facts, tr.span_ns("fit.fused_dispatch"))
