"""Device milliseconds per step under the fit program's ``fit.update``
scope (gradient compression and the optimizer update, with the loss
scaler's ``cond`` where there is one): the union of the intervals of
the instructions whose ``tf_op`` carries the scope.  None for a program
that writes no scope names (benchmark/program_trace.py)."""
import program_trace


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.has_scopes():
        return None
    return program_trace.per_step(facts, tr.scope_ns("fit.update"))
