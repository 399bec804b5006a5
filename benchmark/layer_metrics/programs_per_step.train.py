"""Programs the chip ran per step: events on the device trace's ``XLA
Modules`` line inside the window, over its steps.  Unlike
``dispatches_per_step.train`` (a counter bumped at the sites that
remember to) this counts every program, an eager one launched anywhere
in the step too.  None for a trace without device operations
(benchmark/program_trace.py)."""
import program_trace


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.modules or not facts.get("steps"):
        return None
    return len(tr.modules) / facts["steps"]
