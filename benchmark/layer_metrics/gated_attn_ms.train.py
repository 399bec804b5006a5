"""Device milliseconds per step under operator class
``_contrib_GatedCausalSelfAttention`` (the query-and-gate, key and value
projections, the per-head norms, rotary position, the flash kernel, the
output gate and the output projection), forward and backward.  None for
a program without the operator (benchmark/operator_time.py)."""
import operator_time


def read(facts):
    return operator_time.op_ms_per_step(
        facts, "_contrib_GatedCausalSelfAttention")
