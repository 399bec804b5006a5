"""Device milliseconds per step under operator class
``_contrib_CompressedConvAttention`` (projections, both convolutions,
the query-key mean, normalisation, rotary position, the flash kernel and
the output projection), forward and backward.  None for a program
without the operator (benchmark/operator_time.py)."""
import operator_time


def read(facts):
    return operator_time.op_ms_per_step(
        facts, "_contrib_CompressedConvAttention")
