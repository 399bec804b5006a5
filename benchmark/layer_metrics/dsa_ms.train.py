"""Device milliseconds per step under operator class
``_contrib_SparseIndexedAttention`` (the projections with their norms
and rotary, the index scorer, the choice, the sparse cores and the index
loss, the output projection), forward and backward.  None for a program
without the operator (benchmark/operator_time.py)."""
import operator_time


def read(facts):
    return operator_time.op_ms_per_step(facts,
                                        "_contrib_SparseIndexedAttention")
