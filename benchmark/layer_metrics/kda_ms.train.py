"""Device milliseconds per step under operator class
``_contrib_KimiDeltaAttention`` (the three projections and the two
low-rank pairs, the causal convolution, the gates, the channel-gated
delta rule, the gated norm and the output projection), forward and
backward.  None for a program without the operator
(benchmark/operator_time.py)."""
import operator_time


def read(facts):
    return operator_time.op_ms_per_step(facts,
                                        "_contrib_KimiDeltaAttention")
