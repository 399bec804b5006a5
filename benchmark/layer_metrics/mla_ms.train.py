"""Device milliseconds per step under operator class
``_contrib_LatentAttention`` (the query, latent and key/value
projections, the latent's norm, rotary position and the assembly of q
and k, the flash kernel, the output projection), forward and backward.
None for a program without the operator (benchmark/operator_time.py)."""
import operator_time


def read(facts):
    return operator_time.op_ms_per_step(facts, "_contrib_LatentAttention")
