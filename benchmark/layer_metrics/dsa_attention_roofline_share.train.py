"""The sparse cores' share of their roofline, in percent: the larger of
their FLOPs over the chip's bf16 peak and their bytes over its memory
bandwidth, for a step (the configuration's reference module gives
``dsa_attention_flops(kwargs)``: QK^T and PV forward, dV, dP, dQ, dK
backward, over the CHOSEN (query, key) pairs only, no recompute; and
``dsa_attention_bytes(kwargs)``: one read of q, k, v, one write of o and
as much for their gradients), over the device time a step spends under
scope ``dsa.attention``, forward and backward.  A path that computes
every causal pair under a mask cannot pass the chosen pairs' share of
the causal ones (23 % at 16 384 tokens and 2048 keys); what it computes
again counts in the time only.  None for a program without the scope or
a reference without the counts (benchmark/dsa_time.py)."""
import dsa_time


def read(facts):
    return dsa_time.roofline_share(facts, ("dsa.attention",),
                                   "dsa_attention_flops",
                                   "dsa_attention_bytes")
