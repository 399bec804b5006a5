"""The window layers' attention cores' share of their roofline, in
percent: the larger of their FLOPs over the chip's bf16 peak and their
bytes over its memory bandwidth, for a step (the configuration's
reference module gives ``window_attention_flops(kwargs)``: QK^T and PV
forward, dV, dP, dQ, dK backward, over the BAND's (query, key) pairs
only, no recompute; and ``window_attention_bytes(kwargs)``: one read of
q, k, v, one write of o and as much for their gradients), over the
device time a step spends under scope ``gqa.window``, forward and
backward: the banded flash pair or whatever runs in its place.  Kernels
that compute whole 512 x 512 score blocks cannot pass the band's pairs'
share of the pairs in the blocks they walk (88.9 % at 16 384 tokens and
a window of 4096); a path that computes every causal pair under a mask
cannot pass 43.8 %.  What a kernel computes again counts in the time
only.  None for a program without the scope or a reference without the
counts (benchmark/dsa_time.py)."""
import dsa_time


def read(facts):
    return dsa_time.roofline_share(facts, ("gqa.window",),
                                   "window_attention_flops",
                                   "window_attention_bytes")
