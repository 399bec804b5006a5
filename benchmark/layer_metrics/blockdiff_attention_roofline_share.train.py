"""The block-diffusion attention cores' share of their roofline, in
percent: the larger of their FLOPs over the chip's bf16 peak and their
bytes over its memory bandwidth, for a step (the configuration's
reference module gives ``blockdiff_attention_flops(kwargs)``: QK^T and
PV forward, dV, dP, dQ, dK backward, over the MASK's (query, key) pairs
only, ``L^2 + Bk L`` a head, no recompute; and
``blockdiff_attention_bytes(kwargs)``: one read of q, k, v, one write of
o over the 2 L rows and as much for their gradients), over the device
time a step spends under scope ``gqa.blockdiff``, forward and backward:
the mask's flash pair or whatever runs in its place.  Kernels that
compute whole 512 x 512 score blocks cannot pass the mask's pairs' share
of the pairs in the blocks they walk (88.9 % at L = 8192 and a block
length of 4: 288 blocks for 67.1 M pairs); the causal kernels over the
same 2 L rows could not pass 48.5 %.  What a kernel computes again
counts in the time only.  None for a program without the scope or a
reference without the counts (benchmark/dsa_time.py)."""
import dsa_time


def read(facts):
    return dsa_time.roofline_share(facts, ("gqa.blockdiff",),
                                   "blockdiff_attention_flops",
                                   "blockdiff_attention_bytes")
