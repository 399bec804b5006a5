"""The Pallas grouped matmul's share of the compute roofline, in
percent: the FLOPs of the three grouped products (gate, up, down),
forward and backward, for the tokens the held experts REALLY got in the
window's last step (the program's count outputs; the configuration's
reference module gives ``expert_product_flops(kwargs, tokens_held)``),
over the chip's bf16 peak, over the device time under scope
``pallas.grouped_matmul`` in that step (the kernel forward, its two
backward kernels and the transpositions its VJP makes around them).
Bound by compute: at 512 tokens an expert the products do 1024 FLOP a
weight byte, above the chip's ridge of 240.  None for a program without
the kernel or the counter (benchmark/operator_time.py)."""
import operator_time


def read(facts):
    return operator_time.expert_product_share(facts, "pallas.grouped_matmul")
