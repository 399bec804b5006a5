"""The expert sublayer's share of the compute roofline, in percent: the
FLOPs of the three grouped products (gate, up, down), forward and
backward, for the tokens the held experts REALLY got in the window's
last step (the program's count outputs; the configuration's reference
module gives ``expert_product_flops(kwargs, tokens_held)``), over the
chip's bf16 peak, over the device time under operator class
``_contrib_RoutedExperts`` IN THAT STEP (``moe_ms.train`` is the
window's mean; routing moves the work from step to step, so count and
time are of one step).  Router, sort, gather and scatter count against
it: they are in the time and not in the FLOPs.  Bound by compute: at
512 tokens an expert the products do 2 * 512 = 1024 FLOP a weight byte,
above the chip's ridge of 240.  None for a program without the operator
or the counter (benchmark/operator_time.py)."""
import operator_time


def read(facts):
    return operator_time.expert_product_share(
        facts, "op._contrib_RoutedExperts")
