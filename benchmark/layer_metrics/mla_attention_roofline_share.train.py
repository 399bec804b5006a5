"""Latent attention's causal cores' share of their roofline, in percent:
the larger of their FLOPs over the chip's bf16 peak and their bytes over
the chip's memory bandwidth, for a step (the configuration's reference
module gives ``mla_attention_flops(kwargs)``: QK^T at the keys' width
and PV at the values' forward, dV, dP, dQ, dK backward, the lower
triangle only, no recompute; and ``mla_attention_bytes(kwargs)``: one
read of q, k, v, one write of o and as much for their gradients), over
the device time a step spends under scope ``mla.attention``: the union
of the intervals of the instructions whose ``tf_op`` carries that scope,
forward and backward, the flash kernel (``pallas.flash_attention``
inside it) or whatever runs in its place.  The need is the same whether
a kernel pads the keys' width or not; what a kernel computes again counts
in the time only.  None for a program without the scope or a reference
without the counts."""
import common
import program_trace
import trace_reduce

SCOPE = "mla.attention"


def in_attention(tf_op):
    for part in tf_op.split("/")[:-1]:
        m = program_trace._WRAPPED.match(part)
        if m and m.group(2) == SCOPE:
            return True
    return False


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.has_scopes():
        return None
    model = common.reference_model(facts["config"])
    if not hasattr(model, "mla_attention_flops"):
        return None
    ms = program_trace.per_step(facts, trace_reduce.busy_ns(
        program_trace.intervals(
            [e for e in tr.ops if in_attention(e["tf_op"])])))
    if not ms:
        return None
    kw, peaks = facts["config"]["kwargs"], facts["peaks"]
    least_s = facts["batch"] * max(
        model.mla_attention_flops(kw) / peaks["bf16_flops_per_s"],
        model.mla_attention_bytes(kw) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
