"""The channel-gated delta rule's share of its roofline, in percent: the
larger of its FLOPs over the chip's bf16 peak and its bytes over the
chip's memory bandwidth, for a step (the configuration's reference
module gives ``kda_scan_flops(kwargs)``, the chunked algorithm's matrix
products forward and backward with no recompute, and
``kda_scan_bytes(kwargs)``, one read of q, k, v, g, beta, one write of o
and as much for their gradients; g is as large as k), over the device
time a step spends under scope ``kda.scan`` (``pallas.kda_delta_rule``
where it is a kernel): the union of the intervals of the instructions
whose ``tf_op`` carries that scope, forward and backward, as
``gdn_scan_roofline_share.train`` reads the scalar gate's.  None for a
program without the scope or a reference without the counts."""
import common
import program_trace
import trace_reduce

SCOPES = ("kda.scan", "pallas.kda_delta_rule")


def in_scan(tf_op):
    for part in tf_op.split("/")[:-1]:
        m = program_trace._WRAPPED.match(part)
        if m and m.group(2) in SCOPES:
            return True
    return False


def read(facts):
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.has_scopes():
        return None
    model = common.reference_model(facts["config"])
    if not hasattr(model, "kda_scan_flops"):
        return None
    ms = program_trace.per_step(facts, trace_reduce.busy_ns(
        program_trace.intervals([e for e in tr.ops if in_scan(e["tf_op"])])))
    if not ms:
        return None
    kw, peaks = facts["config"]["kwargs"], facts["peaks"]
    least_s = facts["batch"] * max(
        model.kda_scan_flops(kw) / peaks["bf16_flops_per_s"],
        model.kda_scan_bytes(kw) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
