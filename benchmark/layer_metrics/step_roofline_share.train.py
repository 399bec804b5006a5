"""The step's share of the compute roofline, in percent: the FLOPs the
forward and backward passes need per step (the configuration's
reference module gives ``train_flops_per_sample(kwargs)``, no
recompute) over the chip's bf16 peak, over the device-busy time per
step from the trace.  Bound by compute: at these shapes operations over
peak FLOP/s exceed bytes over peak bytes/s.  A configuration whose
reference gives no count cannot carry this metric: that raises."""
import common


def read(facts):
    tr = facts.get("trace")
    if facts.get("kind") != "train" or not tr or not facts["steps"]:
        return None
    cfg = facts["config"]
    model = common.reference_model(cfg)
    if not hasattr(model, "train_flops_per_sample"):
        raise SystemExit("step_roofline_share.train: reference/%s.py gives "
                         "no train_flops_per_sample(kwargs)"
                         % cfg["reference"])
    flops = model.train_flops_per_sample(cfg["kwargs"]) * facts["batch"]
    steps = tr["host_span_n"].get("fit_step") or facts["steps"]
    least_s = flops / facts["peaks"]["bf16_flops_per_s"]
    return 100.0 * least_s / (tr["busy_s"] / steps)
