"""What the per-operator readers under ``layer_metrics/`` share: device
time under one operator class of the program (a step of the window's
mean, or the last step alone), and the expert layer's token counts of
the last step.  Both give None where the program has no
such operator or counter (a program from before them), never an error.
"""
import program_trace


def op_ms_per_step(facts, op_class):
    """Device milliseconds per step under operator class ``op_class``,
    forward and backward: the union of the intervals of the instructions
    whose ``tf_op`` carries ``<op_class>/...``
    (``program_trace.Trace.scope_ns``).  None for a program that writes
    no scope names or runs no such operator."""
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.has_scopes():
        return None
    ns = tr.scope_ns("op." + op_class)
    return program_trace.per_step(facts, ns) if ns else None


def last_step_scope_ms(facts, prefix):
    """Device milliseconds under the scopes that start with ``prefix``
    (``"op.<class>"``, ``"pallas.<kernel>"``) inside the LAST step of
    the window alone: the last ``jit_step`` program on the device's
    ``XLA Modules`` line and the instructions that start inside it.
    What the program counts about its last step (:func:`expert_tokens`)
    belongs beside this time, not beside the window's mean: routing
    moves the work from step to step.  None where there is no such
    program, scope or trace."""
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.has_scopes():
        return None
    steps = [m for m in tr.modules if m["name"].startswith("jit_step")]
    if not steps:
        return None
    last = max(steps, key=lambda m: m["start_ns"])
    t0, t1 = last["start_ns"], last["start_ns"] + last["dur_ns"]
    inside = [e for e in tr.ops if t0 <= e["start_ns"] < t1]
    ns = program_trace.scoped_ns(inside, tr.op_classes, prefix)
    return ns / 1e6 if ns else None


def expert_product_share(facts, prefix):
    """Percent of the chip's bf16 peak that the three grouped products
    (gate, up, down; forward and backward) reach over the time under
    ``prefix`` in the last step, for the tokens the held experts really
    got in that step: the configuration's reference module counts
    ``expert_product_flops(kwargs, tokens_held)``."""
    import common
    ms = last_step_scope_ms(facts, prefix)
    load = expert_tokens()
    if not ms or load is None:
        return None
    first, n = load["held_first"], load["held_count"]
    held = int(load["counts"][:, first:first + n].sum())
    cfg = facts["config"]
    flops = common.reference_model(cfg).expert_product_flops(
        cfg["kwargs"], held)
    return 100.0 * flops / facts["peaks"]["bf16_flops_per_s"] / (ms / 1e3)


def expert_tokens():
    """``{"counts": (layers, experts) int array, "held_first",
    "held_count"}`` of the last fit step, from the program's own count
    outputs (``mxnet_tpu.telemetry.moe.publish``: the gauges
    ``moe_expert_tokens{layer,expert}`` are filled by the same call);
    None where the program has no such counter."""
    try:
        from mxnet_tpu.telemetry import moe
    except ImportError:
        return None
    return moe.publish()
