"""What the readers of the sparse indexed attention share: device time
a step under some of the operator's own scopes (``dsa.attention``,
``dsa.indexer``, ...: ``jax.named_scope`` names inside
``_contrib_SparseIndexedAttention``, forward and backward), and a
roofline share from it and the reference's counts.  None where the
program writes no such scope or the reference has no such count (a
program or a configuration from before them), never an error."""
import common
import program_trace
import trace_reduce


def carries(tf_op, scopes):
    """Whether one of ``scopes`` is an element of the ``tf_op`` path
    (under jax's ``jvp(...)``, ``transpose(...)``, ``vmap(...)``
    wrappers or bare); the last element is the primitive."""
    for part in tf_op.split("/")[:-1]:
        m = program_trace._WRAPPED.match(part)
        if m and m.group(2) in scopes:
            return True
    return False


def scope_ms_per_step(facts, scopes):
    """Device milliseconds per step of the window under ``scopes``: the
    union of the intervals of the instructions that carry one."""
    tr = program_trace.train_trace(facts)
    if tr is None or not tr.has_scopes():
        return None
    ns = trace_reduce.busy_ns(program_trace.intervals(
        [e for e in tr.ops if carries(e["tf_op"], scopes)]))
    return program_trace.per_step(facts, ns) if ns else None


def roofline_share(facts, scopes, flops, moved):
    """Percent: the larger of ``flops(kwargs)`` over the chip's bf16
    peak and ``moved(kwargs)`` over its memory bandwidth (names of the
    configuration's reference module; a step of ``batch`` sequences),
    over the time a step spends under ``scopes``."""
    model = common.reference_model(facts["config"])
    if not (hasattr(model, flops) and hasattr(model, moved)):
        return None
    ms = scope_ms_per_step(facts, scopes)
    if not ms:
        return None
    kw, peaks = facts["config"]["kwargs"], facts["peaks"]
    least_s = facts["batch"] * max(
        getattr(model, flops)(kw) / peaks["bf16_flops_per_s"],
        getattr(model, moved)(kw) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
