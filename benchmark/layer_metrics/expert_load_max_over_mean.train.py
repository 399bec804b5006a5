"""Over the held experts of all layers, the fullest expert's tokens
over the mean, in the last step of the window (the program's gauge
``moe_expert_load_max_over_mean``, filled from the step's count
outputs when read): 1.0 is even routing, the number of held experts is
every token on one expert.  None for a program without the counter."""
import operator_time


def read(facts):
    load = operator_time.expert_tokens()
    if load is None:
        return None
    from mxnet_tpu import telemetry
    return telemetry.REGISTRY.get("moe_expert_load_max_over_mean").value
