"""Mean share of the engine's slots that held a sequence, per
iteration of the window, in percent (``stats()`` mean_slot_occupancy
as a running sum, differenced over the window)."""


def read(facts):
    if facts.get("kind") != "serve":
        return None
    a, b = facts["engine_before"], facts["engine_after"]
    steps = b["steps"] - a["steps"]
    if not steps:
        return None
    cap = facts["config"]["engine"]["capacity"]
    return 100.0 * (b["slot_occ_sum"] - a["slot_occ_sum"]) / steps / cap
