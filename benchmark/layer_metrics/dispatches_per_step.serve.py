"""Device dispatches per engine iteration over the window
(``DecodeEngine.stats()``: decode_step_dispatches over steps)."""


def read(facts):
    if facts.get("kind") != "serve":
        return None
    a, b = facts["engine_before"], facts["engine_after"]
    steps = b["steps"] - a["steps"]
    return (b["dispatches"] - a["dispatches"]) / steps if steps else None
