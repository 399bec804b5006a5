"""Prefill chunks per engine iteration over the window (``stats()``
prefill_chunks over steps): 1.0 means the one-chunk prefill lane was
busy in every iteration."""


def read(facts):
    if facts.get("kind") != "serve":
        return None
    a, b = facts["engine_before"], facts["engine_after"]
    steps = b["steps"] - a["steps"]
    return (b["prefill_chunks"] - a["prefill_chunks"]) / steps \
        if steps else None
