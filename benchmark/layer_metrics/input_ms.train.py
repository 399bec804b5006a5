"""Host milliseconds per step the loop spends getting and placing the
next batch (the benchmark's own ``input`` span)."""


def read(facts):
    if facts.get("kind") != "train" or not facts["steps"]:
        return None
    return 1e3 * facts["spans"].get("input", 0.0) / facts["steps"]
