"""Device dispatches per fit step: ``profiler.DEVICE_DISPATCHES`` over
the window's steps (1.0 when the whole step is one program)."""


def read(facts):
    if facts.get("kind") != "train" or not facts["steps"]:
        return None
    d = facts["after"]["device_dispatches"] \
        - facts["before"]["device_dispatches"]
    return d / facts["steps"]
