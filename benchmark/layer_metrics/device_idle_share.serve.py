"""Share of the traced steady window in which no operation ran on the
device, in percent (benchmark/trace_reduce.py)."""


def read(facts):
    if facts.get("kind") != "serve" or not facts.get("trace"):
        return None
    return 100.0 * facts["trace"]["idle_share"]
