"""Persistent-compile-cache misses of the whole run up to the window's
end (``aot_cache_misses``): 0 once every program of the cell is in the
cache, so a set-up that compiles again shows here."""


def read(facts):
    after = facts.get("after")
    return after["aot_cache_misses"] if after else None
