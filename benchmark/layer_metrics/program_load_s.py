"""Seconds the dispatch sites' programs took to become executables:
XLA's compile on a first run, the cache's read and deserialisation
warm (``program_build_seconds{phase="load"}`` over ``fit_step``,
``executor``, ``kvstore_bucket``, ...).  None for a program from before
the counter (benchmark/setup_time.py)."""
import setup_time


def read(facts):
    return setup_time.dispatch_build_seconds(("load",))
