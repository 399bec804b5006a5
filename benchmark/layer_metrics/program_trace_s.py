"""Seconds of Python that no cache removes: jax's tracing and lowering
of the programs the dispatch sites built
(``program_build_seconds{phase in trace, lower}`` over ``fit_step``,
``executor``, ``kvstore_bucket``, ...; a thread's nested trace events
counted as their union).  None for a program from before the counter
(benchmark/setup_time.py)."""
import setup_time


def read(facts):
    return setup_time.dispatch_build_seconds(("trace", "lower"))
