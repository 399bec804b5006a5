"""Seconds inside ``Module.init_optimizer``, the program's span
``module.init_optimizer`` (``setup_seconds{phase="init_optimizer"}``):
kvstore creation, its init/pull round trip, the optimizer's state.
None for a program from before the span (benchmark/setup_time.py)."""
import setup_time


def read(facts):
    return setup_time.phase_seconds("init_optimizer")
