"""Seconds inside ``Module.init_params``, the program's span
``module.init_params`` (``setup_seconds{phase="init_params"}``): every
parameter through the initializer, then into the executor.  None for a
program from before the span (benchmark/setup_time.py)."""
import setup_time


def read(facts):
    return setup_time.phase_seconds("init_params")
