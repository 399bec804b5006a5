"""Seconds inside ``Module.bind``, the program's span ``module.bind``
(``setup_seconds{phase="bind"}``): shape inference and the executor's
argument, gradient and auxiliary arrays.  None for a program from
before the span (benchmark/setup_time.py)."""
import setup_time


def read(facts):
    return setup_time.phase_seconds("bind")
