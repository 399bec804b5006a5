"""Seconds of the package's own import, ``mxnet_tpu/__init__.py`` first line
to last (``setup_seconds{phase="import"}``); jax's import is in it only
where the process had not imported jax before.  None for a program from
before the counter (benchmark/setup_time.py)."""
import setup_time


def read(facts):
    return setup_time.phase_seconds("import")
