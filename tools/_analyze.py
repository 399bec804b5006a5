"""Load ``mxnet_tpu/analyze`` as the top-level package ``analyze``.

The static tools must never import ``mxnet_tpu`` itself (that imports
jax), so they take the analyzer sub-package alone — by path.  Putting
``mxnet_tpu/`` on ``sys.path`` instead would shadow the standard
library with the package's own modules: ``mxnet_tpu/operator.py``
answers ``from operator import or_`` (``enum``, Python 3.12) and dies
in a relative import.
"""
import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(ROOT, "mxnet_tpu", "analyze")

_spec = importlib.util.spec_from_file_location(
    "analyze", os.path.join(_PKG, "__init__.py"),
    submodule_search_locations=[_PKG])
analyze = importlib.util.module_from_spec(_spec)
sys.modules["analyze"] = analyze
_spec.loader.exec_module(analyze)
