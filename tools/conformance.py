#!/usr/bin/env python
"""Run the REFERENCE's own python unittest corpus against mxnet_tpu.

The reference's tests (tests/python/unittest/*.py) are the largest parity
oracle that exists for this API, so we execute them verbatim — copied to a
temp dir at run time, never into the repo — against this framework through
an import shim (``import mxnet`` -> ``mxnet_tpu``).  Results are scored
into docs/CONFORMANCE.md by tools/conformance_report.py.

Mechanics:
  * the reference unittest/ + common/ dirs are copied to a tmpdir so their
    relative-path sys.path dances still resolve (but the reference's own
    python/mxnet never shadows ours — that path doesn't exist in the copy)
  * a conftest.py written into the tmpdir installs:
      - a meta-path alias: any ``mxnet[.sub]`` import resolves to
        ``mxnet_tpu[.sub]``
      - a minimal ``nose``/``nose.tools`` stand-in (nose is dead on 3.12)
  * a skiplist (tools/conformance_skips.py) marks tests that are
    out-of-scope by design (GPU-only, engine internals, ...) with reasons;
    everything else must pass or is a triage item.

Usage:
  python tools/conformance.py test_ndarray [test_module ...] [-k EXPR]
  python tools/conformance.py --all        # the four headline files
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.environ.get("CONFORMANCE_REFERENCE", "/root/reference")
HEADLINE = ["test_ndarray", "test_module", "test_gluon", "test_operator"]

_CONFTEST = '''
import importlib
import importlib.abc
import importlib.machinery
import sys
import types

sys.path.insert(0, {repo!r})

# ---- minimal nose stand-in (referenced by common.py and the tests) ----
def _make_nose():
    nose = types.ModuleType("nose")
    tools = types.ModuleType("nose.tools")

    def make_decorator(func):
        def wrap(new):
            new.__name__ = func.__name__
            new.__dict__.update(func.__dict__)
            new.__doc__ = func.__doc__
            return new
        return wrap

    def assert_raises(exc, func=None, *args, **kwargs):
        import pytest
        if func is None:
            return pytest.raises(exc)
        with pytest.raises(exc):
            func(*args, **kwargs)

    def raises(*excs):
        import functools
        def deco(func):
            @functools.wraps(func)
            def inner(*a, **kw):
                import pytest
                with pytest.raises(excs):
                    return func(*a, **kw)
            return inner
        return deco

    tools.make_decorator = make_decorator
    tools.assert_raises = assert_raises
    tools.raises = raises
    tools.ok_ = lambda expr, msg=None: None if expr else (_ for _ in ()).throw(AssertionError(msg))
    tools.eq_ = lambda a, b, msg=None: None if a == b else (_ for _ in ()).throw(AssertionError(msg or f"{{a!r}} != {{b!r}}"))
    nose.tools = tools
    sys.modules["nose"] = nose
    sys.modules["nose.tools"] = tools

_make_nose()

# ---- `mxnet` -> `mxnet_tpu` meta-path alias ----
class _MxAliasFinder(importlib.abc.MetaPathFinder, importlib.abc.Loader):
    PREFIX = "mxnet"

    def find_spec(self, fullname, path=None, target=None):
        if fullname == self.PREFIX or fullname.startswith(self.PREFIX + "."):
            real = "mxnet_tpu" + fullname[len(self.PREFIX):]
            try:
                importlib.import_module(real)
            except ImportError:
                return None
            return importlib.machinery.ModuleSpec(fullname, self,
                                                  is_package=True)
        return None

    def create_module(self, spec):
        real = "mxnet_tpu" + spec.name[len(self.PREFIX):]
        return sys.modules[real]

    def exec_module(self, module):
        pass

sys.modules.setdefault("mxnet", importlib.import_module("mxnet_tpu"))
sys.meta_path.insert(0, _MxAliasFinder())

# numeric-parity tests assume fp32 accumulation; CPU XLA may otherwise
# drop matmuls to bf16 (same setting as the repo's own tests/conftest.py)
import jax
jax.config.update("jax_default_matmul_precision", "float32")

# ---- skiplist -> pytest collection hook ----
sys.path.insert(0, {tools_dir!r})
from conformance_skips import SKIPS

import pytest

def pytest_collection_modifyitems(config, items):
    for item in items:
        base = item.nodeid.rsplit("::", 1)[-1].split("[")[0]
        fname = item.nodeid.split("::")[0].rsplit("/", 1)[-1]
        reason = SKIPS.get((fname, base)) or SKIPS.get(("*", base))
        if reason:
            item.add_marker(pytest.mark.skip(reason=reason))
'''


def stage(tmp):
    """Copy the reference test tree into tmp and write the shim conftest."""
    unit_src = os.path.join(REFERENCE, "tests", "python", "unittest")
    common_src = os.path.join(REFERENCE, "tests", "python", "common")
    unit_dst = os.path.join(tmp, "tests", "python", "unittest")
    shutil.copytree(unit_src, unit_dst)
    shutil.copytree(common_src, os.path.join(tmp, "tests", "python", "common"))
    with open(os.path.join(unit_dst, "conftest.py"), "w") as f:
        f.write(_CONFTEST.format(repo=REPO,
                                 tools_dir=os.path.join(REPO, "tools")))
    # pytest must not pick up the repo's own conftest/ini
    with open(os.path.join(tmp, "pytest.ini"), "w") as f:
        f.write("[pytest]\naddopts = -p no:cacheprovider\n")
    return unit_dst


def run_file(unit_dst, name, extra):
    path = os.path.join(unit_dst, name + ".py")
    cmd = [sys.executable, "-m", "pytest", path, "-q", "--tb=line",
           "--continue-on-collection-errors", "-rf"] + extra
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_ENFORCE_DETERMINISM="0")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(cmd, cwd=os.path.dirname(path),
                          capture_output=True, text=True)
    tail = proc.stdout[-8000:]
    m = re.search(r"(\d+) passed", tail)
    passed = int(m.group(1)) if m else 0
    m = re.search(r"(\d+) failed", tail)
    failed = int(m.group(1)) if m else 0
    m = re.search(r"(\d+) skipped", tail)
    skipped = int(m.group(1)) if m else 0
    m = re.search(r"(\d+) error", tail)
    errors = int(m.group(1)) if m else 0
    fails = re.findall(r"^FAILED (\S+)", tail, re.M)
    return {"file": name, "passed": passed, "failed": failed,
            "skipped": skipped, "errors": errors, "failures": fails,
            "stdout_tail": tail[-4000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="*")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("-k", default=None)
    ap.add_argument("--json", default=None, help="write results JSON here")
    ap.add_argument("--tb", default="line")
    args = ap.parse_args()
    names = HEADLINE if args.all else args.files
    if not names:
        ap.error("give test file basenames or --all")
    extra = []
    if args.k:
        extra += ["-k", args.k]
    if args.tb != "line":
        extra += [f"--tb={args.tb}"]

    results = []
    with tempfile.TemporaryDirectory(prefix="mxtpu-conformance-") as tmp:
        unit_dst = stage(tmp)
        for name in names:
            res = run_file(unit_dst, name, extra)
            results.append(res)
            print(f"{name}: {res['passed']} passed, {res['failed']} failed, "
                  f"{res['skipped']} skipped, {res['errors']} errors")
            for f in res["failures"]:
                print(f"  FAILED {f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
