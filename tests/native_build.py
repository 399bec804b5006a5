"""Shared native-build helpers for the C predict API / C++ wrapper
tests (plain module: no dependency on pytest's conftest import mode)."""
import os
import subprocess
import sys

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def build_native_lib():
    """make -C src; returns the libmxtpu_predict.so path."""
    r = subprocess.run(["make", "-C", os.path.join(_ROOT, "src")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    lib = os.path.join(_ROOT, "mxnet_tpu", "lib", "libmxtpu_predict.so")
    assert os.path.exists(lib)
    return lib


def compile_against_predict_lib(sources, exe, lang="c"):
    """Compile a C/C++ consumer against include/ + libmxtpu_predict.so
    with an rpath so it runs in place."""
    lib = build_native_lib()
    cc = ["gcc", "-O2"] if lang == "c" else ["g++", "-std=c++17", "-O2"]
    r = subprocess.run(
        cc + ["-o", exe] + list(sources)
        + ["-I", os.path.join(_ROOT, "include"), lib,
           "-Wl,-rpath," + os.path.dirname(lib)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    return exe


def predict_subprocess_env():
    """Env for running embedded-interpreter consumers: cpu platform +
    PYTHONPATH reaching mxnet_tpu and its dependencies."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [_ROOT] + [p for p in sys.path
                   if "site-packages" in p or "dist-packages" in p])
    return env
