"""The six per-layer metrics that move ``setup_s`` (``import_s``,
``bind_s``, ``init_params_s``, ``init_optimizer_s``, ``program_trace_s``,
``program_load_s``): their entries, their readers against the program's
counters, and a traced rehearsal of two cells through
``benchmark/setup_breakdown.py``, which prints the run's own line and
the by-hand breakdown of its ``setup_s``.  CPU, rehearsal sizes; no
topology call, here or at import."""
import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
CELLS = ["cgpt13b_train_s2048", "resnet50_train_b256", "zaya1_8b_train_ep2",
         "qwen3next_80b_train_ep16", "kanana2_30b_train_ep8"]
NEW = {
    "import_s": ("program_counter", "package import (mxnet_tpu/__init__.py)"),
    "bind_s": ("program_span", "module set-up (module/module.py)"),
    "init_params_s": ("program_span", "module set-up (module/module.py)"),
    "init_optimizer_s": ("program_span", "module set-up (module/module.py)"),
    "program_trace_s": ("program_counter",
                        "program build (executor.py, aot/)"),
    "program_load_s": ("program_counter",
                       "program build (executor.py, aot/)"),
}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "setup_reader_" + name, os.path.join(BENCH, "layer_metrics",
                                             name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def readers(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(BENCH)
    monkeypatch.delitem(sys.modules, "setup_time", raising=False)
    return {name: _reader(name) for name in NEW}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_lists_the_five_cells_and_has_its_reader(name):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    rows = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(rows) == 1
    m = rows[0]
    assert m["moves"] == "setup_s" and m["unit"] == "s"
    assert m["better"] == "lower"
    assert (m["source"], m["layer"]) == NEW[name]
    assert set(CELLS) <= set(m["workloads"])    # later cells may join
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # the layer's name is the one the accepted entry of that layer has
    if name.startswith("program_"):
        accepted = [x for x in bench["per_layer"]
                    if x["name"] == "compile_cache_misses"][0]
        assert m["layer"] == accepted["layer"]


def _run(script, *args, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    env.pop("XLA_FLAGS", None)
    # the suite's processes run without the persistent cache
    env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, *script, *args], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    return lines, time.perf_counter() - t0


@pytest.mark.parametrize("cell", ["cgpt13b_train_s2048",
                                  "qwen3next_80b_train_ep16"])
def test_a_traced_rehearsal_prints_six_floats_under_the_process_age(cell):
    lines, age = _run([os.path.join(BENCH, "setup_breakdown.py")],
                      "--workload", cell, "--seed", "3000000019",
                      "--seconds", "0.5", "--trace", "1", "--rehearse")
    line, extra = lines[-2], lines[-1]["setup_breakdown"]
    assert line["correct"] is True
    got = {n: line["metrics"][n] for n in NEW}
    assert all(isinstance(v["value"], float) and v["unit"] == "s"
               for v in got.values()), got
    values = {n: v["value"] for n, v in got.items()}
    assert all(v >= 0.0 for v in values.values())
    for n in ("import_s", "bind_s", "init_params_s", "init_optimizer_s",
              "program_trace_s", "program_load_s"):
        assert values[n] > 0.0, n
    assert sum(values.values()) < age
    # the by-hand breakdown beside it: the same six, every second under
    # one name, and the names together no longer than the set-up
    for n in NEW:
        assert extra["named"][n] == pytest.approx(values[n])
    assert extra["first_steps"] == 5
    assert extra["setup_s"] + extra["reference_seconds"] < age
    assert sum(extra["named"].values()) <= extra["setup_s"] * 1.02
    assert extra["named"]["first_steps_s"] > 0
    assert extra["named"]["import_jax_s"] > 0
    assert extra["named"]["backend_init_s"] >= 0
    assert extra["counters"]["program_builds{site=fit_step}"] == 1


_NO_MODULE = """
import json, sys
sys.path[:0] = [%r, %r]
import mxnet_tpu
import importlib.util
out = {}
for name in %r:
    spec = importlib.util.spec_from_file_location(
        "r_" + name, %r + "/layer_metrics/" + name + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out[name] = mod.read({})
print(json.dumps(out))
"""


def test_a_process_that_built_no_module_reads_zero():
    lines, _ = _run(["-c", _NO_MODULE % (ROOT, BENCH, sorted(NEW), BENCH)])
    got = lines[-1]
    assert got.pop("import_s") > 0.0    # the package WAS imported
    assert got == {n: 0.0 for n in NEW if n != "import_s"}


def test_a_program_from_before_the_counters_gives_none(readers, monkeypatch):
    """The parent of the PR that brought them has neither counter: the
    readers return nothing and do not raise."""
    from mxnet_tpu import telemetry
    plain = telemetry.REGISTRY.get
    monkeypatch.setattr(
        telemetry.REGISTRY, "get",
        lambda name: None if name in ("setup_seconds",
                                      "program_build_seconds")
        else plain(name))
    assert {n: r.read({}) for n, r in readers.items()} \
        == {n: None for n in NEW}


def test_build_readers_count_the_dispatch_sites_alone(readers):
    """A build under a set-up span's name is inside that span's seconds
    and one under ``outside`` is the caller's: neither enters
    ``program_trace_s`` or ``program_load_s``; ``cache_read`` is a part
    of ``load`` and is not added to it."""
    from mxnet_tpu.aot import store
    before = {n: readers[n].read({}) for n in ("program_trace_s",
                                               "program_load_s")}
    seconds = store.PROGRAM_BUILD_SECONDS
    for site, phase, s in [("test_reader_site", "trace", 3.0),
                           ("test_reader_site", "lower", 0.5),
                           ("test_reader_site", "load", 2.0),
                           ("test_reader_site", "cache_read", 1.5),
                           ("module.bind", "trace", 5.0),
                           ("module.bind", "load", 5.0),
                           ("fit.build", "lower", 5.0),
                           ("outside", "trace", 7.0),
                           ("outside", "load", 7.0)]:
        seconds.labels(site=site, phase=phase).inc(s)
    assert readers["program_trace_s"].read({}) - before["program_trace_s"] \
        == pytest.approx(3.5)
    assert readers["program_load_s"].read({}) - before["program_load_s"] \
        == pytest.approx(2.0)
