"""The path a process walks once, measured from inside
(docs/OBSERVABILITY.md, "Spans on the profiler's clock"):

* set-up spans — ``module.bind``, ``module.init_params``,
  ``module.init_optimizer`` and ``fit.build`` are annotations in any
  running ``jax.profiler`` trace and keep their seconds in
  ``setup_seconds{phase}`` with recording off;
* build seconds by site — jax's own trace / lower / load events land in
  ``program_build_seconds{site, phase}`` under the innermost of a
  ``RetraceSite.timed`` call, an open set-up span, ``outside``; nested
  trace events count as their union; a warm cache shows as
  ``cache_read``;
* the steady path pays nothing — a span site that passes no counter
  builds no ``Span``, and steps after the first add 0.0 to every child.
"""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym, telemetry
from mxnet_tpu.aot import store
from mxnet_tpu.telemetry import tracing
from mxnet_tpu.telemetry.registry import BUILD_SITE, OUTSIDE, RetraceSite

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SETUP_SPANS = ["module.bind", "module.init_params", "module.init_optimizer",
               "fit.build"]
MODULE_PHASES = ["bind", "init_params", "init_optimizer", "fit_build"]


@pytest.fixture(autouse=True)
def _tracing_off_after():
    yield
    tracing.disable()
    tracing.clear()
    assert BUILD_SITE.name == OUTSIDE


def _children(name):
    """``{label values by label name: value}`` of a labelled counter."""
    return {tuple(sorted(zip(c.label_names, c.label_values))): c.value
            for c in telemetry.REGISTRY.get(name).children()}


def _build_seconds(site, phase):
    return _children("program_build_seconds").get(
        (("phase", phase), ("site", site)), 0.0)


def _builds(site):
    return _children("program_builds").get((("site", site),), 0)


def _phase_seconds():
    got = _children("setup_seconds")
    return {p: got.get((("phase", p),), 0.0)
            for p in MODULE_PHASES + ["import"]}


def _bind_init_step(hidden, initializer=None):
    """A module bound, initialised and stepped once; ``hidden`` keeps a
    test's programs its own (jax caches a traced function by shape)."""
    rng = np.random.RandomState(0)
    X = rng.rand(16, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.float32)
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=hidden,
                             name="fc1")
    net = sym.Activation(net, act_type="relu", name="act1")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(net, num_hidden=2, name="fc2"), name="softmax")
    mod = mx.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 8))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(initializer or mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="adam",
                       optimizer_params={"learning_rate": 0.05})
    batch = mx.io.DataBatch(data=[nd.array(X)], label=[nd.array(y)])
    metric = mx.metric.create("acc")
    assert mod.fit_step(batch, metric)
    metric.get()
    return mod, batch, metric


# ----------------------------------------------------------------------
# set-up spans: seconds with recording off, names in any trace
# ----------------------------------------------------------------------
def test_setup_phases_keep_their_seconds_with_recording_off():
    assert not tracing.enabled()
    before = _phase_seconds()
    assert before["import"] > 0         # the package was imported once
    t0 = time.perf_counter()
    _bind_init_step(hidden=9)
    wall = time.perf_counter() - t0
    after = _phase_seconds()
    spent = {p: after[p] - before[p] for p in MODULE_PHASES}
    assert all(v > 0 for v in spent.values()), spent
    assert sum(spent.values()) < wall
    assert after["import"] == before["import"]
    assert tracing.spans() == [] and tracing.current() is None


def test_setup_spans_are_in_any_device_trace(tmp_path):
    """The four names on the host plane of a trace taken round the same
    block, in the order a process walks them, recording off."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _bind_init_step(hidden=10)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events += [(e.start_ns, e.name) for e in line.events
                           if e.name in SETUP_SPANS]
    assert [name for _, name in sorted(events)] == SETUP_SPANS
    assert tracing.spans() == []


def test_recorded_setup_span_feeds_the_ring_and_its_counter():
    """Recording on: one ring record a span AND the counter, from one
    context; children opened in the body still nest under it."""
    tracing.enable()
    tracing.clear()
    seconds = tracing.SETUP_SECONDS.labels(phase="test_recorded")
    with tracing.span("t.setup", seconds_to=seconds) as outer:
        with tracing.span("t.child") as child:
            assert child.parent_id == outer.span_id
        time.sleep(0.01)
    recs = {s["name"]: s for s in tracing.spans()}
    assert set(recs) == {"t.setup", "t.child"}
    assert seconds.value == pytest.approx(recs["t.setup"]["dur_ms"] / 1e3,
                                          abs=1e-4)
    assert seconds.value >= 0.01


def test_a_site_that_passes_no_counter_builds_no_span():
    """The steady path's sites take the path they took: the bare
    annotation, no ``Span``, no clock read; with a counter and recording
    off the span exists for the counter alone."""
    assert not tracing.enabled()
    plain = tracing.span("fit.prepare")
    assert not isinstance(plain, tracing.Span)
    assert type(plain).__name__ == "_Annotation"
    counted = tracing.span(
        "t.once", seconds_to=tracing.SETUP_SECONDS.labels(phase="test_once"))
    assert isinstance(counted, tracing.Span) and counted.trace_id is None
    with counted:
        assert tracing.current() is None
    assert tracing.spans() == []


# ----------------------------------------------------------------------
# whose build it was
# ----------------------------------------------------------------------
def _fresh_program(tag):
    """A jitted function jax has not seen, and an argument for it (a
    host array: making a device array would be a build of its own)."""
    def fn(x):
        return jnp.tanh(x) * float(tag) + 1.0
    return jax.jit(fn), np.ones((int(tag),), np.float32)


def _through_a_dispatch_site():
    site = RetraceSite(telemetry.REGISTRY.counter("test_site_retraces"),
                       site="test_dispatch")
    fn, x = _fresh_program(101)
    site.timed(fn, x)
    return "test_dispatch"


def _inside_init_params():
    class Eager(mx.initializer.Xavier):
        def __call__(self, desc, arr):
            super().__call__(desc, arr)
            jnp.cumsum(jnp.ones((103,), jnp.float32)).block_until_ready()

    _bind_init_step(hidden=11, initializer=Eager())
    return "module.init_params"


def _bare_jit():
    fn, x = _fresh_program(107)
    fn(x)
    return OUTSIDE


@pytest.mark.parametrize("build", [_through_a_dispatch_site,
                                   _inside_init_params, _bare_jit])
def test_a_build_lands_under_the_innermost_open_site(build):
    sites = ["test_dispatch", "module.init_params", OUTSIDE]
    before = {s: (_builds(s), _build_seconds(s, "trace"),
                  _build_seconds(s, "lower"), _build_seconds(s, "load"))
              for s in sites}
    site = build()
    after = {s: (_builds(s), _build_seconds(s, "trace"),
                 _build_seconds(s, "lower"), _build_seconds(s, "load"))
             for s in sites}
    assert after[site][0] >= before[site][0] + 1
    assert all(a > b for a, b in zip(after[site][1:], before[site][1:]))
    if site == "test_dispatch":         # and nowhere else
        assert after[OUTSIDE] == before[OUTSIDE]
        assert after[site][0] == before[site][0] + 1


def test_the_site_is_the_innermost_and_is_restored():
    site = RetraceSite(telemetry.REGISTRY.counter("test_site_retraces"),
                       site="test_outer")
    seen = []

    def body():
        seen.append(BUILD_SITE.name)
        with tracing.span("t.inner", seconds_to=tracing.SETUP_SECONDS.labels(
                phase="test_inner")):
            seen.append(BUILD_SITE.name)
            with tracing.span("t.plain"):       # no counter: not a site
                seen.append(BUILD_SITE.name)
        seen.append(BUILD_SITE.name)
        raise RuntimeError("a failed dispatch")

    with pytest.raises(RuntimeError):
        site.timed(body)
    assert seen == ["test_outer", "t.inner", "t.inner", "test_outer"]
    assert BUILD_SITE.name == OUTSIDE
    other = []
    t = threading.Thread(target=lambda: other.append(BUILD_SITE.name))
    t.start()
    t.join(timeout=10)
    assert other == [OUTSIDE]           # a thread's own, never inherited


def test_nested_trace_events_count_as_their_union():
    """Hand-made events through the listener, on a thread of their own:
    jax reports the inner traces first, then the one around them, and a
    lowering that traced; the seconds added are the outermost
    intervals', not the sum."""
    trace = "/jax/core/compile/jaxpr_trace_duration"
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    added = {}

    def feed():
        BUILD_SITE.name = "test_union"
        t0 = {p: _build_seconds("test_union", p) for p in ("trace", "lower")}
        for ev, a, b in [(trace, 101.0, 102.0), (trace, 103.0, 105.0),
                         (trace, 103.5, 104.0),     # already inside
                         (trace, 100.0, 110.0),     # the outermost
                         (trace, 121.0, 123.0),     # traced by a lowering
                         (lower, 120.0, 126.0),
                         (trace, 130.0, 131.0)]:    # disjoint, later
            store._on_time_span(ev, a, b, fun_name="hand_made")
        store._on_time_span("/jax/some/other_duration", 0.0, 500.0)
        added.update({p: _build_seconds("test_union", p) - t0[p]
                      for p in t0})

    t = threading.Thread(target=feed)
    t.start()
    t.join(timeout=10)
    assert added["trace"] == pytest.approx(10.0 + 2.0 + 1.0)
    assert added["lower"] == pytest.approx(6.0 - 2.0)
    assert added["trace"] + added["lower"] == pytest.approx(17.0)
    assert _builds("test_union") == 0   # no load event, no executable


# ----------------------------------------------------------------------
# a warm cache directory: the same builds, read instead of compiled
# ----------------------------------------------------------------------
_PROCESS = """
import json
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import nd, sym, telemetry
net = sym.SoftmaxOutput(sym.FullyConnected(
    sym.Variable("data"), num_hidden=4, name="fc"), name="softmax")
mod = mx.Module(net, context=mx.cpu())
mod.bind(data_shapes=[("data", (8, 6))], label_shapes=[("softmax_label", (8,))])
mod.init_params(mx.initializer.Xavier())
mod.init_optimizer(kvstore="tpu", optimizer="adam")
batch = mx.io.DataBatch(data=[nd.array(np.ones((8, 6), np.float32))],
                        label=[nd.array(np.zeros((8,), np.float32))])
assert mod.fit_step(batch, mx.metric.create("acc"))
snap = telemetry.REGISTRY.snapshot()
by = lambda name, key: sum(v for k, v in snap.items()
                           if k.startswith(name + "{") and key in k)
print(json.dumps({
    "hits": snap["aot_cache_hits"], "misses": snap["aot_cache_misses"],
    "builds": by("program_builds", "site="),
    "fit_step_builds": by("program_builds", "site=fit_step"),
    "cache_read": by("program_build_seconds", "phase=cache_read"),
    "trace": by("program_build_seconds", "phase=trace"),
    "load": by("program_build_seconds", "phase=load")}))
"""


def test_a_warm_cache_shows_as_cache_read_with_the_builds_equal(tmp_path):
    def process():
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
        proc = subprocess.run([sys.executable, "-c", _PROCESS], env=env,
                              capture_output=True, text=True, timeout=300,
                              cwd=ROOT)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold, warm = process(), process()
    # (a cold process may hit too: two of its small programs can be
    # one executable)
    assert cold["misses"] > 0 and warm["misses"] == 0
    assert warm["hits"] == cold["hits"] + cold["misses"]
    assert warm["cache_read"] > cold["cache_read"] >= 0.0
    assert warm["cache_read"] <= warm["load"]       # a part of load
    assert warm["builds"] == cold["builds"] > 0
    assert warm["fit_step_builds"] == cold["fit_step_builds"] == 1
    assert warm["trace"] > 0 and cold["trace"] > 0  # no cache removes it


_NO_CACHE_PROCESS = """
import json
import jax, jax.numpy as jnp
from mxnet_tpu import telemetry
from mxnet_tpu.aot import store
jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))
snap = telemetry.REGISTRY.snapshot()
print(json.dumps({"dir": store._STATE["dir"], "builds": sum(
    v for k, v in snap.items() if k.startswith("program_builds{"))}))
"""


def test_builds_are_counted_where_the_cache_cannot_be_on(tmp_path):
    """A cache directory that cannot be created leaves the cache off
    (a read-only checkout must still import); the build listener is no
    part of the cache and listens anyway."""
    (tmp_path / "a_file").write_text("")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "a_file" / "cache"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _NO_CACHE_PROCESS], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["dir"] is None and got["builds"] >= 1


# ----------------------------------------------------------------------
# the steady path
# ----------------------------------------------------------------------
def test_steps_after_the_first_build_nothing_and_record_nothing():
    from mxnet_tpu.module import fused_fit
    mod, batch, metric = _bind_init_step(hidden=12)
    seconds = _children("program_build_seconds")
    builds = _children("program_builds")
    setup = _phase_seconds()
    traced = fused_fit.TRACE_COUNT
    dispatches = telemetry.REGISTRY.get("device_dispatches")
    d0 = dispatches.value
    for _ in range(10):
        assert mod.fit_step(batch, metric)
    assert _children("program_build_seconds") == seconds
    assert _children("program_builds") == builds
    assert _phase_seconds() == setup
    assert fused_fit.TRACE_COUNT == traced
    assert dispatches.value - d0 == 10
    assert not tracing.enabled() and tracing.spans() == []
