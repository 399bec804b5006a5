"""The program's names in a ``jax.profiler`` trace, with no switch
thrown (docs/OBSERVABILITY.md, "Spans on the profiler's clock" and
"Scope names"):

* host spans — ``telemetry.tracing.span`` sites are
  ``TraceAnnotation``s on the host plane of any running device trace
  while ``tracing.enabled()`` is false and the ring stays empty;
* scope names — every compiled step carries ``<op class>/<node name>``
  per graph node, ``fit.update`` / ``fit.metric`` / ``fit.sentinel``
  in the fit program and ``pallas.<kernel>`` around each kernel, in
  the ``op_name`` metadata a device trace reports as ``tf_op``;
* one copy — a span that ended while ``mx.profiler`` ran is in
  ``profile.json`` once, and one dispatch context feeds the
  annotation, the ring and ``profile_symbolic``'s host event.
"""
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.telemetry import tracing


@pytest.fixture(autouse=True)
def _tracing_off_after():
    yield
    tracing.disable()
    tracing.clear()


def _module(batch=16):
    rng = np.random.RandomState(0)
    X = rng.rand(batch, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.float32)
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=8, name="fc1")
    net = sym.Activation(net, act_type="relu", name="act1")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(net, num_hidden=2, name="fc2"), name="softmax")
    mod = mx.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 8))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    return mod, mx.io.DataBatch(data=[nd.array(X)], label=[nd.array(y)])


class _Trace:
    """A ``jax.profiler`` trace around a block; ``events(names)`` gives
    the host plane's ``(name, start_ns, end_ns)`` in start order."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "trace")

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def events(self, names):
        from jax.profiler import ProfileData
        path = glob.glob(self.dir + "/**/*.xplane.pb", recursive=True)[0]
        out = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name in names]
        return sorted(out, key=lambda e: e[1])


STEP_SPANS = ["fit.prepare", "fit.fused_dispatch", "fit.rebind",
              "metric.readback"]


def test_fit_step_spans_are_in_any_device_trace(tmp_path):
    """Two fit steps, each read back: the four host spans of the step
    are on the trace's host plane, one after the other, while tracing
    is disabled and the ring records nothing."""
    mod, batch = _module()
    metric = mx.metric.create("acc")
    assert mod.fit_step(batch, metric)          # compile outside
    metric.get()
    metric.reset()
    assert not tracing.enabled()
    with _Trace(tmp_path) as tr:
        for _ in range(2):
            assert mod.fit_step(batch, metric)
            mod.update_metric(metric, batch.label)
            metric.get()
            metric.reset()
    ev = tr.events(STEP_SPANS)
    assert [e[0] for e in ev] == STEP_SPANS * 2
    for (_, _, end), (_, start, _) in zip(ev, ev[1:]):
        assert end <= start                     # in order, not overlapping
    assert all(end > start for _, start, end in ev)
    assert tracing.spans() == [] and tracing.current() is None


def test_enabled_span_is_annotation_and_ring_record(tmp_path):
    """Enabled, the same context feeds both: the annotation in the
    trace and ONE record in the ring, nesting kept."""
    tracing.enable()
    tracing.clear()
    with _Trace(tmp_path) as tr:
        with tracing.span("t.outer", k=1) as outer:
            with tracing.span("t.inner") as inner:
                assert inner.parent_id == outer.span_id
            # the cross-thread form is ring-only
            tracing.start_span("t.request").end()
    names = [e[0] for e in tr.events({"t.outer", "t.inner", "t.request"})]
    assert names == ["t.outer", "t.inner"]
    assert sorted(s["name"] for s in tracing.spans()) == [
        "t.inner", "t.outer", "t.request"]


def _lowered_op_names(fn, *args):
    text = fn.lower(*args).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def test_fit_program_carries_scope_names():
    """The lowered fit program's ``op_name``s: the three regions of the
    step, and per graph node the operator's class then the node's name,
    forward (``jvp``) and backward (``transpose(jvp)``)."""
    mod, batch = _module()
    metric = mx.metric.create("acc")
    assert mod.fit_step(batch, metric)
    fn, args = mod._fused_fit._prepare(batch, metric)[:2]
    names = _lowered_op_names(fn, *args)
    assert any("/fit.update/" in n for n in names)
    assert any("/fit.metric/" in n for n in names)
    assert "jit(step)/jvp(FullyConnected)/fc1/dot_general" in names
    assert "jit(step)/transpose(jvp(FullyConnected))/fc2/dot_general" \
        in names
    assert any(n.startswith("jit(step)/jvp(SoftmaxOutput)/softmax/")
               for n in names)
    # every instruction of the graph sits under some node's scope
    graph = [n for n in names if n.startswith("jit(step)/")
             and "/fit." not in n]
    assert graph and all(re.match(
        r"jit\(step\)/(transpose\()?jvp\((FullyConnected|Activation|"
        r"SoftmaxOutput)\)+/(fc1|act1|fc2|softmax)/", n) for n in graph)


def test_sentinel_and_scaler_regions_are_scoped(monkeypatch):
    """A bfloat16 language model with the sentinel on: ``fit.sentinel``
    around the witness block, and ``fit.update`` around the loss
    scaler's ``cond`` and what sits under it."""
    from mxnet_tpu.models import transformer
    from mxnet_tpu.module import fused_fit
    monkeypatch.setattr(fused_fit, "_sentinel_enabled", lambda: True)
    net = transformer.get_symbol(num_classes=50, num_layers=1, d_model=16,
                                 num_heads=2, seq_len=8, dtype="bfloat16")
    mod = mx.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 8))],
             label_shapes=[("softmax_label", (16,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="adam", optimizer_params={
        "learning_rate": 1e-3, "multi_precision": True})
    rng = np.random.RandomState(1)
    batch = mx.io.DataBatch(
        data=[nd.array(rng.randint(0, 50, (2, 8)).astype(np.float32))],
        label=[nd.array(rng.randint(0, 50, (16,)).astype(np.float32))])
    metric = mx.metric.create("ce")
    assert mod.fit_step(batch, metric)
    assert mod._fused_fit._scaler is not None
    fn, args = mod._fused_fit._prepare(batch, metric)[:2]
    names = _lowered_op_names(fn, *args)
    assert any("/fit.sentinel/" in n for n in names)
    assert "jit(step)/fit.update/cond" in names
    assert any(n.startswith("jit(step)/fit.update/cond/") for n in names)
    assert any(n.startswith("jit(step)/jvp(LayerNorm)/") for n in names)
    assert any(n.startswith("jit(step)/transpose(jvp(Embedding))/")
               for n in names)


def test_executor_programs_carry_node_scopes():
    """The unfused path and the engine's step are built from the same
    node loop: forward-only programs read ``<op class>/<node name>``."""
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4, name="fc")
    exe = net.simple_bind(mx.cpu(), data=(2, 3))
    names = _lowered_op_names(exe._jit_fwd_eval, exe._args_values(),
                              exe._auxs_values(), exe._next_seed())
    assert "jit(_fwd_eval)/FullyConnected/fc/dot_general" in names


def test_pallas_layernorm_carries_kernel_scope():
    """A forced-interpret LayerNorm kernel sits under
    ``pallas.<kernel>``, the label ``PALLAS_LAUNCHES`` counts it by,
    forward and (under ``transpose``) backward."""
    from mxnet_tpu.pallas.layernorm import layernorm_fused
    x = jnp.ones((16, 128), jnp.float32)
    g = jnp.ones((128,), jnp.float32)

    def loss(x, g, b):
        return layernorm_fused(x, g, b, interpret=True)[0].sum()

    names = _lowered_op_names(jax.jit(jax.grad(loss)), x, g, g)
    assert any("pallas.layernorm_fused)" in n or "pallas.layernorm_fused/"
               in n for n in names)
    assert any("transpose(" in n and "pallas.layernorm_fused_bwd" in n
               for n in names)


def test_profiler_dump_holds_each_span_once(tmp_path):
    """A span that ended while ``mx.profiler`` ran is in
    ``profile.json`` once (the dump-time path alone carries the ring),
    and the dispatch context's ``profile_symbolic`` event is there once
    under the reference's name, beside the dotted span."""
    from mxnet_tpu import profiler
    tracing.enable()
    tracing.clear()
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4, name="fc")
    exe = net.simple_bind(mx.cpu(), data=(2, 3))
    exe.forward(is_train=False)                 # compile outside
    tracing.clear()
    path = str(tmp_path / "prof.json")
    profiler.set_config(filename=path)
    profiler.set_state("run")
    try:
        with tracing.span("g.step"):
            exe.forward(is_train=False)
    finally:
        profiler.set_state("stop")
    profiler.dump()
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert names.count("g.step") == 1
    assert names.count("executor.forward") == 1
    assert names.count("Executor::forward") == 1


def test_io_data_wait_is_a_context_span(tmp_path):
    """The prefetch queue's wait opens and ends on one thread: the
    context form, so a device trace shows it."""
    X = np.zeros((8, 4), np.float32)
    it = mx.io.PrefetchingIter(mx.io.NDArrayIter(X, np.zeros(8), 4))
    with _Trace(tmp_path) as tr:
        it.next()
    assert [e[0] for e in tr.events({"io.data_wait"})] == ["io.data_wait"]


def test_engine_loop_spans_are_in_a_device_trace(tmp_path):
    """A traced run of four requests shows the engine's loop on the
    profiler's clock: ``decode.tick`` with ``decode.admit``,
    ``decode.step`` and ``decode.emit`` inside, tracing disabled."""
    from mxnet_tpu.decode import DecodeEngine
    from mxnet_tpu.models import transformer
    cfg = dict(num_classes=50, num_layers=1, d_model=16, num_heads=2,
               seq_len=32)
    tsym = transformer.get_symbol(**cfg)
    arg_shapes, _, _ = tsym.infer_shape(data=(1, 32), softmax_label=(32,))
    rng = np.random.RandomState(7)
    params = {n: rng.normal(0, 0.1, s).astype(np.float32)
              for n, s in zip(tsym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    eng = DecodeEngine(params, cfg, capacity=2, block_size=4,
                       num_blocks=16, chunk_tokens=8, warmup=True)
    try:
        with _Trace(tmp_path) as tr:
            handles = [eng.submit([1, 2, 3], max_new_tokens=4)
                       for _ in range(4)]
            for h in handles:
                h.result(timeout=120)
        assert eng.stats()["dispatches_per_step"] == 1.0
    finally:
        eng.stop()
    inner = {"decode.admit", "decode.step", "decode.emit"}
    ev = tr.events(inner | {"decode.tick"})
    ticks = [e for e in ev if e[0] == "decode.tick"]
    assert ticks and sum(e[0] == "decode.admit" for e in ev) == 4
    steps = [e for e in ev if e[0] == "decode.step"]
    assert steps and len(steps) == sum(e[0] == "decode.emit" for e in ev)
    for name, start, end in ev:                 # each inside some tick
        if name in inner:
            assert any(t0 <= start and end <= t1 for _, t0, t1 in ticks)
    assert tracing.spans() == []
