"""mx.trace: spans, traceparent, exports, program registry, pod health.

Covers the PR 8 contract (docs/OBSERVABILITY.md):

* span API — parent/child linkage (thread-local nesting + explicit
  cross-thread parents), W3C traceparent round trip, bounded ring;
* export round trips — flight-recorder dump carries ``{"span": ...}``
  lines and the program top-K, profiler dumps carry span ``X`` events;
* the OVERHEAD GUARD — with tracing enabled, the fused fit step stays
  at zero steady-state retraces and exactly one device dispatch per
  step, and the decode engine stays at ``dispatches_per_step == 1.0``
  with zero steady retraces (spans bracket host dispatch only);
* acceptance — one ``POST /generate`` under tracing produces a single
  CONNECTED trace: http span → scheduler → prefill → ≥1 decode-
  iteration spans, visible in both flight and chrome exports;
* compiled-program registry — every live jit site reports nonzero
  compiler FLOPs/bytes;
* pod health — straggler detector (single-process world: the exchange
  is an identity and never flags) and the hang watchdog.
"""
import json
import os
import sys
import tempfile
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym, telemetry
from mxnet_tpu import metric as metric_mod
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracing_off_after():
    yield
    tracing.disable()
    tracing.clear()


# ----------------------------------------------------------------------
# span API
# ----------------------------------------------------------------------
def test_span_disabled_is_noop():
    """Disabled, the context form is a bare profiler annotation (a
    running jax.profiler trace shows it) that answers the span protocol
    with no-ops; nothing is recorded, no id is minted."""
    assert not tracing.enabled()
    sp = tracing.span("x.y", k=1)
    assert not isinstance(sp, tracing.Span)
    assert sp.context is None and sp.trace_id is None
    assert sp.set(a=1) is sp and sp.end() is sp
    with sp:
        assert tracing.current() is None
    assert tracing.start_span("x.z") is tracing.NULL_SPAN
    assert tracing.spans() == []


def test_span_parent_child_linkage_thread_local():
    tracing.enable()
    tracing.clear()
    with tracing.span("a.root", k=1) as root:
        rid = root.span_id
        with tracing.span("a.child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == rid
            with tracing.span("a.grandchild") as gc:
                assert gc.parent_id == child.span_id
    recs = tracing.spans()
    names = [r["name"] for r in recs]
    # children end (and record) before parents
    assert names == ["a.grandchild", "a.child", "a.root"]
    assert recs[-1]["parent_id"] is None
    assert recs[-1]["attrs"] == {"k": 1}
    assert all(r["trace_id"] == recs[-1]["trace_id"] for r in recs)
    # find_trace returns parents before children
    ordered = tracing.find_trace(recs[-1]["trace_id"])
    assert [r["name"] for r in ordered] == ["a.root", "a.child",
                                            "a.grandchild"]


def test_span_explicit_cross_thread_parent():
    tracing.enable()
    tracing.clear()
    parent = tracing.start_span("b.request")
    ctx = parent.context
    child = tracing.start_span("b.worker", parent=ctx, slot=3)
    child.end()
    parent.end(outcome="ok")
    recs = tracing.spans()
    assert recs[0]["parent_id"] == parent.span_id
    assert recs[0]["attrs"]["slot"] == 3
    assert recs[1]["attrs"]["outcome"] == "ok"
    # end() is idempotent
    parent.end()
    assert len(tracing.spans()) == 2


def test_traceparent_round_trip_and_malformed():
    tracing.enable()
    sp = tracing.start_span("c.x")
    header = tracing.traceparent(sp)
    ctx = tracing.extract(header)
    assert ctx.trace_id == sp.trace_id and ctx.span_id == sp.span_id
    assert tracing.extract({"traceparent": header}).trace_id == sp.trace_id
    sp.end()
    for bad in (None, "", "garbage", "00-zz-yy-01", "00-1234-5678-01",
                "00-%s-%s-01" % ("0" * 32, "0" * 16), {}):
        assert tracing.extract(bad) is None


def test_span_ring_is_bounded():
    tracing.enable()
    tracing.clear()
    d0 = telemetry.REGISTRY.get("trace_spans_dropped").value
    for i in range(tracing.SPAN_CAPACITY + 10):
        tracing.start_span("d.x").end()
    assert len(tracing.spans()) == tracing.SPAN_CAPACITY
    assert telemetry.REGISTRY.get("trace_spans_dropped").value - d0 == 10


# ----------------------------------------------------------------------
# export round trips
# ----------------------------------------------------------------------
def test_flight_dump_carries_spans(tmp_path):
    tracing.enable()
    tracing.clear()
    with tracing.span("e.step", step=7):
        pass
    rec = telemetry.FlightRecorder(capacity=8)
    path = str(tmp_path / "flight.jsonl")
    rec.install(path, every=1)
    rec.tick()
    rec.dump()
    lines = [json.loads(l) for l in open(path)]
    spans = [l["span"] for l in lines if "span" in l]
    assert any(s["name"] == "e.step" and s["attrs"]["step"] == 7
               for s in spans)
    # metric samples still follow, final last (the PR 4 contract)
    assert lines[-1].get("final") and "metrics" in lines[-1]


def test_chrome_events_carry_ids():
    tracing.enable()
    tracing.clear()
    with tracing.span("f.outer"):
        with tracing.span("f.inner"):
            time.sleep(0.002)
    evs = tracing.chrome_events()
    assert {e["name"] for e in evs} == {"f.outer", "f.inner"}
    for e in evs:
        assert e["ph"] == "X" and e["cat"] == "trace"
        assert e["args"]["trace_id"] and e["args"]["span_id"]
    inner = next(e for e in evs if e["name"] == "f.inner")
    outer = next(e for e in evs if e["name"] == "f.outer")
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert outer["dur"] >= inner["dur"] > 0


def test_profiler_dump_includes_trace_spans(tmp_path):
    from mxnet_tpu import profiler
    tracing.enable()
    tracing.clear()
    path = str(tmp_path / "prof.json")
    profiler.set_config(filename=path)
    profiler.set_state("run")
    try:
        with profiler.scope("work"):
            with tracing.span("g.step"):
                pass
    finally:
        profiler.set_state("stop")
    profiler.dump()
    doc = json.load(open(path))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "g.step" in names
    ev = next(e for e in doc["traceEvents"] if e["name"] == "g.step")
    assert ev["args"]["trace_id"]


# ----------------------------------------------------------------------
# overhead guard: tracing adds zero retraces / zero extra dispatches
# ----------------------------------------------------------------------
def _fit_module(batch=16):
    rng = np.random.RandomState(0)
    X = rng.rand(batch, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.float32)
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=2, name="fc"),
        name="softmax")
    mod = mx.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 8))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    return mod, mx.io.DataBatch(data=[nd.array(X)], label=[nd.array(y)])


def _program_build_seconds():
    return {c.label_values: c.value for c in
            telemetry.REGISTRY.get("program_build_seconds").children()}


def test_tracing_overhead_guard_fused_fit():
    """Tracing ON must be free where it matters: zero steady-state
    retraces and exactly one device launch per fused fit step."""
    tracing.enable()
    mod, batch_nd = _fit_module()
    m = metric_mod.Accuracy()
    assert mod.fit_step(batch_nd, m)          # first step traces
    from mxnet_tpu.module import fused_fit
    traced = fused_fit.TRACE_COUNT
    disp = telemetry.REGISTRY.get("device_dispatches")
    d0 = disp.value
    built = _program_build_seconds()
    for _ in range(4):
        assert mod.fit_step(batch_nd, m)
    assert fused_fit.TRACE_COUNT == traced, \
        "tracing instrumentation caused a fused-step retrace"
    assert disp.value - d0 == 4               # one launch per step
    # nothing traced, lowered or loaded after the first step: the
    # steady path adds 0.0 to every child (tests/test_setup_spans.py)
    assert _program_build_seconds() == built
    assert any(s["name"] == "fit.fused_dispatch"
               for s in tracing.spans())


def test_tracing_overhead_guard_decode():
    """Decode under tracing: dispatches_per_step stays 1.0 and the
    steady-state retrace witness stays 0."""
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
    tracing.enable()
    eng = DecodeEngine(params, cfg, capacity=2, block_size=4,
                       num_blocks=16, chunk_tokens=8, warmup=True)
    try:
        handles = [eng.submit([1, 2, 3], max_new_tokens=6)
                   for _ in range(3)]
        for h in handles:
            h.result(timeout=120)
        stats = eng.stats()
        assert stats["steady_state_retraces"] == 0
        assert stats["dispatches_per_step"] == 1.0
        names = {s["name"] for s in tracing.spans()}
        assert {"decode.request", "decode.queued", "decode.prefill",
                "decode.iteration"} <= names
    finally:
        eng.stop()


# ----------------------------------------------------------------------
# acceptance: one /generate = one connected trace
# ----------------------------------------------------------------------
def test_generate_single_connected_trace(tmp_path):
    import http.client
    from mxnet_tpu.decode import DecodeEngine
    from mxnet_tpu.models import transformer
    from mxnet_tpu.serving import ModelServer

    cfg = dict(num_classes=50, num_layers=1, d_model=16, num_heads=2,
               seq_len=32)
    tsym = transformer.get_symbol(**cfg)
    arg_shapes, _, _ = tsym.infer_shape(data=(1, 32), softmax_label=(32,))
    rng = np.random.RandomState(3)
    params = {n: nd.array(rng.normal(0, 0.1, s).astype(np.float32))
              for n, s in zip(tsym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    tracing.enable()
    tracing.clear()
    eng = DecodeEngine(params, cfg, capacity=2, block_size=4,
                       num_blocks=16, chunk_tokens=8, warmup=True)
    srv = ModelServer(tsym, params, {}, input_shapes={"data": (32,)},
                      num_replicas=1, warmup=False, decode_engine=eng)
    try:
        host, port = srv.start_http(port=0)
        conn = http.client.HTTPConnection(host, port, timeout=120)
        trace_id, span_id = "ab" * 16, "cd" * 8
        conn.request(
            "POST", "/generate",
            json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 4}),
            {"Content-Type": "application/json",
             "traceparent": "00-%s-%s-01" % (trace_id, span_id)})
        resp = conn.getresponse()
        lines = resp.read().decode().strip().splitlines()
        assert resp.status == 200
        assert json.loads(lines[-1])["done"]
        eng.drain(30)
    finally:
        srv.stop()
        eng.stop()

    trace = tracing.find_trace(trace_id)
    names = [s["name"] for s in trace]
    assert names[0] == "http.generate"        # joined the caller's trace
    assert "decode.request" in names
    assert "decode.prefill" in names
    assert sum(1 for n in names if n == "decode.iteration") >= 1
    # CONNECTED: every span's parent is the remote caller's span or
    # another span of this trace
    ids = {s["span_id"] for s in trace}
    for s in trace:
        assert s["parent_id"] in ids or s["parent_id"] == span_id, s
    # both exports carry the trace
    rec = telemetry.FlightRecorder(capacity=8)
    path = str(tmp_path / "f.jsonl")
    rec.install(path, every=1)
    rec.dump()
    flight_spans = [json.loads(l)["span"] for l in open(path)
                    if "span" in json.loads(l)]
    assert any(s["trace_id"] == trace_id for s in flight_spans)
    assert any(e["args"]["trace_id"] == trace_id
               for e in tracing.chrome_events())


# ----------------------------------------------------------------------
# compiled-program registry
# ----------------------------------------------------------------------
def test_program_registry_lists_live_jit_sites():
    # hermetic view: earlier test files legitimately register programs
    # XLA costs at 0 FLOPs (tiny copy/elementwise graphs in
    # test_operator), which would trip the blanket flops>0 assertion
    # below — this test is about the sites IT creates
    telemetry.programs.clear()
    mod, batch_nd = _fit_module(batch=8)
    m = metric_mod.Accuracy()
    assert mod.fit_step(batch_nd, m)
    # a plain executor forward as a second site
    x = sym.Variable("data")
    net = sym.FullyConnected(x, num_hidden=3, name="pfc")
    exe = net.simple_bind(ctx=mx.cpu(), grad_req="null", data=(2, 5))
    exe.forward(is_train=False, data=np.zeros((2, 5), np.float32))

    rows = telemetry.programs()
    sites = {r["site"] for r in rows}
    assert "fit_step" in sites and "executor" in sites
    for r in rows:
        if r["site"] in ("fit_step", "executor") \
                and "analysis_error" not in r:
            assert r["flops"] > 0, r
            assert r["bytes_accessed"] > 0, r
            assert r["peak_hbm_bytes"] > 0, r
    fit_rows = [r for r in rows if r["site"] == "fit_step"]
    assert fit_rows and fit_rows[0]["compile_ms"] is not None
    # analysis must not move the zero-retrace witnesses
    from mxnet_tpu.module import fused_fit
    traced = fused_fit.TRACE_COUNT
    telemetry.programs()
    assert fused_fit.TRACE_COUNT == traced


def test_program_registry_kvstore_site():
    kv = mx.kv.create("device")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
    kv.init("w", nd.ones((8, 4)))
    kv.push("w", nd.ones((8, 4)))
    rows = telemetry.programs(site="kvstore_bucket")
    assert rows, "bucket program never registered"
    assert any(r.get("flops", 0) > 0 for r in rows
               if "analysis_error" not in r)


def test_top_programs_and_flight_table(tmp_path):
    mod, batch_nd = _fit_module(batch=8)
    mod.fit_step(batch_nd, metric_mod.Accuracy())
    telemetry.programs()                     # force analysis
    top = telemetry.programs.top_programs(3, analyze=False)
    assert top and top[0]["flops"] >= top[-1]["flops"]
    rec = telemetry.FlightRecorder(capacity=4)
    path = str(tmp_path / "p.jsonl")
    rec.install(path, every=1)
    rec.dump()
    lines = [json.loads(l) for l in open(path)]
    tables = [l["programs"] for l in lines if "programs" in l]
    assert tables and tables[0][0]["flops"] > 0


# ----------------------------------------------------------------------
# pod health
# ----------------------------------------------------------------------
def test_straggler_single_process_never_flags():
    mon = telemetry.PodHealthMonitor(every=2, factor=1.5)
    assert mon.step(100.0) is None           # off-cadence step
    got = mon.step(5000.0)                   # exchange step
    assert got == -1                         # a world of one: no peer
    assert telemetry.REGISTRY.get("straggler_rank").value == -1
    assert mon.last_exchange == [(0, mon.last_exchange[0][1])]


def test_health_monitor_fit_loop_wiring(monkeypatch):
    """MXNET_HEALTH_EVERY arms the monitor inside Module.fit even in a
    single-process world (the exchange is an identity there)."""
    monkeypatch.setenv("MXNET_HEALTH_EVERY", "2")
    c0 = telemetry.REGISTRY.get("health_exchanges").value
    rng = np.random.RandomState(1)
    X = rng.rand(32, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.float32)
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=2, name="fc"),
        name="softmax")
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            initializer=mx.initializer.Xavier())
    assert telemetry.REGISTRY.get("health_exchanges").value - c0 == 1


def test_watchdog_fires_on_stall(tmp_path):
    stalls = telemetry.REGISTRY.get("watchdog_stalls").value
    out = open(str(tmp_path / "wd.txt"), "w+")
    wd = telemetry.Watchdog("test", factor=2.0, min_s=0.05, poll_s=0.02,
                            min_samples=2, stream=out)
    wd.arm()
    try:
        for _ in range(3):                   # healthy steps: no firing
            wd.begin()
            time.sleep(0.001)
            wd.end()
        time.sleep(0.1)
        assert wd.stalls == 0
        wd.begin()                           # stalled step
        time.sleep(0.3)
        wd.end()
    finally:
        wd.disarm()
        out.flush()
        out.seek(0)
        text = out.read()
        out.close()
    assert wd.stalls == 1                    # fired exactly once
    assert telemetry.REGISTRY.get("watchdog_stalls").value - stalls == 1
    assert "watchdog" in text and "test" in text


def test_watchdog_never_fires_during_warmup():
    wd = telemetry.Watchdog("warm", factor=2.0, min_s=0.01, poll_s=0.01,
                            min_samples=8)
    wd.arm()
    try:
        wd.begin()                           # no completed samples yet
        time.sleep(0.08)
        wd.end()
        assert wd.stalls == 0
    finally:
        wd.disarm()


# ----------------------------------------------------------------------
# static check stays green with the new series
# ----------------------------------------------------------------------
def test_check_telemetry_covers_trace_series():
    import subprocess
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_telemetry.py")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "label keys documented" in proc.stdout
