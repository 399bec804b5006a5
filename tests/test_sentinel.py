"""mx.sentinel: pod aggregation, in-launch numerics, SLO rule engine.

The contract under test (ISSUE 19 acceptance):
  * rule parsing + the incident lifecycle — an invariant must fail
    ``for_steps`` consecutive evaluations to open an incident, opening
    fires ONCE (counter + action), recovery clears, a fresh breach
    opens a second incident; ``delta(...)`` rules skip their first
    sample; ``MXNET_SENTINEL_RULES`` file loading;
  * per-metric label cardinality cap (``MXNET_TELEMETRY_MAX_SERIES``):
    past the cap ``labels()`` degrades to a detached overflow child and
    ``telemetry_series_dropped`` counts it — capped series never reach
    the exposition;
  * Prometheus exposition conformance for LABELED histograms —
    per-label-set ``_sum``/``_count``/cumulative ``_bucket`` lines,
    label values escaped (backslash, quote, newline) and round-tripped
    through ``parse_text``/``parse_labels``;
  * flight-recorder dump rotation (``MXNET_TELEMETRY_FLIGHT_KEEP``);
  * the in-launch witnesses ride the EXISTING donated programs: zero
    extra dispatches/retraces/host syncs with sentinels on, and an
    injected-NaN batch trips a ``nonfinite_grads`` alert within ONE
    ``MXNET_SENTINEL_EVERY`` interval (fused fit step AND the bucketed
    kvstore engine, which also dedups re-publishes);
  * ``aggregate.merge`` rank-labels scalars and bucket-merges
    histograms; ``GET /pod_metrics`` on the standalone exporter and
    sentinel incidents in ``GET /health`` on ModelServer;
  * the real 2-process world (tests/sentinel_agg_worker.py, slow).
"""
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym, telemetry
from mxnet_tpu import metric as metric_mod
from mxnet_tpu.module import fused_fit
from mxnet_tpu.telemetry import aggregate, export, flight, sentinel
from mxnet_tpu.telemetry import registry as registry_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _View:
    """Minimal rule-engine view: a dict with ``lookup``."""

    def __init__(self, **vals):
        self.vals = vals

    def lookup(self, ref):
        return self.vals.get(ref)


# ----------------------------------------------------------------------
# rule parsing + incident lifecycle
# ----------------------------------------------------------------------
def test_rule_parsing():
    r = sentinel.Rule("decode_ttft_steps_p99 < 700", for_steps=3)
    assert (r.metric, r.op, r.threshold, r.for_steps, r.delta) \
        == ("decode_ttft_steps_p99", "<", 700.0, 3, False)
    d = sentinel.Rule("delta(nonfinite_grads) == 0")
    assert d.delta and d.metric == "nonfinite_grads"
    assert d.name == "nonfinite_grads"      # default name = metric
    assert sentinel.Rule("grad_norm <= 1e3").threshold == 1000.0
    assert sentinel.Rule("loss_zscore >= -2.5").holds(0.0)
    for bad in ("grad_norm ?? 3", "delta(grad_norm < 1", "grad_norm) > 1",
                "grad_norm <", "1 < grad_norm", "grad_norm < foo", ""):
        with pytest.raises(ValueError):
            sentinel.Rule(bad)


def test_incident_lifecycle_fires_once_and_clears():
    eng = sentinel.RuleEngine()
    hits = []
    r = eng.rule("loss_zscore < 4", for_steps=2, name="z",
                 action=lambda rule, value: hits.append(value))
    alerts = sentinel.SENTINEL_ALERTS.labels(rule="z")
    a0 = alerts.value
    assert eng.evaluate(_View(loss_zscore=10.0)) == []   # breach 1 of 2
    assert not r.firing
    assert eng.evaluate(_View(loss_zscore=11.0)) == [r]  # opens: fires once
    assert r.firing and alerts.value - a0 == 1 and hits == [11.0]
    assert eng.evaluate(_View(loss_zscore=12.0)) == []   # open: no re-fire
    assert alerts.value - a0 == 1 and len(hits) == 1
    assert eng.active() == [{"rule": "z", "expr": "loss_zscore < 4",
                             "value": 12.0}]
    assert eng.evaluate(_View(loss_zscore=0.5)) == []    # recovery clears
    assert not r.firing and eng.active() == []
    eng.evaluate(_View(loss_zscore=9.0))                 # fresh breach ->
    assert eng.evaluate(_View(loss_zscore=9.0)) == [r]   # SECOND incident
    assert alerts.value - a0 == 2
    # absent series: no fire, no clear — the incident stays open
    assert eng.evaluate(_View()) == []
    assert r.firing
    # a failing action must not break evaluation
    eng.rule("grad_norm < 1", name="boom",
             action=lambda rule, value: 1 / 0)
    eng.evaluate(_View(grad_norm=5.0))


def test_delta_rules_skip_first_sample():
    eng = sentinel.RuleEngine()
    r = eng.rule("delta(nonfinite_grads) == 0", name="nf")
    assert eng.evaluate(_View(nonfinite_grads=7.0)) == []   # no prev yet
    assert r.last_value is None
    assert eng.evaluate(_View(nonfinite_grads=7.0)) == []   # delta 0 holds
    assert eng.evaluate(_View(nonfinite_grads=12.0)) == [r]  # delta 5 fires
    assert r.last_value == 5.0 and r.firing
    assert eng.evaluate(_View(nonfinite_grads=12.0)) == []   # delta 0 clears
    assert not r.firing


def test_env_rules_file(tmp_path, monkeypatch):
    path = tmp_path / "rules.json"
    path.write_text(json.dumps([
        {"expr": "grad_norm < 1e3", "for_steps": 2, "name": "gn"},
        {"expr": "delta(nonfinite_grads) == 0"}]))
    monkeypatch.setenv("MXNET_SENTINEL_RULES", str(path))
    eng = sentinel.RuleEngine()
    loaded = eng.rules()
    assert [r.name for r in loaded] == ["gn", "nonfinite_grads"]
    assert loaded[0].for_steps == 2
    assert len(eng.rules()) == 2            # loaded once, not per call
    # a broken file logs a warning and leaves the engine usable
    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    monkeypatch.setenv("MXNET_SENTINEL_RULES", str(bad))
    eng2 = sentinel.RuleEngine()
    assert eng2.rules() == []


# ----------------------------------------------------------------------
# registry label-cardinality cap
# ----------------------------------------------------------------------
def test_label_series_cap_degrades_to_overflow(monkeypatch):
    monkeypatch.setattr(registry_mod, "MAX_SERIES", 3)
    r = telemetry.Registry()
    c = r.counter("capped_total", "cap test")
    dropped = registry_mod.SERIES_DROPPED
    d0 = dropped.value
    for i in range(6):
        c.labels(idx=i).inc()
    assert len(c.children()) == 3
    assert dropped.value - d0 == 3
    # an EXISTING child is served from the cache, not dropped
    before = dropped.value
    c.labels(idx=0).inc()
    assert dropped.value == before
    assert c.labels(idx=0).value == 2
    # overflow children type-check but never reach the exposition
    text = export.generate_text(r)
    assert text.count("capped_total{") == 3
    for i in range(3, 6):
        assert 'idx="%d"' % i not in text


# ----------------------------------------------------------------------
# exposition conformance: labeled histograms + label escaping
# ----------------------------------------------------------------------
def test_labeled_histogram_exposition_roundtrip():
    r = telemetry.Registry()
    h = r.histogram("req_ms", "latency", bounds=(1, 2, 4))
    evil = 'a\\b"c\nd'
    h.labels(path=evil).observe(1.5)
    h.labels(path=evil).observe(3.0)
    h.labels(path="ok").observe(0.5)
    text = export.generate_text(r)
    # on the wire: backslash, quote and newline are escaped per the
    # exposition format, so every sample stays on one line
    assert 'path="a\\\\b\\"c\\nd"' in text
    parsed = export.parse_text(text)
    fam = parsed["req_ms"]
    assert fam["type"] == "histogram"
    # one _sum/_count PER LABEL SET, values un-escaped on the way back
    counts = {export.parse_labels(k)[1]["path"]: v
              for k, v in fam["samples"].items()
              if k.startswith("req_ms_count")}
    sums = {export.parse_labels(k)[1]["path"]: v
            for k, v in fam["samples"].items()
            if k.startswith("req_ms_sum")}
    assert counts == {evil: 2.0, "ok": 1.0}
    assert sums == {evil: 4.5, "ok": 0.5}
    # cumulative buckets per label set, +Inf last and equal to _count
    evil_buckets = [(export.parse_labels(k)[1]["le"], v)
                    for k, v in fam["samples"].items()
                    if k.startswith("req_ms_bucket")
                    and export.parse_labels(k)[1].get("path") == evil]
    assert [le for le, _ in evil_buckets] == ["1", "2", "4", "+Inf"]
    vals = [v for _, v in evil_buckets]
    assert vals == sorted(vals) and vals[-1] == 2.0


# ----------------------------------------------------------------------
# flight-recorder dump rotation
# ----------------------------------------------------------------------
def test_flight_dump_rotation(tmp_path, monkeypatch):
    reg = telemetry.Registry()
    reg.counter("flight_ctr").inc()
    fr = flight.FlightRecorder(registry=reg, keep=3)
    path = str(tmp_path / "flight.jsonl")
    for _ in range(5):
        fr.dump(path)
    assert os.path.exists(path)
    assert os.path.exists(path + ".1") and os.path.exists(path + ".2")
    assert not os.path.exists(path + ".3")    # oldest dropped at keep=3
    for p in (path, path + ".1", path + ".2"):
        with open(p) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        assert lines and lines[-1].get("final") is True
    # keep=1 keeps the overwrite-in-place behavior
    fr1 = flight.FlightRecorder(registry=reg, keep=1)
    p1 = str(tmp_path / "solo.jsonl")
    fr1.dump(p1)
    fr1.dump(p1)
    assert os.path.exists(p1) and not os.path.exists(p1 + ".1")
    # the default comes from MXNET_TELEMETRY_FLIGHT_KEEP
    monkeypatch.setenv("MXNET_TELEMETRY_FLIGHT_KEEP", "2")
    assert flight.FlightRecorder(registry=reg).keep == 2


# ----------------------------------------------------------------------
# in-launch numerics: fused fit step
# ----------------------------------------------------------------------
def _fit_module(batch=16):
    rng = np.random.RandomState(0)
    X = rng.rand(4 * batch, 8).astype(np.float32)
    y = (X.sum(axis=1) > 4).astype(np.float32)
    data = sym.Variable("data")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(data, num_hidden=2, name="fc"), name="softmax")
    mod = mx.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 8))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    batch_nd = mx.io.DataBatch(data=[nd.array(X[:batch])],
                               label=[nd.array(y[:batch])])
    return mod, batch_nd


def test_fused_sentinels_zero_extra_dispatches_and_publish():
    """With sentinels ON (the default) the witnesses ride the one
    donated program: dispatches/step stays 1, zero retraces, zero host
    syncs in the loop — and the sync boundary publishes real values."""
    assert sentinel.numerics_enabled()
    mod, batch_nd = _fit_module()
    m = metric_mod.Accuracy()
    assert mod.fit_step(batch_nd, m)          # first step traces
    assert mod._fused_fit is not None
    assert mod._fused_fit._sent_state is not None
    traced = fused_fit.TRACE_COUNT
    disp = telemetry.REGISTRY.get("device_dispatches")
    d0 = disp.value
    s0 = metric_mod.HOST_SYNCS
    for _ in range(4):
        assert mod.fit_step(batch_nd, m)
    assert fused_fit.TRACE_COUNT == traced, \
        "sentinel witnesses caused a fused-step retrace"
    assert disp.value - d0 == 4               # still ONE launch per step
    assert metric_mod.HOST_SYNCS == s0        # and ZERO host syncs
    mod._fit_sync()                           # the existing sync boundary
    assert sentinel.GRAD_NORM.value > 0
    assert np.isfinite(float(sentinel.LOSS_ZSCORE.value))


def test_fused_sentinels_off_switch(monkeypatch):
    monkeypatch.setenv("MXNET_SENTINEL_NUMERICS", "0")
    assert not sentinel.numerics_enabled()
    mod, batch_nd = _fit_module()
    m = metric_mod.Accuracy()
    assert mod.fit_step(batch_nd, m)
    assert mod._fused_fit is not None
    assert mod._fused_fit._sent_state is None
    assert mod._fused_fit.publish_sentinels() is None
    # the same ONE launch a step as with the witnesses on (test above)
    disp = telemetry.REGISTRY.get("device_dispatches")
    d0 = disp.value
    for _ in range(4):
        assert mod.fit_step(batch_nd, m)
    assert disp.value - d0 == 4


def test_nan_trips_alert_within_one_sentinel_interval(monkeypatch):
    """The pinned acceptance bound: an injected-NaN batch must fire the
    ``nonfinite_grads`` delta rule within ONE MXNET_SENTINEL_EVERY
    interval of aggregation exchanges."""
    EVERY = 2
    monkeypatch.setenv("MXNET_SENTINEL_EVERY", str(EVERY))
    eng = sentinel.SENTINEL
    eng.clear()
    try:
        eng.rule("delta(nonfinite_grads) == 0", name="nf_guard")
        alerts = sentinel.SENTINEL_ALERTS.labels(rule="nf_guard")
        a0 = alerts.value
        mod, batch_nd = _fit_module()
        m = metric_mod.Accuracy()
        agg = aggregate.PodMetricsAggregator(every=EVERY)

        def drive(batch):
            # the fit loop's exact sequence (base_module._run_train_epoch):
            # drain through the sync boundary first so the shipped
            # snapshot carries fresh in-launch values
            assert mod.fit_step(batch, m)
            if agg.due():
                mod._fit_sync()
            return agg.step()

        for _ in range(2 * EVERY):           # clean baseline intervals
            drive(batch_nd)
        assert alerts.value == a0
        X = batch_nd.data[0].asnumpy()
        X[:] = np.nan
        bad = mx.io.DataBatch(data=[nd.array(X)], label=batch_nd.label)
        steps_to_alert = None
        for k in range(1, EVERY + 1):
            drive(bad)
            if alerts.value > a0:
                steps_to_alert = k
                break
        assert steps_to_alert is not None and steps_to_alert <= EVERY, \
            "NaN injection did not alert within one sentinel interval"
        assert sentinel.NONFINITE_GRADS.value > 0
        assert [a["rule"] for a in eng.active()] == ["nf_guard"]
    finally:
        eng.clear()
        aggregate._set_default(None)


# ----------------------------------------------------------------------
# in-launch numerics: bucketed kvstore engine
# ----------------------------------------------------------------------
def _bucketed_kv():
    kv = mx.kv.create("device")
    kv.set_bucketing(True)
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.05, momentum=0.9))
    return kv


def _push_pull(kv, keys, vals):
    kv.push(keys, [[nd.array(v)] for v in vals])
    outs = [nd.zeros(v.shape) for v in vals]
    kv.pull(keys, out=outs)
    for o in outs:
        o.asnumpy()


def test_kvstore_bucket_witness_counts_and_dedups():
    assert sentinel.numerics_enabled()
    kv = _bucketed_kv()
    keys = ["w%d" % i for i in range(4)]
    rng = np.random.RandomState(0)
    for k in keys:
        kv.init(k, nd.array(rng.normal(0, 1, (8, 8)).astype(np.float32)))
    clean = [rng.normal(0, 1, (8, 8)).astype(np.float32) for _ in keys]
    _push_pull(kv, keys, clean)
    eng = kv._engine
    assert eng is not None
    assert eng.publish_sentinels() == 0.0     # clean grads: zero count
    n0 = sentinel.NONFINITE_GRADS.value
    bad = []
    for v in clean:
        b = v.copy()
        b[0, 0] = np.nan
        bad.append(b)
    _push_pull(kv, keys, bad)
    assert eng.publish_sentinels() == 4.0     # one NaN element per key
    assert sentinel.NONFINITE_GRADS.value - n0 == 4
    # re-publish with no new dispatch: dedup, no double count
    assert eng.publish_sentinels() == 4.0
    assert sentinel.NONFINITE_GRADS.value - n0 == 4


def test_kvstore_bucket_witness_off_switch(monkeypatch):
    monkeypatch.setenv("MXNET_SENTINEL_NUMERICS", "0")
    kv = _bucketed_kv()
    kv.init("w", nd.array(np.ones((4, 4), np.float32)))
    _push_pull(kv, ["w"], [np.ones((4, 4), np.float32)])
    assert kv._engine.publish_sentinels() is None


# ----------------------------------------------------------------------
# pod aggregation: merge semantics + scrape surfaces
# ----------------------------------------------------------------------
def test_merge_rank_labels_and_histogram_merge():
    ra, rb = telemetry.Registry(), telemetry.Registry()
    ra.counter("events_total").inc(3)
    rb.counter("events_total").inc(4)
    ra.gauge("depth").set(2)
    rb.gauge("depth").set(9)
    ra.histogram("lat", bounds=(1, 10)).observe(0.5)
    hb = rb.histogram("lat", bounds=(1, 10))
    hb.observe(5)
    hb.observe(50)
    # the aggregator's own bookkeeping must NOT be re-exported per rank
    ra.gauge("sentinel_pod_ranks").set(2)
    view = aggregate.merge([aggregate.local_payload(ra),
                            aggregate.local_payload(rb)])
    assert view.n_ranks == 2 and not view.degraded
    assert view.scalars[("events_total", (("rank", "0"),))]["value"] == 3
    assert view.scalars[("events_total", (("rank", "1"),))]["value"] == 4
    assert view.lookup("events_total") == 7.0     # counters sum
    assert view.lookup("depth") == 9.0            # gauges take the max
    h = view.hists[("lat", ())]
    assert h["count"] == 3 and h["sum"] == 55.5
    assert h["min"] == 0.5 and h["max"] == 50.0
    assert view.lookup("lat_count") == 3
    assert view.lookup("lat_max") == 50.0
    assert view.lookup("lat_p99") >= 10           # merged distribution
    assert view.lookup("no_such_series") is None
    assert all(n != "sentinel_pod_ranks" for n, _ in view.scalars)
    text = view.generate_text()
    assert 'events_total{rank="0"} 3' in text
    assert 'depth{rank="1"} 9' in text
    assert 'le="+Inf"' in text and "lat_count 3" in text


def test_exporter_pod_metrics_endpoint():
    telemetry.REGISTRY.counter("exporter_probe_total").inc()
    aggregate._set_default(None)        # force the local-fallback path
    exp = telemetry.start_http_exporter(port=0)
    try:
        host, port = exp.address
        url = "http://%s:%d" % (host, port)
        r = urllib.request.urlopen(url + "/pod_metrics", timeout=30)
        assert r.headers["Content-Type"] == export.CONTENT_TYPE
        assert 'exporter_probe_total{rank="0"} 1' in r.read().decode()
        plain = urllib.request.urlopen(url + "/metrics",
                                       timeout=30).read().decode()
        assert "exporter_probe_total 1" in plain  # /metrics: no rank label
    finally:
        exp.stop()


def test_server_health_carries_sentinel_incidents():
    from mxnet_tpu.serving import ModelServer
    eng = sentinel.SENTINEL
    eng.clear()
    rng = np.random.RandomState(3)
    data = sym.Variable("data")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(data, num_hidden=2, name="fc"), name="softmax")
    arg_shapes, _, _ = net.infer_shape(data=(1, 8))
    args = {n: rng.uniform(-0.5, 0.5, s).astype(np.float32)
            for n, s in zip(net.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    srv = ModelServer(net, args, {}, {"data": (8,)}, num_replicas=1,
                      max_batch_size=2, max_latency_ms=2.0)
    try:
        host, port = srv.start_http(port=0)
        url = "http://%s:%d/health" % (host, port)
        doc = json.loads(urllib.request.urlopen(url,
                                                timeout=30).read().decode())
        assert doc["status"] == "ok" and doc["sentinel_alerts"] == []
        # open an incident (counters are never negative, so this
        # invariant is false on the spot) and watch it surface
        eng.rule("sentinel_exchanges < -1", name="impossible")
        sentinel.evaluate_local()
        doc = json.loads(urllib.request.urlopen(url,
                                                timeout=30).read().decode())
        assert [a["rule"] for a in doc["sentinel_alerts"]] == ["impossible"]
    finally:
        srv.stop()
        eng.clear()


# ----------------------------------------------------------------------
# the real 2-process world (CPU jax.distributed backend)
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_two_process_pod_aggregation():
    """Spawn a real 2-process world: rank-labeled + bucket-merged pod
    view on rank 0, /pod_metrics serving both ranks, once-per-incident
    SLO firing/clearing, and bounded-timeout degradation when a rank
    sits an exchange out (tests/sentinel_agg_worker.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_multihost.py"),
         "-n", "2",
         sys.executable, os.path.join(ROOT, "tests",
                                      "sentinel_agg_worker.py")],
        env=env, capture_output=True, text=True, timeout=420)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0
    assert proc.stdout.count("all sentinel agg checks passed") == 2
