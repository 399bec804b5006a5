"""Compiled bucketed kvstore hot path (mxnet_tpu/kvstore_fused.py).

Pins: bit-for-bit parity between the bucketed-compiled and eager per-key
paths (dense and 2-bit; atol = 0, the op sequences are identical so the
floats are identical), zero retraces across steady-state steps, 2-bit
error-feedback semantics vs the reference gradient_compression.h,
bucket-size-cap planning, priority-ordered dispatch, async push sync
points, the 8-virtual-device smoke (conftest forces
--xla_force_host_platform_device_count=8), and the profiler counters.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import kvstore_fused
from mxnet_tpu.parallel.compression import TwoBitCompressor

SHAPES = [(64, 32), (128,), (3, 3, 8, 8), (500, 10), (7,)]


def _make_kv(bucketed, compress=None, optimizer=True):
    kv = mx.kv.create("device")
    kv.set_bucketing(bucketed)
    if compress is not None:
        kv.set_gradient_compression({"type": "2bit",
                                     "threshold": compress})
    if optimizer:
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.05, momentum=0.9,
                                          wd=1e-4, rescale_grad=0.5))
    return kv


def _run_steps(kv, n_steps=4, n_dev=3, seed=1):
    keys = ["p%d" % i for i in range(len(SHAPES))]
    rng = np.random.RandomState(0)
    for k, s in zip(keys, SHAPES):
        kv.init(k, nd.array(rng.normal(0, 1, s).astype(np.float32)))
    r = np.random.RandomState(seed)
    for _ in range(n_steps):
        grads = [[nd.array(r.normal(0, 1, s).astype(np.float32))
                  for _ in range(n_dev)] for s in SHAPES]
        kv.push(keys, grads, priority=[-i for i in range(len(keys))])
    outs = [nd.zeros(s) for s in SHAPES]
    kv.pull(keys, out=outs)
    return [o.asnumpy() for o in outs]


# Parity tolerance: the bucket program replays the exact eager op
# sequence, but XLA may pick different FMA contractions in different
# compilation units, so optimizer-applied weights can drift by ~1 ulp
# per mul-add chain (observed: one element in 2048 off by 1.2e-7 after
# 3 steps). The compressor path itself (quantize -> error feedback ->
# reduce) uses only adds and exact-constant selects, which no
# contraction can perturb — that part is pinned bit-for-bit below.
_ULP_RTOL = 5e-7
_ULP_ATOL = 5e-7


def test_bucketed_matches_eager_sgd():
    """Dense parity, bucketed-compiled vs eager per-key: SGD momentum +
    wd + rescale over multiple steps and device streams (tolerance: see
    _ULP_RTOL note above)."""
    a = _run_steps(_make_kv(True))
    b = _run_steps(_make_kv(False))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=_ULP_RTOL, atol=_ULP_ATOL)


def test_bucketed_compression_matches_eager():
    """2-bit quantize + error feedback + reduce + SGD apply, 3 device
    streams, 4 steps, bucketed vs eager (tolerance: _ULP_RTOL note)."""
    a = _run_steps(_make_kv(True, compress=0.1))
    b = _run_steps(_make_kv(False, compress=0.1))
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=_ULP_RTOL, atol=_ULP_ATOL)


def test_compressor_output_matches_eager_bit_for_bit():
    """2-bit numerics match the eager compressor bit-for-bit on the same
    inputs (acceptance criterion): with no updater the store receives
    exactly the quantized+reduced gradients, and the error-feedback
    residual evolves through adds alone — atol=0, multiple steps, dense
    and compressed, so the whole compressor pipeline is pinned exact."""
    for compress in (None, 0.25):
        a = _run_steps(_make_kv(True, compress, optimizer=False),
                       n_steps=3)
        b = _run_steps(_make_kv(False, compress, optimizer=False),
                       n_steps=3)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_zero_retraces_after_first_step():
    """Steady-state steps hit the compile cache: the bucket-program trace
    counter moves only on the first flush (acceptance criterion)."""
    kv = _make_kv(True, compress=0.5)
    keys = ["p%d" % i for i in range(len(SHAPES))]
    rng = np.random.RandomState(0)
    for k, s in zip(keys, SHAPES):
        kv.init(k, nd.array(rng.normal(0, 1, s).astype(np.float32)))

    def step(seed):
        r = np.random.RandomState(seed)
        grads = [[nd.array(r.normal(0, 1, s).astype(np.float32))
                  for _ in range(2)] for s in SHAPES]
        kv.push(keys, grads, priority=[-i for i in range(len(keys))])

    step(1)   # first flush: compiles each bucket program once
    traced_after_first = kvstore_fused.TRACE_COUNT
    for seed in range(2, 8):
        step(seed)
    assert kvstore_fused.TRACE_COUNT == traced_after_first, \
        "bucket programs retraced in steady state"
    # rescale_grad is a runtime argument, not a compile key: gluon
    # Trainer.step rewrites it every call (scale/batch_size), and a
    # ragged final batch must not recompile every bucket
    for batch in (32, 7, 32):
        kv._updater.optimizer.rescale_grad = 1.0 / batch
        step(10 + batch)
    assert kvstore_fused.TRACE_COUNT == traced_after_first, \
        "rescale_grad change retraced bucket programs"


def test_compressor_jit_no_recompile_across_steps_and_instances():
    """TwoBitCompressor methods are jitted with the instance static and
    hashed by threshold: repeated calls and fresh equal-threshold
    instances share one compile-cache entry; only a new threshold or a
    new shape traces again."""
    import jax.numpy as jnp
    g = jnp.ones((16, 8))
    r = jnp.zeros((16, 8))
    c1 = TwoBitCompressor(0.5)
    c1.compress_decompress(g, r)
    base = TwoBitCompressor._traces
    for _ in range(5):
        c1.compress_decompress(g, r)
    assert TwoBitCompressor._traces == base, "retraced across steps"
    c2 = TwoBitCompressor(0.5)   # equal config -> shared cache
    c2.compress_decompress(g, r)
    assert TwoBitCompressor._traces == base, "equal instance retraced"
    c3 = TwoBitCompressor(0.75)  # different config -> one new trace
    c3.compress_decompress(g, r)
    assert TwoBitCompressor._traces == base + 1


def test_bigarray_bound_env_caps_buckets(monkeypatch):
    """MXNET_KVSTORE_BIGARRAY_BOUND caps bucket bytes: a tiny cap makes
    per-key buckets, and a value bigger than the cap gets its own."""
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "1024")
    kv = _make_kv(True)
    keys = ["a", "b", "c"]
    shapes = [(8, 8), (8, 8), (1000,)]   # 256B, 256B, 4000B (> cap)
    for k, s in zip(keys, shapes):
        kv.init(k, nd.zeros(s))
    kv.push(keys, [[nd.ones(s)] for s in shapes], priority=[0, 0, 0])
    buckets = kv._engine.last_flush_buckets
    assert ["a", "b"] in buckets           # both fit under 1 KiB
    assert ["c"] in buckets                # oversized -> own bucket


def test_priority_orders_bucket_dispatch(monkeypatch):
    """Pushes enqueue under the default cap (async), then the sync-point
    flush packs and dispatches buckets in descending priority."""
    kv = _make_kv(True)
    kv.set_async_push(True)
    for k in ("lo", "hi", "mid"):
        kv.init(k, nd.zeros((4, 4)))
    kv.push(["lo", "hi", "mid"], [[nd.ones((4, 4))]] * 3,
            priority=[-10, 5, 0])
    assert kv._engine.has_pending
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "1")
    out = nd.zeros((4, 4))
    kv.pull("hi", out=out)                     # sync point flushes all
    assert kv._engine.last_flush_buckets == [["hi"], ["mid"], ["lo"]]


def test_streaming_flush_dispatches_full_buckets_mid_push(monkeypatch):
    """Once a bucket's worth of bytes is pending, the engine dispatches
    the full buckets immediately (enqueue order = dispatch order) and
    keeps the partial tail pending until the sync point."""
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "256")
    kv = _make_kv(True, optimizer=False)       # assign mode: pull == push
    kv.set_async_push(True)
    keys = ["k%d" % i for i in range(5)]
    for k in keys:
        kv.init(k, nd.zeros((4, 4)))           # 64 B each, cap = 4 keys
    kv.push(keys, [[nd.ones((4, 4))]] * 5, priority=[0] * 5)
    # first four keys filled a bucket and went out mid-push; k4 pends
    assert kv._engine.last_flush_buckets == [keys[:4]]
    assert kv._engine.has_pending
    out = nd.zeros((4, 4))
    kv.pull("k4", out=out)
    np.testing.assert_allclose(out.asnumpy(), 1.0)
    assert not kv._engine.has_pending


def test_async_push_snapshots_grad_at_push_time():
    """MXNet's push-at-call semantics: mutating the gradient array after
    an async push must not change what the deferred flush applies."""
    kv = mx.kv.create("local")
    kv.set_async_push(True)
    kv.init("w", nd.ones((4, 4)))
    g = nd.ones((4, 4)) * 5
    kv.push("w", g)
    g[:] = 0.0                       # rebinds g's buffer post-push
    out = nd.zeros((4, 4))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), 5.0)


def test_async_push_defers_until_pull():
    """With async push on, push() only enqueues; the store still holds
    the old value until a sync point (pull here) flushes the buckets."""
    kv = mx.kv.create("local")
    kv.set_async_push(True)
    kv.init("w", nd.ones((4, 4)))
    kv.push("w", nd.ones((4, 4)) * 5)
    assert kv._engine.has_pending
    assert float(kv._store["w"].asnumpy()[0, 0]) == 1.0   # not yet applied
    out = nd.zeros((4, 4))
    kv.pull("w", out=out)                                  # sync point
    assert not kv._engine.has_pending
    np.testing.assert_allclose(out.asnumpy(), 5.0)


def test_multichip_8dev_smoke():
    """Multichip smoke: one gradient stream per forced host device
    (conftest pins XLA_FLAGS=--xla_force_host_platform_device_count=8).
    The bucket program reduces all 8 device-resident streams in one
    compiled computation, dense and 2-bit."""
    import jax
    devs = jax.devices()
    assert len(devs) == 8, "conftest should force 8 host devices"
    for compress in (None, 2.0):
        kv = mx.kv.create("tpu")
        if compress is not None:
            kv.set_gradient_compression({"type": "2bit",
                                         "threshold": compress})
        kv.init(0, nd.zeros((16, 4)))
        grads = []
        for d in range(8):
            arr = nd.ones((16, 4))
            arr._set_data(jax.device_put(arr._data, devs[d]))
            grads.append(arr)
        kv.push(0, grads)
        out = nd.zeros((16, 4))
        kv.pull(0, out=out)
        if compress is None:
            np.testing.assert_allclose(out.asnumpy(), 8.0)
        else:
            # each stream: acc 1.0 < threshold 2.0 -> q 0, residual 1.0
            np.testing.assert_allclose(out.asnumpy(), 0.0)
            kv.push(0, [nd.ones((16, 4)) * 1.5 for _ in range(8)])
            kv.pull(0, out=out)
            # acc 2.5 > 2.0 -> q +2 per stream, reduced = 16
            np.testing.assert_allclose(out.asnumpy(), 16.0)


def test_error_feedback_reference_semantics():
    """2-bit semantics vs gradient_compression.h: strict-inequality
    threshold buckets and residual accumulation across pushes, on both
    paths. threshold=0.5: q = +0.5 where acc > 0.5, -0.5 where
    acc < -0.5, else 0 (exactly at +-0.5 stays 0), residual -= q."""
    for bucketed in (True, False):
        kv = mx.kv.create("local")
        kv.set_bucketing(bucketed)
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        grad = np.array([[0.6, -0.7, 0.5, -0.5, 0.3, 0.0]], np.float32)
        kv.init("g", nd.zeros(grad.shape))
        kv.push("g", nd.array(grad))
        out = nd.zeros(grad.shape)
        kv.pull("g", out=out)
        np.testing.assert_array_equal(
            out.asnumpy(),
            np.array([[0.5, -0.5, 0.0, 0.0, 0.0, 0.0]], np.float32))
        # residuals now [0.1, -0.2, 0.5, -0.5, 0.3, 0]; second push of
        # 0.3 accumulates: acc = [0.4, 0.1, 0.8, -0.2, 0.6, 0.3]
        kv.push("g", nd.array(np.full(grad.shape, 0.3, np.float32)))
        kv.pull("g", out=out)
        np.testing.assert_array_equal(
            out.asnumpy(),
            np.array([[0.0, 0.0, 0.5, 0.0, 0.5, 0.0]], np.float32))


def test_residual_survives_bucket_composition_change():
    """Error feedback accumulated inside one bucket's flat residual must
    survive the keyset changing between steps (spill + reseed path)."""
    kv = mx.kv.create("local")
    kv.set_gradient_compression({"type": "2bit", "threshold": 2.0})
    for k in ("a", "b"):
        kv.init(k, nd.zeros((4, 4)))
    # one bucket holding both keys: residuals a=b=1.5
    kv.push(["a", "b"], [[nd.ones((4, 4)) * 1.5]] * 2, priority=[0, 0])
    out = nd.zeros((4, 4))
    # now push each key alone (different bucket composition)
    kv.push("a", nd.ones((4, 4)))       # acc 2.5 -> q +2
    kv.pull("a", out=out)
    np.testing.assert_allclose(out.asnumpy(), 2.0)
    kv.push("b", nd.ones((4, 4)))
    kv.pull("b", out=out)
    np.testing.assert_allclose(out.asnumpy(), 2.0)


def test_optimizer_state_save_load_bucketed(tmp_path):
    """Momentum lives in per-key Updater states even on the bucketed
    path, so save/load round-trips and training continues identically."""
    def fresh(snapshot=None, states=None):
        kv = _make_kv(True)
        kv.init("p", nd.array(snapshot) if snapshot is not None
                else nd.ones((8, 8)))
        if states is not None:
            kv.load_optimizer_states(states)
        return kv

    kv = fresh()
    for _ in range(3):
        kv.push("p", [nd.ones((8, 8)) * 0.5])
    fname = str(tmp_path / "opt.states")
    kv.save_optimizer_states(fname, dump_optimizer=True)
    snap = kv._store["p"].asnumpy().copy()
    kv2 = fresh(snapshot=snap, states=fname)
    kv.push("p", [nd.ones((8, 8)) * 0.5])
    kv2.push("p", [nd.ones((8, 8)) * 0.5])
    np.testing.assert_allclose(kv._store["p"].asnumpy(),
                               kv2._store["p"].asnumpy(), rtol=1e-6)


def test_profiler_counters():
    """kvstore_bytes_pushed / kvstore_compress_ratio /
    kvstore_bucket_count emit through the thread-safe Counter."""
    before = kvstore_fused.BYTES_PUSHED.value
    kv = _make_kv(True, compress=0.5)
    kv.init("w", nd.zeros((32, 32)))
    kv.push("w", [nd.ones((32, 32)), nd.ones((32, 32))])
    pushed = kvstore_fused.BYTES_PUSHED.value - before
    assert pushed == 32 * 32 * 4 * 2   # two device streams of f32
    assert kvstore_fused.COMPRESS_RATIO.value == 16.0
    assert kvstore_fused.BUCKET_COUNT.value == 1


def test_custom_updater_and_sparse_fall_back_eager():
    """Ineligible pushes (custom updater) keep full eager semantics with
    the engine enabled."""
    kv = mx.kv.create("local")
    assert kv._bucketed
    kv.set_updater(lambda key, recv, stored: stored.__iadd__(recv))
    kv.init("w", nd.zeros((4, 4)))
    kv.push("w", nd.ones((4, 4)))
    out = nd.zeros((4, 4))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), 1.0)
    assert kv._engine is None or not kv._engine.stats["flushes"]


# ----------------------------------------------------------------------
# backward-overlapped collectives (docs/KVSTORE.md "Overlapped push")
# ----------------------------------------------------------------------
def test_overlap_witness_ticks_on_streaming_flush(monkeypatch):
    """A bucket dispatched by the mid-push streaming flush happened
    strictly before the final backward bucket landed — that is the
    overlap witness (kvstore_overlap_dispatches), and the closing sync
    point records the dispatch window histogram."""
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "256")
    kv = mx.kv.create("tpu")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    keys = ["k%d" % i for i in range(5)]
    for k in keys:
        kv.init(k, nd.zeros((4, 4)))           # 64 B each, cap = 4 keys
    wit = telemetry.REGISTRY.get("kvstore_overlap_dispatches")
    hist = telemetry.REGISTRY.get("kvstore_overlap_window_ms")
    w0, h0 = wit.value, hist.count
    kv.set_async_push(True)
    kv.push(keys, [[nd.ones((4, 4))]] * 5, priority=[0] * 5)
    assert wit.value > w0, "no overlapped dispatch on streaming flush"
    assert kv._engine.has_pending              # k4 still pending: the
    # witness fired BEFORE the final bucket
    out = nd.zeros((4, 4))
    kv.pull("k4", out=out)                     # sync point
    assert hist.count == h0 + 1, "window histogram missed the step"


def test_overlap_escape_hatch(monkeypatch):
    """MXNET_KVSTORE_OVERLAP=0 restores strictly serial dispatch: the
    streaming flush still runs (bucket planning is orthogonal) but the
    overlap witness never ticks. Overlap reorders work and adds none:
    the overlapped arm dispatches the serial arm's bucket programs."""
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "256")
    wit = telemetry.REGISTRY.get("kvstore_overlap_dispatches")

    def arm(overlap):
        monkeypatch.setenv("MXNET_KVSTORE_OVERLAP", overlap)
        kv = mx.kv.create("tpu")
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
        keys = ["k%d" % i for i in range(5)]
        for k in keys:
            kv.init(k, nd.zeros((4, 4)))
        w0 = wit.value
        kv.set_async_push(True)
        kv.push(keys, [[nd.ones((4, 4))]] * 5, priority=[0] * 5)
        out = nd.zeros((4, 4))
        kv.pull("k4", out=out)
        kv._sync_engine()
        return wit.value - w0, kv._engine.stats["buckets"]

    serial_ticks, serial_buckets = arm("0")
    assert serial_ticks == 0, "escape hatch leaked the overlap witness"
    overlap_ticks, overlap_buckets = arm("1")
    assert overlap_ticks > 0
    assert overlap_buckets == serial_buckets > 0
