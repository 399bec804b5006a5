"""Single-launch fused fit step (mxnet_tpu/module/fused_fit.py).

Pins: weight parity of the fused fit step vs the eager fwd_bwd+kvstore
path (dense and 2-bit arms; ulp tolerance per the FMA-parity note in
tests/test_kvstore_fused.py — grads here come from two different XLA
programs, so the bound is looser than the same-grads kvstore pin), zero
steady-state retraces across ragged final batches (TRACE_COUNT),
fallback routing for non-fusable optimizers / custom updaters /
monitors, error-feedback residual spill/reseed across path switches,
metric parity device vs host accumulation, zero per-batch host syncs,
the dispatch-count witness, and the 8-virtual-device smoke (conftest
forces --xla_force_host_platform_device_count=8).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu import metric as metric_mod
from mxnet_tpu import profiler
from mxnet_tpu.module import fused_fit

# fused and eager compute gradients in DIFFERENT XLA programs, so each
# step can differ by ~1 ulp of FMA contraction; 5 steps at lr 0.1 keeps
# the drift well inside these bounds on MLP-scale weights
_RTOL = 2e-5
_ATOL = 1e-6


def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.SoftmaxOutput(sym.FullyConnected(net, num_hidden=4,
                                               name="fc2"), name="softmax")
    return net


def _data(n=96, d=6, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, d).astype(np.float32) * 0.1
    y = rng.randint(0, classes, n)
    for i in range(n):
        X[i, y[i]] += 1.0
    return X, y.astype(np.float32)


def _init_params(seed=42):
    r = np.random.RandomState(seed)
    return {"fc1_weight": r.normal(0, 0.1, (8, 6)).astype(np.float32),
            "fc1_bias": np.zeros(8, np.float32),
            "fc2_weight": r.normal(0, 0.1, (4, 8)).astype(np.float32),
            "fc2_bias": np.zeros(4, np.float32)}


def _make_mod(fused, kvstore=None, compress=None, optimizer="sgd",
              opt_params=None, context=None, batch=16):
    mod = mx.Module(_mlp(), context=context or mx.cpu(),
                    compression_params=({"type": "2bit",
                                         "threshold": compress}
                                        if compress else None))
    mod._fused_fit_enabled = fused
    mod.bind(data_shapes=[("data", (batch, 6))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(arg_params={k: nd.array(v)
                                for k, v in _init_params().items()},
                    aux_params={})
    mod.init_optimizer(
        kvstore=mx.kv.create(kvstore) if kvstore else "local",
        optimizer=optimizer,
        optimizer_params=opt_params or {"learning_rate": 0.1,
                                        "momentum": 0.9, "wd": 1e-4})
    return mod


def _run(mod, metric=None, n_steps=5, batch=16, seed=0):
    X, y = _data(seed=seed)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    for i, b in enumerate(it):
        if i >= n_steps:
            break
        mod.fit_step(b, metric)
        mod.update_metric(metric, b.label) if metric is not None else None
    return mod.get_params()[0]


def _assert_params_close(a, b, rtol=_RTOL, atol=_ATOL):
    for k in a:
        np.testing.assert_allclose(a[k].asnumpy(), b[k].asnumpy(),
                                   rtol=rtol, atol=atol, err_msg=k)


def _assert_2bit_close(a, b, lr, threshold, steps):
    """Discretization-aware 2-bit parity (docs/TRAINING.md Parity): the
    quantizer is a threshold COMPARE, so a ~1-ulp gradient difference
    between the two XLA programs can flip a near-boundary element by a
    whole ±threshold step. Pin (1) every element within the flip bound
    lr*threshold*steps*momentum-amplification, and (2) the GLOBAL
    median abs diff at ulp scale — the median ignores sparse flips, but
    a residual-accounting bug (lost/duplicated error feedback) shifts
    most elements and blows it up."""
    flip = lr * threshold * steps * 10.0      # sum of momentum powers < 10
    diffs = []
    for k in a:
        x, z = a[k].asnumpy(), b[k].asnumpy()
        np.testing.assert_allclose(x, z, rtol=0, atol=flip, err_msg=k)
        diffs.append(np.abs(x - z).ravel())
    assert np.median(np.concatenate(diffs)) <= 10 * _ATOL


def test_fused_parity_dense_local_updater():
    """kvstore=None (the single-device default): fused single-launch
    steps produce the same weights as the eager fwd_bwd + local-updater
    path (ulp tolerance, see module docstring)."""
    a = _run(_make_mod(True))
    b = _run(_make_mod(False))
    _assert_params_close(a, b)


def test_fused_parity_dense_and_2bit_kvstore():
    """update_on_kvstore with a device store, dense and 2-bit arms:
    fused vs eager weight parity, residual error feedback included.

    The 2-bit arm's tolerance is discretization-aware (docs/TRAINING.md
    Parity): the quantizer is a threshold COMPARE, so a ~1-ulp gradient
    difference between the two XLA programs can flip a near-boundary
    element by a whole ±threshold step (|Δw| ~ lr*threshold, amplified
    by momentum). The pin is therefore bulk-tight — ≥95% of elements at
    the dense ulp tolerance — with the rare flips bounded by
    lr*threshold*steps*momentum-amplification."""
    for compress in (None, 0.005):
        mod_f = _make_mod(True, kvstore="device", compress=compress)
        mod_e = _make_mod(False, kvstore="device", compress=compress)
        a = _run(mod_f)
        b = _run(mod_e)
        assert mod_f._fused_fit is not None and mod_f._fused_fit.launches == 5
        assert mod_e._fused_fit is None
        if compress is None:
            _assert_params_close(a, b)
            continue
        _assert_2bit_close(a, b, lr=0.1, threshold=compress, steps=5)


def test_zero_steady_state_retraces_across_ragged_batches():
    """Each distinct batch shape traces the fit program once; repeats —
    including alternating ragged final batches — hit the jit cache."""
    mod = _make_mod(True, kvstore="device")
    m = metric_mod.Accuracy()
    X, y = _data()

    def step(n):
        b = mx.io.DataBatch(data=[nd.array(X[:n])],
                            label=[nd.array(y[:n])])
        assert mod.fit_step(b, m)

    step(16)
    step(7)        # ragged shape: one new trace
    traced = fused_fit.TRACE_COUNT
    for n in (16, 7, 16, 7, 16):
        step(n)
    assert fused_fit.TRACE_COUNT == traced, \
        "fit program retraced in steady state across ragged batches"
    # rescale_grad is a runtime argument, not a compile key
    mod._optimizer.rescale_grad = 1.0 / 7
    step(16)
    assert fused_fit.TRACE_COUNT == traced


def test_fallback_routing_non_fusable_configs():
    """Optimizers without a fused signature (waiver-listed eager-only
    ones like ftrl/signum) and custom updaters keep the eager path —
    and training still works."""
    for optimizer, params in (
            ("ftrl", {"learning_rate": 0.05}),
            ("signum", {"learning_rate": 0.01})):
        mod = _make_mod(True, optimizer=optimizer, opt_params=params)
        before = {k: v.asnumpy().copy()
                  for k, v in mod.get_params()[0].items()}
        _run(mod, n_steps=2)
        assert mod._fused_fit is None, optimizer
        after = mod.get_params()[0]
        assert not np.allclose(before["fc1_weight"],
                               after["fc1_weight"].asnumpy())
    # custom updater installed AFTER fused steps already ran: the
    # per-step liveness check routes subsequent batches back to eager
    mod = _make_mod(True, kvstore="device")
    _run(mod, n_steps=1)
    assert mod._fused_fit is not None
    mod._kvstore.set_updater(lambda key, grad, weight: None)
    X, y = _data()
    b = mx.io.DataBatch(data=[nd.array(X[:16])], label=[nd.array(y[:16])])
    assert not mod._fused_fit.step(b)
    mod.fit_step(b)                      # eager path runs the custom updater


def test_hyperparam_mutation_switches_program():
    """Mutating an optimizer hyperparameter mid-training takes effect on
    the fused path (one retrace), like it would on the eager path."""
    mod = _make_mod(True, kvstore="device")
    X, y = _data()
    b = mx.io.DataBatch(data=[nd.array(X[:16])], label=[nd.array(y[:16])])
    assert mod.fit_step(b)
    traced = fused_fit.TRACE_COUNT
    mod._optimizer.momentum = 0.0
    assert mod.fit_step(b)
    assert fused_fit.TRACE_COUNT == traced + 1   # new program, once
    assert mod.fit_step(b)
    assert fused_fit.TRACE_COUNT == traced + 1


def _plan_builds():
    return fused_fit.FIT_PLAN_BUILDS.value


def _ev_nothing(mod, m, tmp_path, b):
    return m


def _ev_load_optimizer_states(mod, m, tmp_path, b):
    fname = str(tmp_path / "mid.states")
    mod.save_optimizer_states(fname)
    mod.load_optimizer_states(fname)     # new state objects, same values
    return m


def _ev_swapped_metric(mod, m, tmp_path, b):
    return metric_mod.Accuracy()


def _ev_mutated_hyperparameter(mod, m, tmp_path, b):
    mod._optimizer.momentum = 0.5
    return m


def _ev_set_updater_and_back(mod, m, tmp_path, b):
    kv = mod._kvstore
    live = kv._updater
    kv.set_updater(lambda key, grad, weight: None)
    assert not mod._fused_fit.step(b, m)         # the eager pair's batch
    mod.fit_step(b, m)
    kv.set_updater(live)
    return m


def _ev_monitored_batches(mod, m, tmp_path, b):
    mod._monitor_installed = True
    for _ in range(2):
        assert not mod._fused_fit.step(b, m)     # falls back per batch
        mod.fit_step(b, m)
        mod.update_metric(m, b.label)
    mod._monitor_installed = False
    return m


@pytest.mark.parametrize("event,builds", [
    (_ev_nothing, 1),
    (_ev_load_optimizer_states, 2),
    (_ev_swapped_metric, 2),
    (_ev_mutated_hyperparameter, 2),
    (_ev_set_updater_and_back, 2),
    (_ev_monitored_batches, 2),
], ids=lambda v: v.__name__[4:] if callable(v) else None)
def test_step_plan_holds_and_breaks(event, builds, tmp_path):
    """The step plan (docs/TRAINING.md): derived once over steady steps
    with a ``metric.reset()`` between each, derived again after what
    replaces something it rests on — and a run that keeps its plan ends
    bit for bit where a run that derives everything every step ends."""
    X, y = _data()
    batches = [mx.io.DataBatch(data=[nd.array(X[i * 16:(i + 1) * 16])],
                               label=[nd.array(y[i * 16:(i + 1) * 16])])
               for i in range(6)]

    def run(keep_plan):
        mod = _make_mod(True, kvstore="device", compress=0.005)
        fused = mod._get_fused_fit()
        m, values, b0 = metric_mod.Accuracy(), [], _plan_builds()
        for i, b in enumerate(batches):
            if i == 3:
                m = event(mod, m, tmp_path, b)
            if not keep_plan:
                fused._plan = None       # derive everything, as before
            assert mod.fit_step(b, m)
            mod.update_metric(m, b.label)
            values.append(m.get()[1])
            m.reset()
        assert fused.launches == 6
        return mod.get_params()[0], values, _plan_builds() - b0

    params, values, built = run(keep_plan=True)
    assert built == builds
    ref_params, ref_values, ref_built = run(keep_plan=False)
    assert ref_built >= 6
    assert values == ref_values
    for k in params:
        np.testing.assert_array_equal(params[k].asnumpy(),
                                      ref_params[k].asnumpy(), err_msg=k)


def test_no_eager_program_after_metric_reset(monkeypatch):
    """From the second step on a fused step launches ONE program, also
    right after ``metric.reset()``: the accumulator's zeros are the
    plan's, not two eager ``jnp.float32(0.0)`` (each a program of its
    own on the chip)."""
    import jax.numpy as jnp
    from jax._src import dispatch
    eager = []
    real = dispatch.xla_primitive_callable

    def counting(prim, **params):
        eager.append(prim.name)
        return real(prim, **params)

    monkeypatch.setattr(dispatch, "xla_primitive_callable", counting)
    jnp.float32(0.0)
    if not eager:
        pytest.skip("this jax does not route eager primitives through "
                    "dispatch.xla_primitive_callable")
    mod = _make_mod(True, kvstore="device")
    m = metric_mod.Accuracy()
    X, y = _data()
    b = mx.io.DataBatch(data=[nd.array(X[:16])], label=[nd.array(y[:16])])
    assert mod.fit_step(b, m)                    # compile + warm
    for _ in range(4):
        m.get()
        m.reset()
        assert m._dev_sum is None
        del eager[:]
        d0 = profiler.DEVICE_DISPATCHES.value
        assert mod.fit_step(b, m)
        assert eager == [], "eager programs inside a fused step: %s" % eager
        assert profiler.DEVICE_DISPATCHES.value - d0 == 1
        mod.update_metric(m, b.label)
    assert m.get()[1] == pytest.approx(m.sum_metric + float(m._dev_sum)
                                       / float(m._dev_num))


def test_monitor_falls_back_per_batch():
    """An installed monitor routes batches to the eager (tappable) path
    without losing 2-bit residual state: fused→eager→fused matches the
    pure-eager run."""
    mod = _make_mod(True, kvstore="device", compress=0.005)
    X, y = _data()
    batches = [mx.io.DataBatch(data=[nd.array(X[i * 16:(i + 1) * 16])],
                               label=[nd.array(y[i * 16:(i + 1) * 16])])
               for i in range(5)]
    ref = _make_mod(False, kvstore="device", compress=0.005)
    for i, b in enumerate(batches):
        if i == 2:
            mod._monitor_installed = True      # force two eager batches
        if i == 4:
            mod._monitor_installed = False     # back to fused
        handled = mod.fit_step(b)
        assert handled == (i not in (2, 3))
        ref.fit_step(b)
    # a lost/duplicated residual across the path switch would shift
    # most elements, failing the global-median pin in _assert_2bit_close
    _assert_2bit_close(mod.get_params()[0], ref.get_params()[0],
                       lr=0.1, threshold=0.005, steps=5)


def test_metric_device_accumulation_matches_host():
    """Accuracy accumulated inside the fused program equals the host
    accumulation of the eager twin on the same batches — and the fused
    loop performs zero blocking host syncs between get() boundaries."""
    mod_f = _make_mod(True, kvstore="device")
    mod_e = _make_mod(False, kvstore="device")
    m_f = metric_mod.Accuracy()
    m_e = metric_mod.Accuracy()
    h0 = metric_mod.HOST_SYNCS.value
    _run(mod_f, metric=m_f)
    assert metric_mod.HOST_SYNCS.value == h0, \
        "fused fit loop performed a per-batch host sync"
    _run(mod_e, metric=m_e)
    assert metric_mod.HOST_SYNCS.value > h0      # eager converts per batch
    name_f, val_f = m_f.get()                    # boundary readback
    name_e, val_e = m_e.get()
    assert name_f == name_e
    assert val_f == pytest.approx(val_e, abs=1e-12)
    assert metric_mod.HOST_SYNCS.value > h0
    # reset clears the device accumulator; get() then reports nan
    m_f.reset()
    assert m_f._dev_sum is None and np.isnan(m_f.get()[1])


def test_dispatch_witness_one_launch_per_step():
    """profiler.DEVICE_DISPATCHES moves by exactly 1 per fused step (the
    bench witness), vs 1 fwd_bwd + N bucket programs per eager step."""
    mod = _make_mod(True, kvstore="device")
    m = metric_mod.Accuracy()
    X, y = _data()
    b = mx.io.DataBatch(data=[nd.array(X[:16])], label=[nd.array(y[:16])])
    mod.fit_step(b, m)                           # compile + warm
    d0 = profiler.DEVICE_DISPATCHES.value
    for _ in range(4):
        mod.fit_step(b, m)
        mod.update_metric(m, b.label)
    assert profiler.DEVICE_DISPATCHES.value - d0 == 4
    mod_e = _make_mod(False, kvstore="device")
    mod_e.fit_step(b)
    d0 = profiler.DEVICE_DISPATCHES.value
    mod_e.fit_step(b)
    assert profiler.DEVICE_DISPATCHES.value - d0 >= 2


def test_fused_keys_align_with_frozen_params():
    """Frozen params keep their index slots in local-updater keys (eager
    model._update_params enumerates the FULL param list), so with
    fixed_param_names set, fused and eager runs must produce the same
    state keys and the same weights."""
    def train(fused):
        mod = mx.Module(_mlp(), context=mx.cpu(),
                        fixed_param_names=["fc1_weight"])
        mod._fused_fit_enabled = fused
        mod.bind(data_shapes=[("data", (16, 6))],
                 label_shapes=[("softmax_label", (16,))])
        mod.init_params(arg_params={k: nd.array(v)
                                    for k, v in _init_params().items()},
                        aux_params={})
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        _run(mod, n_steps=3)
        assert (mod._fused_fit is not None) == fused
        return mod.get_params()[0], sorted(mod._updater.states,
                                           key=str)
    a, keys_f = train(True)
    b, keys_e = train(False)
    assert keys_f == keys_e
    _assert_params_close(a, b)
    np.testing.assert_array_equal(a["fc1_weight"].asnumpy(),
                                  _init_params()["fc1_weight"])


def test_optimizer_state_interchange(tmp_path):
    """Optimizer state written by fused steps loads into an eager module
    (same updater keys) and vice versa."""
    mod = _make_mod(True, kvstore="device")
    _run(mod, n_steps=3)
    fname = str(tmp_path / "fused.states")
    mod.save_optimizer_states(fname)
    mod_e = _make_mod(False, kvstore="device")
    mod_e.load_optimizer_states(fname)
    _run(mod_e, n_steps=1)                       # continues eager, no crash
    mod_f2 = _make_mod(True, kvstore="device")
    mod_f2.load_optimizer_states(fname)
    _run(mod_f2, n_steps=1)                      # continues fused


def test_fit_sync_every_env(monkeypatch):
    """MXNET_FIT_SYNC_EVERY bounds async depth without changing
    results."""
    monkeypatch.setenv("MXNET_FIT_SYNC_EVERY", "2")
    X, y = _data()
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2, "momentum": 0.9},
            initializer=mx.initializer.Xavier())
    it.reset()
    assert mod.score(it, "acc")[0][1] > 0.9
    assert mod._fused_fit is not None and mod._fused_fit.launches > 0


def test_multichip_8dev_smoke():
    """8 virtual devices: the fused step consumes the dp-sharded batch,
    GSPMD inserts the gradient reduce, params stay replicated."""
    import jax
    assert len(jax.devices()) == 8, "conftest should force 8 host devices"
    rng = np.random.RandomState(0)
    X = rng.rand(128, 6).astype(np.float32)
    y = rng.randint(0, 4, 128).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    assert mod._fused_fit is not None and mod._fused_fit.launches > 0
    arg, _ = mod.get_params()
    for v in arg.values():
        assert np.isfinite(v.asnumpy()).all()
