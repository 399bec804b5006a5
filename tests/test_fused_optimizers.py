"""Fused optimizer update programs (mxnet_tpu/fused_update.py) and
bf16 mixed-precision training end-to-end.

Pins: the fused-vs-eager parity matrix (every fused optimizer kind x
{f32, bf16 multi-precision} x {2-bit error feedback on/off}) at the
kvstore level where both paths see IDENTICAL gradients, bit-level
equality of the 2-bit error-feedback residuals on the f32
master-gradient view, zero steady-state retraces while an lr schedule
advances every step and batches go ragged, the dynamic loss scaler's
overflow-skip semantics (weights/states frozen through a non-finite
step, backoff, growth, static mode), checkpoint resume parity for a
bf16+Adam multi-precision run (master weights + scaler state round
trip), and the satellite-2 guarantee that a DEFAULT Adam config never
falls back to the eager per-key path (no ``unfused_optimizer:`` slug).

Tolerances: at the kvstore level the bucketed and eager paths consume
the same pushed gradients, so f32 weights drift only by FMA
contraction (~1 ulp per mul-add chain; docs/TRAINING.md Parity). The
bf16 arm stores bf16 weights stepped from f32 masters on both paths;
one bf16 ulp is ~0.8%, so the pin is 1e-2 (docs/TRAINING.md documents
this bound). Residuals evolve through adds and exact-constant selects
only — no contraction can perturb them — hence the atol=0 pin.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu import fused_update
from mxnet_tpu import telemetry
from mxnet_tpu.module import fused_fit

SHAPES = [(32, 16), (64,), (3, 3, 4, 4), (7,)]

# f32: identical grads, same op sequence modulo program boundaries ->
# ulp-scale drift only (sqrt/div chains in the adaptive optimizers are
# a little wider than SGD's, hence 5e-6 over test_kvstore_fused's 5e-7)
_F32_RTOL = _F32_ATOL = 5e-6
# bf16: both paths step an f32 master and round to bf16 once; a master
# drifting across a rounding boundary moves the stored value by one
# bf16 ulp (~2**-8)
_BF16_TOL = 1e-2

_OPTIMIZERS = {
    "sgd": lambda **kw: mx.optimizer.SGD(
        learning_rate=0.05, momentum=0.9, wd=1e-4, **kw),
    "adam": lambda **kw: mx.optimizer.Adam(
        learning_rate=0.01, wd=1e-4, **kw),
    "lamb": lambda **kw: mx.optimizer.LAMB(
        learning_rate=0.01, wd=1e-2, **kw),
    "rmsprop": lambda **kw: mx.optimizer.RMSProp(
        learning_rate=0.01, centered=True, **kw),
    "adagrad": lambda **kw: mx.optimizer.AdaGrad(
        learning_rate=0.05, **kw),
    "adamax": lambda **kw: mx.optimizer.Adamax(
        learning_rate=0.01, **kw),
    "nadam": lambda **kw: mx.optimizer.Nadam(
        learning_rate=0.01, **kw),
    "lbsgd": lambda **kw: mx.optimizer.LBSGD(
        learning_rate=0.05, momentum=0.9, wd=1e-4, **kw),
}


def _make_kv(bucketed, opt_name, compress=None, multi_precision=False):
    kv = mx.kv.create("device")
    kv.set_bucketing(bucketed)
    if compress is not None:
        kv.set_gradient_compression({"type": "2bit",
                                     "threshold": compress})
    kw = {"multi_precision": True} if multi_precision else {}
    kv.set_optimizer(_OPTIMIZERS[opt_name](rescale_grad=0.5, **kw))
    return kv


def _run_kv(kv, dtype="float32", n_steps=3, n_dev=2, seed=1):
    """Init + push identical gradient streams; returns pulled weights
    as f32 numpy. Both the bucketed-compiled and eager per-key paths
    see the exact same inputs, so parity is on the optimizer math."""
    keys = ["p%d" % i for i in range(len(SHAPES))]
    rng = np.random.RandomState(0)
    for k, s in zip(keys, SHAPES):
        w = nd.array(rng.normal(0, 1, s).astype(np.float32))
        kv.init(k, w if dtype == "float32" else w.astype(dtype))
    r = np.random.RandomState(seed)
    for _ in range(n_steps):
        grads = []
        for s in SHAPES:
            vs = [nd.array(r.normal(0, 1, s).astype(np.float32))
                  for _ in range(n_dev)]
            if dtype != "float32":
                vs = [v.astype(dtype) for v in vs]
            grads.append(vs)
        kv.push(keys, grads)
    outs = [nd.zeros(s) if dtype == "float32"
            else nd.zeros(s).astype(dtype) for s in SHAPES]
    kv.pull(keys, out=outs)
    return [o.astype("float32").asnumpy() for o in outs]


# ----------------------------------------------------------------------
# the parity matrix: optimizer x {f32, bf16+MP} x {2bit on/off}
# ----------------------------------------------------------------------
@pytest.mark.parametrize("compress", [None, 0.05],
                         ids=["dense", "2bit"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("opt_name", sorted(_OPTIMIZERS))
def test_fused_matches_eager_matrix(opt_name, dtype, compress):
    mp = dtype != "float32"
    a = _run_kv(_make_kv(True, opt_name, compress, mp), dtype)
    b = _run_kv(_make_kv(False, opt_name, compress, mp), dtype)
    tol = {"rtol": _F32_RTOL, "atol": _F32_ATOL} if not mp else \
          {"rtol": _BF16_TOL, "atol": _BF16_TOL}
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, err_msg=opt_name, **tol)


def test_residuals_bit_identical_on_f32_master_view():
    """2-bit error feedback under bf16 multi-precision Adam: the
    residuals live on the f32 MASTER-gradient view (bf16 grads are
    widened exactly once before compression) and evolve through adds
    and exact-constant selects only, so the bucketed-compiled and
    eager per-key residuals must agree BIT-FOR-BIT even though the
    optimizer-applied weights drift by FMA ulps."""
    kvs = {}
    for bucketed in (True, False):
        kv = _make_kv(bucketed, "adam", compress=0.05,
                      multi_precision=True)
        _run_kv(kv, "bfloat16")
        kv._sync_engine()   # spill flat bucket residuals per (key, dev)
        kvs[bucketed] = kv
    res_f = kvs[True]._compression_residuals
    res_e = kvs[False]._compression_residuals
    assert res_f and sorted(res_f) == sorted(res_e)
    for rk in res_f:
        x = res_f[rk].asnumpy()
        assert x.dtype == np.float32, (rk, x.dtype)
        np.testing.assert_array_equal(x, res_e[rk].asnumpy(), err_msg=rk)
    # and they are nonzero — real error feedback, not a dropped path
    assert any(float(np.abs(v.asnumpy()).sum()) > 0
               for v in res_f.values())


# ----------------------------------------------------------------------
# satellite 2: default Adam NEVER falls back
# ----------------------------------------------------------------------
def test_default_adam_takes_fused_path_no_fallback():
    """An out-of-the-box Adam config must ride the compiled bucketed
    path: the ``kvstore_fallbacks`` counter gains no
    ``unfused_optimizer:Adam`` count and the engine reports the config
    eligible."""
    c = telemetry.REGISTRY.get("kvstore_fallbacks").labels(
        reason="unfused_optimizer:Adam")
    before = c.value
    kv = mx.kv.create("device")
    kv.set_bucketing(True)
    kv.set_optimizer(mx.optimizer.Adam())     # ALL defaults
    _run_kv(kv)
    assert c.value == before, "default Adam fell back to eager"
    eng = kv._get_engine()
    assert eng.ineligible_reason(
        "p0", [kv._store["p0"]], eng._updater_mode()) is None


def test_waived_eager_optimizer_counts_bounded_slug():
    """Waiver-listed eager-only optimizers fall back with the bounded
    ``unfused_optimizer:<Name>`` slug (docs/KVSTORE.md)."""
    c = telemetry.REGISTRY.get("kvstore_fallbacks").labels(
        reason="unfused_optimizer:Ftrl")
    before = c.value
    kv = mx.kv.create("device")
    kv.set_bucketing(True)
    kv.set_optimizer(mx.optimizer.Ftrl())
    kv.init("w", nd.array(np.ones((8,), np.float32)))
    kv.push("w", nd.array(np.full((8,), 0.1, np.float32)))
    assert c.value > before


# ----------------------------------------------------------------------
# zero steady-state retraces: lr schedule + ragged batches
# ----------------------------------------------------------------------
def _mlp(low_precision=False, plant=False):
    """``plant`` adds a gain whose gradient is non-finite exactly in
    the columns where a batch holds a zero (d/dp sqrt(x*p) at x == 0)
    while the forward and every other gradient stay finite."""
    data = sym.Variable("data")
    if low_precision:
        data = sym.Cast(data, dtype="bfloat16")
    if plant:
        gain = sym.Variable("plant_gain", shape=(1, 6), dtype="bfloat16")
        data = data + sym.sqrt(sym.broadcast_mul(data, gain))
    net = sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    if low_precision:
        net = sym.Cast(net, dtype="float32")
    return sym.SoftmaxOutput(net, name="softmax")


def _make_mod(optimizer="adam", opt_params=None, low_precision=False,
              batch=16):
    mod = mx.Module(_mlp(low_precision), context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 6))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params=opt_params
                       or {"learning_rate": 0.05})
    return mod


def _batch(n=16, seed=0, bad=False, zero_at=()):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 6).astype(np.float32)
    if bad:
        X[0, 0] = np.inf       # forward -> inf logits -> nan grads
    for r, c in zero_at:       # _mlp(plant=True): that column's gradient
        X[r, c] = 0.0
    y = rng.randint(0, 4, n).astype(np.float32)
    return mx.io.DataBatch(data=[nd.array(X)], label=[nd.array(y)])


def test_zero_retraces_while_lr_schedule_advances():
    """The lr schedule changes the learning rate EVERY step; lr is a
    runtime argument of the fused program, so the trace counter must
    not move in steady state — across ragged final batches too."""
    sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.9)
    mod = _make_mod(opt_params={"learning_rate": 0.1,
                                "lr_scheduler": sched})
    assert mod.fit_step(_batch(16))
    assert mod.fit_step(_batch(7))      # ragged shape: one new trace
    traced = fused_fit.TRACE_COUNT
    lr0 = mod._optimizer._get_lr(0)
    for i, n in enumerate((16, 7, 16, 16, 7)):
        assert mod.fit_step(_batch(n, seed=i))
    assert fused_fit.TRACE_COUNT == traced, \
        "lr schedule stepping retraced the fit program"
    # the schedule really advanced (decayed lr), without a retrace
    assert mod._optimizer._get_lr(0) < lr0


def test_bf16_multi_precision_single_launch_no_retrace():
    """bf16 + Adam multi-precision: fused single-launch steps, zero
    steady-state retraces, and the update state is ((mean, var), w32)
    with an f32 master."""
    mod = _make_mod(opt_params={"learning_rate": 0.05,
                                "multi_precision": True},
                    low_precision=True)
    for i in range(3):
        assert mod.fit_step(_batch(seed=i))
    traced = fused_fit.TRACE_COUNT
    for i in range(3):
        assert mod.fit_step(_batch(seed=i))
    assert fused_fit.TRACE_COUNT == traced
    assert mod._fused_fit is not None and mod._fused_fit.launches == 6
    st = next(iter(mod._updater.states.values()))
    inner, w32 = st
    assert str(w32.dtype).startswith("float32")
    assert len(inner) == 2      # (mean, var)


# ----------------------------------------------------------------------
# loss scaler: overflow-skip semantics
# ----------------------------------------------------------------------
def test_loss_scaler_overflow_skips_update_and_backs_off():
    """A non-finite gradient must skip the weight/state update entirely
    (bit-identical params through the bad step), bump the skip counter,
    and halve the dynamic scale — all detected on device, no per-step
    host sync."""
    mod = _make_mod(opt_params={"learning_rate": 0.05,
                                "multi_precision": True},
                    low_precision=True)
    for i in range(2):
        assert mod.fit_step(_batch(seed=i))
    scaler = mod._loss_scaler
    assert scaler is not None
    init_scale = scaler.publish()
    before = {k: v.asnumpy().copy()
              for k, v in mod.get_params()[0].items()}

    assert mod.fit_step(_batch(bad=True))      # nan grads: skipped
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in before:
        np.testing.assert_array_equal(before[k], after[k], err_msg=k)
    scaler.publish()
    assert scaler.skips == 1
    assert scaler.scale == init_scale * fused_update.DynamicLossScaler.BACKOFF

    assert mod.fit_step(_batch(seed=5))        # finite again: applied
    moved = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    assert any(not np.array_equal(before[k], moved[k]) for k in before)
    scaler.publish()
    assert scaler.skips == 1                   # no new skips


def test_loss_scaler_step_fn_growth_backoff_and_static():
    """Pure in-program bookkeeping: growth after ``growth_interval``
    consecutive finite steps (capped at MAX_SCALE), backoff + good
    reset on overflow, and a static scaler that skips but never
    adjusts."""
    s = fused_update.DynamicLossScaler(init_scale=4.0, growth_interval=2)
    st = s.device_state()
    st = s.step_fn(True, st)
    assert float(st[0]) == 4.0 and int(st[1]) == 1
    st = s.step_fn(True, st)                   # hits the interval
    assert float(st[0]) == 8.0 and int(st[1]) == 0
    st = s.step_fn(False, st)                  # overflow
    assert float(st[0]) == 4.0
    assert int(st[1]) == 0 and int(st[2]) == 1
    # cap
    s2 = fused_update.DynamicLossScaler(
        init_scale=fused_update.DynamicLossScaler.MAX_SCALE,
        growth_interval=1)
    st2 = s2.step_fn(True, s2.device_state())
    assert float(st2[0]) == fused_update.DynamicLossScaler.MAX_SCALE
    # static: fixed scale, still counts skips
    s3 = fused_update.DynamicLossScaler(init_scale=128.0, dynamic=False)
    st3 = s3.step_fn(False, s3.device_state())
    assert float(st3[0]) == 128.0 and int(st3[2]) == 1
    st3 = s3.step_fn(True, st3)
    assert float(st3[0]) == 128.0 and int(st3[2]) == 1


# ----------------------------------------------------------------------
# checkpoint resume parity: bf16 + Adam multi-precision
# ----------------------------------------------------------------------
def test_bf16_adam_checkpoint_resume_parity(tmp_path):
    """Checkpoint a bf16+MP Adam run mid-training and resume: the
    continued run is BIT-IDENTICAL to the uninterrupted one (the f32
    masters live in the optimizer states file) and the loss-scaler
    triple rides along in extra['loss_scaler']."""
    from mxnet_tpu import checkpoint
    prefix = str(tmp_path / "ck")

    mx.random.seed(0)
    np.random.seed(0)
    mod = _make_mod(opt_params={"learning_rate": 0.05,
                                "multi_precision": True},
                    low_precision=True)
    for i in range(3):
        mod.fit_step(_batch(seed=i))
    mgr = checkpoint.CheckpointManager(prefix, module=mod,
                                       install_preemption=False)
    man = mgr.save(epoch=0, step=3, block=True)
    mgr.close()
    assert "loss_scaler" in checkpoint.snapshot._load_extra(prefix, man)
    for i in range(3, 6):
        mod.fit_step(_batch(seed=i))
    ref = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    mod._loss_scaler.publish()

    mx.random.seed(99)
    res = _make_mod(opt_params={"learning_rate": 0.05,
                                "multi_precision": True},
                    low_precision=True)
    man2 = checkpoint.restore(res, prefix)
    assert man2["step"] == 3
    for i in range(3, 6):
        res.fit_step(_batch(seed=i))
    got = {k: v.asnumpy() for k, v in res.get_params()[0].items()}
    assert sorted(got) == sorted(ref)
    for k in ref:
        assert ref[k].dtype == got[k].dtype
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)
    # scaler state continued identically (same finite-step history)
    res._loss_scaler.publish()
    assert res._loss_scaler.scale == mod._loss_scaler.scale
    assert res._loss_scaler.skips == mod._loss_scaler.skips


# ----------------------------------------------------------------------
# gradients cross into the guarded update in the backward's own dtype
# ----------------------------------------------------------------------
def _lp_mod(optimizer, opt_params, compress=None, plant=False, fused=True):
    mod = mx.Module(_mlp(True, plant), context=mx.cpu(), compression_params=(
        {"type": "2bit", "threshold": compress} if compress else None))
    mod._fused_fit_enabled = fused
    mod.bind(data_shapes=[("data", (16, 6))],
             label_shapes=[("softmax_label", (16,))])
    np.random.seed(3)       # initializers draw from numpy's global RNG
    mod.init_params(initializer=mx.initializer.One() if plant
                    else mx.initializer.Xavier())
    mod.init_optimizer(
        kvstore=mx.kv.create("device") if compress else "local",
        optimizer=optimizer, optimizer_params=opt_params)
    return mod


# Hyperparameters that are powers of two (rescale_grad is 1/16, the
# batch), no weight decay: every product of the update is then exact,
# so a mul-add that LLVM contracts to an FMA in one program and not in
# another rounds the same, and two differently laid out programs can be
# held to atol=0.  (With wd=1e-3 one Adam mean in 48 differs by an ulp.)
_LP_CASES = {
    "adam": ("adam", {"learning_rate": 2.0 ** -5, "beta1": 0.5,
                      "beta2": 0.5, "multi_precision": True}, None),
    "sgd_mom": ("sgd", {"learning_rate": 2.0 ** -4, "momentum": 0.5,
                        "multi_precision": True}, None),
    "adam_2bit": ("adam", {"learning_rate": 2.0 ** -5, "beta1": 0.5,
                           "beta2": 0.5, "multi_precision": True},
                  2.0 ** -8),
}


def _widen_first_reference(mod, ff, use_wd):
    """The fit step put together from its own pieces (``jax.vjp`` over
    the module's ``graph_fn``, ``two_bit_quantize``,
    ``_fused.apply_one``, the scaler's ``step_fn``) with every gradient
    widened to float32 FIRST, at the top of the program, as the fit
    program did until PR 25.  ``lax.reduce_precision`` holds XLA to the
    bf16 value the backward states: left alone it drops a bf16 -> f32
    widening that sits next to the dot TOGETHER with the dot's own
    rounding to bf16 (``xla_allow_excess_precision``), and the update
    then runs from an accumulator that no gradient array ever held."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import _compiled_cache
    from mxnet_tpu.kvstore_fused import two_bit_quantize
    graph_fn = _compiled_cache(mod._symbol)["graph_fn"]
    order = tuple(ff._order)
    opt = mod._optimizer
    upd = fused_update.build(opt._fused_fit_sig())
    exe = mod._exec_group._exec
    tpls = [fused_update.state_template(ff._updater.states[uk])
            for uk in ff._ukeys]
    mp = [bool(opt.multi_precision)
          and fused_update.is_low_precision(exe.arg_dict[n].dtype)
          for n in order]
    scaler, threshold = ff._scaler, ff._threshold

    @jax.jit
    def reference(params, states, residuals, scaler_state, inputs, auxs,
                  lr_vec, wd_vec, rescale, extra, seed):
        outs, vjp_fn, _ = jax.vjp(
            lambda p: graph_fn({**inputs, **p}, auxs, seed, True),
            params, has_aux=True)
        (grads,) = vjp_fn([jnp.ones_like(o) for o in outs])
        assert all(grads[n].dtype == jnp.bfloat16 for n in order)
        g32 = {n: jax.lax.reduce_precision(
            grads[n].astype(jnp.float32), 8, 7) for n in order}
        finite = jnp.bool_(True)
        for n in order:
            finite = jnp.logical_and(finite,
                                     jnp.all(jnp.isfinite(g32[n])))

        def apply(_):
            ps, ss, rs = {}, {}, {}
            for i, n in enumerate(order):
                g = g32[n]
                if threshold is not None:
                    g, rs[n] = two_bit_quantize(residuals[n], g, threshold)
                w, s = fused_update.apply_one(
                    upd, params[n], g,
                    fused_update.unflatten(tpls[i], states[n]), mp[i],
                    lr_vec[i], wd_vec[i], rescale,
                    extra[i] if upd.n_extra else (), use_wd)
                ps[n] = w
                ss[n] = tuple(fused_update.flatten_state(s)[0])
            return ps, ss, (rs if threshold is not None else residuals)

        ps, ss, rs = jax.lax.cond(
            finite, apply, lambda _: (params, states, residuals), None)
        return ps, ss, rs, scaler.step_fn(finite, scaler_state)
    return reference


@pytest.mark.parametrize("case", sorted(_LP_CASES))
def test_fit_step_bit_equal_to_widen_first_reference(case):
    """bf16 parameters with f32 masters, 3 fused steps: the weights,
    the masters and moments, the 2-bit residuals and the scaler's
    triple are BIT-EQUAL to a step that widens every gradient to
    float32 before anything reads it.  The fit program hands the
    gradients over narrow and each reader widens for itself; bf16 ->
    f32 is exact, so widening early or late gives the same bits."""
    import jax
    optimizer, opt_params, compress = _LP_CASES[case]
    mod = _lp_mod(optimizer, opt_params, compress)
    ff = mod._get_fused_fit()
    assert ff is not None
    reference = None
    for t in range(3):
        fn, args, carried = ff._prepare(_batch(seed=t), None)
        host = jax.tree.map(np.asarray, args)   # before the donation
        (params, states, residuals, _, scaler_state, _, inputs, auxs,
         lr_vec, wd_vec, rescale, extra, seed) = host
        if reference is None:
            reference = _widen_first_reference(
                mod, ff, bool(np.any(wd_vec != 0)))
        want = reference(params, states, residuals, scaler_state, inputs,
                         auxs, lr_vec, wd_vec, rescale, extra, seed)
        got = fn(*args)
        ff._rebind(got, None, *carried)
        ff.launches += 1
        got = (got[0], got[1], got[2], got[4])
        flat_got = jax.tree_util.tree_leaves_with_path(got)
        flat_want = jax.tree.leaves(want)
        assert len(flat_got) == len(flat_want)
        for (path, a), b in zip(flat_got, flat_want):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg="step %d %s" % (t, jax.tree_util.keystr(path)))
        # the step really trained: every weight moved
        for n in ff._order:
            assert not np.array_equal(np.asarray(got[0][n], np.float32),
                                      np.asarray(params[n], np.float32)), n
    if compress:
        assert any(float(np.abs(np.asarray(r)).sum()) > 0
                   for r in got[2].values())


def test_planted_nonfinite_gradient_skips_and_is_counted():
    """Two columns of ONE bf16 gradient are made non-finite (forward
    and every other gradient finite): nothing updates — weights,
    masters, moments bit-identical —, the scaler's skips advance by
    one, and the sentinel's non-finite count rises by exactly the two
    planted elements.  The finiteness check and the count read the
    gradient in bf16; the predicate is that of the widened copy."""
    mod = _lp_mod("adam", {"learning_rate": 0.05,
                           "multi_precision": True}, plant=True)
    ff = mod._get_fused_fit()
    for t in range(2):
        assert mod.fit_step(_batch(seed=t))
    sent0 = np.asarray(ff._sent_state)
    assert sent0[3] == 0 and np.isfinite(sent0[4])

    def state():
        leaves = {}
        for n, uk in zip(ff._order, ff._ukeys):
            leaves[n] = mod._exec_group._exec.arg_dict[n].asnumpy()
            for i, l in enumerate(fused_update.flatten_state(
                    ff._updater.states[uk])[0]):
                leaves[n, i] = l.asnumpy()
        return leaves

    before = state()
    assert mod.fit_step(_batch(seed=7, zero_at=[(0, 1), (3, 4), (5, 4)]))
    after = state()
    assert len(before) == 5 * 4     # weight + (mean, var, master) a leaf
    for k in before:
        np.testing.assert_array_equal(before[k], after[k], err_msg=str(k))
    scaler = mod._loss_scaler
    scaler.publish()
    assert scaler.skips == 1
    sent1 = np.asarray(ff._sent_state)
    assert sent1[3] - sent0[3] == 2         # columns 1 and 4
    assert not np.isfinite(sent1[4])        # the norm saw them too

    assert mod.fit_step(_batch(seed=8))       # finite again: applied
    moved = state()
    assert all(not np.array_equal(before[n], moved[n]) for n in ff._order)
    scaler.publish()
    assert scaler.skips == 1
    assert np.asarray(ff._sent_state)[3] == sent1[3]


def test_fit_step_2bit_residuals_bit_identical_to_eager_path():
    """bf16 multi-precision Adam with 2-bit compression through
    ``Module.fit_step``: the fused program's error-feedback residuals
    (float32, on the master-gradient view) equal the eager fwd_bwd +
    kvstore path's BIT FOR BIT, and so do the bf16 weights.  Both
    widen the bf16 gradient the backward WROTE; while the fused step
    widened at the top of its program, XLA updated from the dot's
    unrounded accumulator and the residuals parted from the eager
    path's in their last bits."""
    res, weights = {}, {}
    for fused in (True, False):
        mod = _lp_mod(*_LP_CASES["adam_2bit"], fused=fused)
        for t in range(3):
            assert mod.fit_step(_batch(seed=t)) == fused
        if fused:
            res[fused] = {n: np.asarray(r) for n, r in
                          mod._fused_fit._residuals.items()}
        else:
            mod._kvstore._sync_engine()     # spill the flat buckets
            res[fused] = {k[0]: r.asnumpy() for k, r in
                          mod._kvstore._compression_residuals.items()}
        weights[fused] = {n: w.asnumpy()
                          for n, w in mod.get_params()[0].items()}
    assert sorted(res[True]) == sorted(res[False]) and res[True]
    for n in res[True]:
        assert res[True][n].dtype == np.float32
        np.testing.assert_array_equal(res[True][n], res[False][n],
                                      err_msg=n)
        np.testing.assert_array_equal(weights[True][n], weights[False][n],
                                      err_msg=n)
    assert any(float(np.abs(r).sum()) > 0 for r in res[True].values())


def test_cond_gradient_operands_keep_parameter_dtype():
    """Structure of the traced step: every gradient the scaler's
    ``cond`` takes as an operand has its parameter's dtype.  An operand
    of a conditional is a buffer in memory, so a float32 view of a
    bf16 gradient there is a second, wider copy of it."""
    import jax
    mod = _lp_mod("adam", {"learning_rate": 0.05, "multi_precision": True})
    fn, args, _ = mod._get_fused_fit()._prepare(_batch(), None)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    while len(jaxpr.eqns) == 1 and "jaxpr" in jaxpr.eqns[0].params:
        jaxpr = jaxpr.eqns[0].params["jaxpr"].jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    inputs = set(map(id, jaxpr.invars))
    # the cond's operands the step computed itself (not its arguments)
    computed = [v for v in conds[0].invars[1:]
                if hasattr(v, "count") and id(v) not in inputs]
    want = sorted((tuple(p.shape), str(p.dtype)) for p in args[0].values())
    got = sorted((tuple(v.aval.shape), str(v.aval.dtype)) for v in computed
                 if v.aval.shape)
    assert got == want and all(d == "bfloat16" for _, d in got)
