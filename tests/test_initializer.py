"""Initializer semantics (reference tests/python/unittest/test_init.py
strategy + python/mxnet/initializer.py behaviors): name-suffix dispatch,
statistical properties of the weight rules, structural properties of
Orthogonal/Bilinear, Mixed pattern routing, and the device-init
equivalence used by TrainStep.
"""
import json

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.initializer import InitDesc


def _init(initializer, name, shape):
    arr = nd.zeros(shape)
    initializer(InitDesc(name), arr)
    return arr.asnumpy()


def test_name_suffix_dispatch():
    init = mx.init.Xavier()
    assert np.all(_init(init, "fc1_bias", (8,)) == 0)
    assert np.all(_init(init, "bn_gamma", (8,)) == 1)
    assert np.all(_init(init, "bn_beta", (8,)) == 0)
    assert np.all(_init(init, "bn_moving_mean", (8,)) == 0)
    assert np.all(_init(init, "bn_moving_var", (8,)) == 1)
    w = _init(init, "fc1_weight", (64, 64))
    assert w.std() > 0


def test_uniform_normal_constant():
    mx.random.seed(0)
    u = _init(mx.init.Uniform(0.3), "w_weight", (100, 100))
    assert abs(u.max()) <= 0.3 and abs(u.min()) <= 0.3 and u.std() > 0.1
    n = _init(mx.init.Normal(0.5), "w_weight", (100, 100))
    assert abs(n.std() - 0.5) < 0.02
    c = _init(mx.init.Constant(2.5), "w_weight", (4, 4))
    assert np.all(c == 2.5)


def test_xavier_magnitude():
    mx.random.seed(0)
    fan_in = fan_out = 256
    w = _init(mx.init.Xavier(rnd_type="gaussian", factor_type="avg",
                             magnitude=3), "w_weight", (fan_out, fan_in))
    expect_std = np.sqrt(3.0 / ((fan_in + fan_out) / 2.0))
    assert abs(w.std() - expect_std) < 0.01


def test_msra_prelu():
    mx.random.seed(0)
    w = _init(mx.init.MSRAPrelu(factor_type="in", slope=0.0),
              "w_weight", (256, 256))
    assert abs(w.std() - np.sqrt(2.0 / 256)) < 0.01


def test_orthogonal_rows():
    mx.random.seed(0)
    w = _init(mx.init.Orthogonal(scale=1.0), "w_weight", (32, 64))
    wwt = w @ w.T
    np.testing.assert_allclose(wwt, np.eye(32), atol=1e-4)


def test_bilinear_upsampling_kernel():
    w = _init(mx.init.Bilinear(), "up_weight", (1, 1, 4, 4))
    k = w[0, 0]
    np.testing.assert_allclose(k, k.T, atol=1e-6)      # symmetric
    assert k.max() <= 1.0 and k.min() > 0


def test_mixed_pattern_routing():
    """Mixed routes by pattern to an inner initializer, which then
    applies its OWN name-suffix dispatch (reference Mixed semantics:
    Constant on a ``_bias`` name still hits _init_bias -> 0)."""
    init = mx.init.Mixed([".*fancy_weight", ".*"],
                         [mx.init.Constant(7.0), mx.init.Zero()])
    assert np.all(_init(init, "fc_fancy_weight", (4, 4)) == 7.0)
    assert np.all(_init(init, "fc_weight", (4, 4)) == 0.0)
    # suffix dispatch inside the routed initializer is preserved
    assert np.all(_init(init, "fc_bias", (4,)) == 0.0)


def test_load_initializer_with_default():
    params = {"fc_weight": nd.ones((3, 3)) * 2}
    init = mx.init.Load(params, default_init=mx.init.Zero())
    assert np.all(_init(init, "fc_weight", (3, 3)) == 2.0)
    assert np.all(_init(init, "other_weight", (3, 3)) == 0.0)


def test_initializer_dumps_roundtrip():
    """Serialized init attrs (Variable(init=...)) parse back (reference
    initializer JSON attr convention)."""
    s = mx.init.Xavier(rnd_type="uniform", factor_type="in",
                       magnitude=2.34).dumps()
    klass, kwargs = json.loads(s)
    assert klass.lower() == "xavier"
    assert abs(kwargs["magnitude"] - 2.34) < 1e-9
    inst = mx.init.get(klass, **kwargs)
    assert isinstance(inst, mx.init.Xavier)


def test_device_init_matches_host_rules():
    """TrainStep's device-side init (_device_init_rule) must follow the
    same name rules as the host Initializer."""
    from mxnet_tpu.parallel.trainer import _device_init_rule
    import jax

    init = mx.init.Xavier()
    key = jax.random.key(0)
    rule = _device_init_rule(init, "bn_gamma", None, (8,), "float32")
    assert np.all(np.asarray(rule(key)) == 1)
    rule = _device_init_rule(init, "fc_bias", None, (8,), "float32")
    assert np.all(np.asarray(rule(key)) == 0)
    rule = _device_init_rule(init, "fc_weight", None, (64, 64), "float32")
    w = np.asarray(rule(key))
    assert w.std() > 0
    # custom subclasses have no closed-form device rule -> host fallback
    class My(mx.init.Xavier):
        def _init_weight(self, name, arr):
            arr[:] = 5.0
    assert _device_init_rule(My(), "fc_weight", None, (4, 4),
                             "float32") is None
