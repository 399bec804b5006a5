"""Operator correctness (parity model: tests/python/unittest/test_operator.py).

Forward checks against NumPy; gradients via the numeric-gradient harness
(central differences vs the executor's jax.vjp autodiff)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  check_symbolic_forward, simple_forward)


def test_fully_connected():
    x = np.random.randn(4, 7).astype("float32")
    w = np.random.randn(5, 7).astype("float32")
    b = np.random.randn(5).astype("float32")
    out = nd.FullyConnected(nd.array(x), nd.array(w), nd.array(b), num_hidden=5)
    assert_almost_equal(out, x @ w.T + b, rtol=1e-4)
    # flatten semantics
    x3 = np.random.randn(2, 3, 4).astype("float32")
    w2 = np.random.randn(6, 12).astype("float32")
    out2 = nd.FullyConnected(nd.array(x3), nd.array(w2), nd.array(np.zeros(6, "float32")),
                             num_hidden=6)
    assert_almost_equal(out2, x3.reshape(2, 12) @ w2.T, rtol=1e-4)


def test_fully_connected_grad():
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, num_hidden=3, name="fc")
    check_numeric_gradient(fc, {"data": np.random.randn(2, 4),
                                "fc_weight": np.random.randn(3, 4),
                                "fc_bias": np.random.randn(3)})


def test_activation():
    x = np.array([[-1.0, 0.0, 2.0]], dtype="float32")
    assert_almost_equal(nd.Activation(nd.array(x), act_type="relu"), [[0, 0, 2]])
    assert_almost_equal(nd.Activation(nd.array(x), act_type="sigmoid"),
                        1 / (1 + np.exp(-x)), rtol=1e-4)
    assert_almost_equal(nd.Activation(nd.array(x), act_type="tanh"),
                        np.tanh(x), rtol=1e-4)
    assert_almost_equal(nd.Activation(nd.array(x), act_type="softrelu"),
                        np.log1p(np.exp(x)), rtol=1e-4)


def test_leaky_relu():
    x = np.array([-2.0, 3.0], dtype="float32")
    assert_almost_equal(nd.LeakyReLU(nd.array(x), act_type="leaky", slope=0.1),
                        [-0.2, 3.0], rtol=1e-5)
    assert_almost_equal(nd.LeakyReLU(nd.array(x), act_type="elu", slope=1.0),
                        [np.expm1(-2.0), 3.0], rtol=1e-5)


def test_convolution_forward():
    x = np.random.randn(2, 3, 8, 8).astype("float32")
    w = np.random.randn(4, 3, 3, 3).astype("float32")
    b = np.random.randn(4).astype("float32")
    out = nd.Convolution(nd.array(x), nd.array(w), nd.array(b),
                         kernel=(3, 3), num_filter=4, pad=(1, 1))
    assert out.shape == (2, 4, 8, 8)
    # spot check vs naive conv: output (1,1) window covers x[0:3, 0:3]
    expect = (x[0, :, 0:3, 0:3] * w[1]).sum() + b[1]
    assert abs(float(out.asnumpy()[0, 1, 1, 1]) - expect) < 1e-2


def test_convolution_grad():
    data = sym.Variable("data")
    conv = sym.Convolution(data, kernel=(2, 2), num_filter=2, name="conv")
    check_numeric_gradient(conv, {"data": np.random.randn(1, 2, 4, 4),
                                  "conv_weight": np.random.randn(2, 2, 2, 2),
                                  "conv_bias": np.random.randn(2)},
                           numeric_eps=1e-2, rtol=5e-2, atol=5e-2)


def test_convolution_groups_stride_dilate():
    x = np.random.randn(1, 4, 9, 9).astype("float32")
    w = np.random.randn(4, 2, 3, 3).astype("float32")
    out = nd.Convolution(nd.array(x), nd.array(w), no_bias=True,
                         kernel=(3, 3), num_filter=4, num_group=2,
                         stride=(2, 2), dilate=(2, 2))
    assert out.shape == (1, 4, 3, 3)


def test_deconvolution():
    x = np.random.randn(1, 3, 5, 5).astype("float32")
    w = np.random.randn(3, 2, 4, 4).astype("float32")
    out = nd.Deconvolution(nd.array(x), nd.array(w), kernel=(4, 4),
                           num_filter=2, stride=(2, 2), pad=(1, 1))
    assert out.shape == (1, 2, 10, 10)
    # deconv(conv) shape inverse property via numeric grad path
    data = sym.Variable("data")
    dc = sym.Deconvolution(data, kernel=(2, 2), num_filter=2, name="dc",
                           no_bias=True)
    check_numeric_gradient(dc, {"data": np.random.randn(1, 1, 3, 3),
                                "dc_weight": np.random.randn(1, 2, 2, 2)},
                           numeric_eps=1e-2, rtol=5e-2, atol=5e-2)


def test_pooling():
    x = np.arange(16, dtype="float32").reshape(1, 1, 4, 4)
    out = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2), pool_type="max")
    assert out.asnumpy().reshape(2, 2).tolist() == [[5, 7], [13, 15]]
    out = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2), pool_type="avg")
    assert out.asnumpy().reshape(2, 2).tolist() == [[2.5, 4.5], [10.5, 12.5]]
    out = nd.Pooling(nd.array(x), global_pool=True, pool_type="max", kernel=(1, 1))
    assert float(out.asnumpy().ravel()[0]) == 15
    # 'full' convention rounds up output size
    out_full = nd.Pooling(nd.array(x), kernel=(3, 3), stride=(2, 2),
                          pool_type="max", pooling_convention="full")
    assert out_full.shape == (1, 1, 2, 2)


def test_batchnorm_train_eval():
    x = np.random.randn(8, 3, 4, 4).astype("float32") * 2 + 5
    data = sym.Variable("data")
    bn = sym.BatchNorm(data, fix_gamma=False, name="bn")
    ex = bn.simple_bind(mx.cpu(), "write", data=x.shape)
    ex.arg_dict["bn_gamma"][:] = 1.0
    with_mean = ex.forward(is_train=True, data=x)[0].asnumpy()
    # normalized per-channel: ~0 mean, ~1 std
    assert abs(with_mean.mean(axis=(0, 2, 3))).max() < 1e-3
    assert abs(with_mean.std(axis=(0, 2, 3)) - 1).max() < 1e-2
    # eval mode normalizes with the moving stats exactly
    mm = ex.aux_dict["bn_moving_mean"].asnumpy().reshape(1, 3, 1, 1)
    mv = ex.aux_dict["bn_moving_var"].asnumpy().reshape(1, 3, 1, 1)
    out_eval = ex.forward(is_train=False, data=x)[0].asnumpy()
    expect = (x - mm) / np.sqrt(mv + 1e-3)
    assert abs(out_eval - expect).max() < 1e-3


def test_batchnorm_grad():
    data = sym.Variable("data")
    bn = sym.BatchNorm(data, fix_gamma=False, eps=1e-3, name="bn")
    check_numeric_gradient(
        bn, {"data": np.random.randn(4, 2, 3, 3),
             "bn_gamma": np.random.uniform(0.5, 1.5, 2),
             "bn_beta": np.random.randn(2)},
        aux_states={"bn_moving_mean": np.zeros(2), "bn_moving_var": np.ones(2)},
        numeric_eps=1e-2, rtol=0.1, atol=5e-2)


def test_layernorm():
    x = np.random.randn(4, 10).astype("float32")
    g = np.random.uniform(0.5, 1.5, 10).astype("float32")
    b = np.random.randn(10).astype("float32")
    out = nd.LayerNorm(nd.array(x), nd.array(g), nd.array(b), axis=-1, eps=1e-5)
    mean = x.mean(-1, keepdims=True)
    std = x.std(-1, keepdims=True)
    expect = (x - mean) / np.sqrt(std**2 + 1e-5) * g + b
    assert_almost_equal(out, expect, rtol=1e-3, atol=1e-4)


def test_softmax_output_grad_semantics():
    # SoftmaxOutput backward = (softmax - onehot), ignoring out_grad
    x = np.random.randn(3, 5).astype("float32")
    y = np.array([0, 2, 4], dtype="float32")
    data = sym.Variable("data")
    label = sym.Variable("label")
    smo = sym.SoftmaxOutput(data, label, name="smo")
    ex = smo.simple_bind(mx.cpu(), {"data": "write", "label": "null"},
                         data=(3, 5), label=(3,))
    ex.forward(is_train=True, data=x, label=y)
    ex.backward()
    prob = np.exp(x) / np.exp(x).sum(-1, keepdims=True)
    oh = np.eye(5)[y.astype(int)]
    assert_almost_equal(ex.grad_dict["data"], prob - oh, rtol=1e-4, atol=1e-5)


def test_softmax_output_ignore_label():
    x = np.random.randn(4, 3).astype("float32")
    y = np.array([0, 1, -1, 2], dtype="float32")
    data, label = sym.Variable("data"), sym.Variable("label")
    smo = sym.SoftmaxOutput(data, label, use_ignore=True, ignore_label=-1,
                            name="smo")
    ex = smo.simple_bind(mx.cpu(), {"data": "write", "label": "null"},
                         data=(4, 3), label=(4,))
    ex.forward(is_train=True, data=x, label=y)
    ex.backward()
    g = ex.grad_dict["data"].asnumpy()
    assert np.allclose(g[2], 0)  # ignored row has zero grad
    assert not np.allclose(g[0], 0)


def test_dropout():
    x = nd.ones((1000,))
    with mx.autograd.train_mode():
        out = nd.Dropout(x, p=0.5)
    arr = out.asnumpy()
    frac_zero = (arr == 0).mean()
    assert 0.35 < frac_zero < 0.65
    assert np.allclose(arr[arr != 0], 2.0)
    # eval mode: identity
    out_eval = nd.Dropout(x, p=0.5)
    assert np.allclose(out_eval.asnumpy(), 1.0)


def test_embedding():
    w = np.random.randn(10, 4).astype("float32")
    idx = np.array([1, 5, 1], dtype="float32")
    out = nd.Embedding(nd.array(idx), nd.array(w), input_dim=10, output_dim=4)
    assert_almost_equal(out, w[[1, 5, 1]])


def test_elemwise_and_broadcast():
    a = np.random.randn(3, 1).astype("float32")
    b = np.random.randn(1, 4).astype("float32")
    assert_almost_equal(nd.broadcast_add(nd.array(a), nd.array(b)), a + b)
    assert_almost_equal(nd.broadcast_maximum(nd.array(a), nd.array(b)),
                        np.maximum(a, b))
    x = np.random.rand(5).astype("float32") + 0.5
    assert_almost_equal(nd.sqrt(nd.array(x)), np.sqrt(x), rtol=1e-4)
    assert_almost_equal(nd.log(nd.array(x)), np.log(x), rtol=1e-4)
    assert_almost_equal(nd.exp(nd.array(x)), np.exp(x), rtol=1e-4)
    assert_almost_equal(nd.square(nd.array(x)), x * x, rtol=1e-4)
    assert_almost_equal(nd.sign(nd.array(np.array([-2.0, 0.0, 3.0]))), [-1, 0, 1])


def test_dot():
    a = np.random.randn(3, 4).astype("float32")
    b = np.random.randn(4, 5).astype("float32")
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b)), a @ b, rtol=1e-4)
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b.T), transpose_b=True),
                        a @ b, rtol=1e-4)
    assert_almost_equal(nd.dot(nd.array(a.T), nd.array(b), transpose_a=True),
                        a @ b, rtol=1e-4)
    x = np.random.randn(2, 3, 4).astype("float32")
    y = np.random.randn(2, 4, 5).astype("float32")
    assert_almost_equal(nd.batch_dot(nd.array(x), nd.array(y)), x @ y, rtol=1e-4)


def test_reshape_magic():
    x = nd.zeros((2, 3, 4))
    assert nd.Reshape(x, shape=(-1,)).shape == (24,)
    assert nd.Reshape(x, shape=(0, -1)).shape == (2, 12)
    assert nd.Reshape(x, shape=(-2,)).shape == (2, 3, 4)
    assert nd.Reshape(x, shape=(-3, 0)).shape == (6, 4)
    assert nd.Reshape(x, shape=(-4, 1, 2, -2)).shape == (1, 2, 3, 4)
    assert nd.Reshape(x, shape=(0, -4, -1, 3, 0)).shape == (2, 1, 3, 4)


def test_slice_ops():
    x = nd.array(np.arange(24).reshape(2, 3, 4))
    out = nd.slice(x, begin=(0, 1), end=(2, 3))
    assert out.shape == (2, 2, 4)
    out = nd.slice_axis(x, axis=2, begin=1, end=3)
    assert out.shape == (2, 3, 2)
    out = nd.take(x, nd.array([0, 0, 1]), axis=1)
    assert out.shape == (2, 3, 4)


def test_transpose_concat_split():
    x = nd.array(np.arange(6).reshape(2, 3))
    assert nd.transpose(x).shape == (3, 2)
    c = nd.Concat(x, x, dim=0)
    assert c.shape == (4, 3)
    parts = nd.SliceChannel(c, num_outputs=2, axis=0)
    assert len(parts) == 2 and parts[0].shape == (2, 3)


def test_softmax_ops():
    x = np.random.randn(2, 5).astype("float32")
    expect = np.exp(x) / np.exp(x).sum(-1, keepdims=True)
    assert_almost_equal(nd.softmax(nd.array(x)), expect, rtol=1e-4)
    assert_almost_equal(nd.log_softmax(nd.array(x)), np.log(expect), rtol=1e-3,
                        atol=1e-4)


def test_one_hot_pick():
    idx = nd.array([0, 2])
    oh = nd.one_hot(idx, depth=3)
    assert oh.asnumpy().tolist() == [[1, 0, 0], [0, 0, 1]]
    x = nd.array([[0.1, 0.2, 0.3], [0.4, 0.5, 0.6]])
    p = nd.pick(x, nd.array([1, 2]), axis=1)
    assert_almost_equal(p, [0.2, 0.6])


def test_ordering():
    x = np.array([[3.0, 1.0, 2.0], [0.5, 2.5, 1.5]], dtype="float32")
    s = nd.sort(nd.array(x), axis=1)
    assert s.asnumpy()[0].tolist() == [1, 2, 3]
    a = nd.argsort(nd.array(x), axis=1)
    assert a.asnumpy()[0].tolist() == [1, 2, 0]
    v, i = nd.topk(nd.array(x), k=2, axis=1, ret_typ="both")
    assert v.asnumpy()[0].tolist() == [3, 2]
    assert i.asnumpy()[0].tolist() == [0, 2]


def test_sequence_ops():
    x = np.arange(24, dtype="float32").reshape(4, 2, 3)  # (seq, batch, feat)
    lens = np.array([2, 3], dtype="float32")
    masked = nd.SequenceMask(nd.array(x), nd.array(lens),
                             use_sequence_length=True, value=-1.0)
    m = masked.asnumpy()
    assert np.allclose(m[2:, 0], -1)
    assert np.allclose(m[3:, 1], -1)
    last = nd.SequenceLast(nd.array(x), nd.array(lens), use_sequence_length=True)
    assert np.allclose(last.asnumpy()[0], x[1, 0])
    assert np.allclose(last.asnumpy()[1], x[2, 1])


def test_clip_where():
    x = nd.array([-5.0, 0.5, 5.0])
    assert nd.clip(x, a_min=-1, a_max=1).asnumpy().tolist() == [-1, 0.5, 1]
    cond = nd.array([1.0, 0.0, 1.0])
    out = nd.where(cond, nd.ones((3,)), nd.zeros((3,)))
    assert out.asnumpy().tolist() == [1, 0, 1]


def test_upsampling():
    x = nd.array(np.arange(4, dtype="float32").reshape(1, 1, 2, 2))
    out = nd.UpSampling(x, scale=2, sample_type="nearest")
    assert out.shape == (1, 1, 4, 4)
    assert out.asnumpy()[0, 0, 0].tolist() == [0, 0, 1, 1]


def test_block_grad():
    data = sym.Variable("data")
    blocked = sym.BlockGrad(data * 2.0)
    out = blocked + data
    ex = out.simple_bind(mx.cpu(), "write", data=(2,))
    ex.forward(is_train=True, data=np.array([1.0, 2.0], "float32"))
    ex.backward(nd.ones((2,)))
    assert ex.grad_dict["data"].asnumpy().tolist() == [1, 1]


def test_rnn_shapes_and_grad():
    seq, batch, insz, h = 3, 2, 4, 5
    from mxnet_tpu.ops.rnn import rnn_param_size
    psz = rnn_param_size(1, insz, h, False, "lstm")
    x = np.random.randn(seq, batch, insz).astype("float32")
    params = np.random.randn(psz).astype("float32") * 0.1
    state = np.zeros((1, batch, h), "float32")
    cell = np.zeros((1, batch, h), "float32")
    out = nd.RNN(nd.array(x), nd.array(params), nd.array(state), nd.array(cell),
                 state_size=h, num_layers=1, mode="lstm")
    assert out.shape == (seq, batch, h)
    outs = nd.RNN(nd.array(x), nd.array(params), nd.array(state), nd.array(cell),
                  state_size=h, num_layers=1, mode="lstm", state_outputs=True)
    assert outs[1].shape == (1, batch, h) and outs[2].shape == (1, batch, h)
    # gru / vanilla / bidirectional
    for mode in ("gru", "rnn_tanh", "rnn_relu"):
        psz2 = rnn_param_size(1, insz, h, False, mode)
        o = nd.RNN(nd.array(x), nd.array(np.random.randn(psz2).astype("float32") * 0.1),
                   nd.array(state), state_size=h, num_layers=1, mode=mode)
        assert o.shape == (seq, batch, h)
    psz3 = rnn_param_size(2, insz, h, True, "lstm")
    o = nd.RNN(nd.array(x), nd.array(np.random.randn(psz3).astype("float32") * 0.1),
               nd.array(np.zeros((4, batch, h), "float32")),
               nd.array(np.zeros((4, batch, h), "float32")),
               state_size=h, num_layers=2, bidirectional=True, mode="lstm")
    assert o.shape == (seq, batch, 2 * h)


def test_optimizer_update_ops():
    w = nd.array([1.0, 2.0])
    g = nd.array([0.5, 0.5])
    out = nd.sgd_update(w, g, lr=0.1)
    assert_almost_equal(out, [0.95, 1.95])
    mom = nd.zeros((2,))
    out = nd.sgd_mom_update(w, g, mom, lr=0.1, momentum=0.9)
    assert_almost_equal(out, [0.95, 1.95])
    assert_almost_equal(mom, [-0.05, -0.05])  # state mutated in place
    mean, var = nd.zeros((2,)), nd.zeros((2,))
    out = nd.adam_update(w, g, mean, var, lr=0.1)
    assert float(mean.asnumpy()[0]) != 0  # state updated
    assert out.shape == (2,)


def test_regression_outputs():
    x = np.random.randn(4, 3).astype("float32")
    y = np.random.randn(4, 3).astype("float32")
    data, label = sym.Variable("data"), sym.Variable("label")
    lro = sym.LinearRegressionOutput(data, label)
    ex = lro.simple_bind(mx.cpu(), {"data": "write", "label": "null"},
                         data=(4, 3), label=(4, 3))
    out = ex.forward(is_train=True, data=x, label=y)
    assert_almost_equal(out[0], x)
    ex.backward()
    assert_almost_equal(ex.grad_dict["data"], (x - y) / 4, rtol=1e-4)


def test_cast_and_init_ops():
    out = nd._zeros(shape=(2, 3), dtype="float16")
    assert out.dtype == np.float16 and out.shape == (2, 3)
    out = nd._arange(start=1, stop=7, step=2)
    assert out.asnumpy().tolist() == [1, 3, 5]
    x = nd.ones((2,), dtype="float32")
    assert nd.Cast(x, dtype="int32").dtype == np.int32
    e = nd._eye(N=3)
    assert e.asnumpy().tolist() == np.eye(3).tolist()


def test_norm_and_l2norm():
    x = np.random.randn(3, 4).astype("float32")
    assert abs(float(nd.norm(nd.array(x)).asscalar()) - np.linalg.norm(x)) < 1e-4
    out = nd.L2Normalization(nd.array(x), mode="instance")
    expect = x / np.sqrt((x**2).sum(1, keepdims=True) + 1e-10)
    assert_almost_equal(out, expect, rtol=1e-4)
