"""NHWC layout support + fused one-pass BatchNorm numerics.

Convolution/Pooling accept
channel-last layouts, the resnet builder threads layout end-to-end, and
training BatchNorm runs the one-pass fused schedule with a hand-derived
backward (ops/nn.py _bn_train_fused). These tests pin NHWC==NCHW
numerics and the BN gradient against autodiff of the naive formula.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.ops.registry import get_op


def test_conv_nhwc_matches_nchw():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 5, 9, 9).astype("float32")          # NCHW
    w = rng.randn(7, 5, 3, 3).astype("float32")          # OIHW
    b = rng.randn(7).astype("float32")
    conv = get_op("Convolution").fn
    want = np.asarray(conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                           kernel=(3, 3), num_filter=7, pad=(1, 1),
                           stride=(2, 2)))
    x_l = np.transpose(x, (0, 2, 3, 1))                  # NHWC
    w_l = np.transpose(w, (0, 2, 3, 1))                  # OHWI
    got = np.asarray(conv(jnp.asarray(x_l), jnp.asarray(w_l),
                          jnp.asarray(b), kernel=(3, 3), num_filter=7,
                          pad=(1, 1), stride=(2, 2), layout="NHWC"))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), want,
                               rtol=2e-5, atol=2e-5)


def test_conv_nhwc_grouped():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, 8, 8).astype("float32")
    w = rng.randn(6, 3, 3, 3).astype("float32")          # 2 groups
    conv = get_op("Convolution").fn
    want = np.asarray(conv(jnp.asarray(x), jnp.asarray(w), None,
                           kernel=(3, 3), num_filter=6, pad=(1, 1),
                           num_group=2, no_bias=True))
    got = np.asarray(conv(jnp.asarray(np.transpose(x, (0, 2, 3, 1))),
                          jnp.asarray(np.transpose(w, (0, 2, 3, 1))),
                          None, kernel=(3, 3), num_filter=6, pad=(1, 1),
                          num_group=2, no_bias=True, layout="NHWC"))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), want,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("ptype", ["max", "avg"])
def test_pooling_nhwc_matches_nchw(ptype):
    rng = np.random.RandomState(2)
    x = rng.randn(2, 4, 10, 10).astype("float32")
    pool = get_op("Pooling").fn
    want = np.asarray(pool(jnp.asarray(x), kernel=(3, 3), stride=(2, 2),
                           pad=(1, 1), pool_type=ptype))
    got = np.asarray(pool(jnp.asarray(np.transpose(x, (0, 2, 3, 1))),
                          kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                          pool_type=ptype, layout="NHWC"))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), want,
                               rtol=1e-5, atol=1e-6)


def test_pooling_nhwc_global():
    rng = np.random.RandomState(3)
    x = rng.randn(2, 4, 6, 6).astype("float32")
    pool = get_op("Pooling").fn
    want = np.asarray(pool(jnp.asarray(x), global_pool=True,
                           pool_type="avg", kernel=(1, 1)))
    got = np.asarray(pool(jnp.asarray(np.transpose(x, (0, 2, 3, 1))),
                          global_pool=True, pool_type="avg",
                          kernel=(1, 1), layout="NHWC"))
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), want,
                               rtol=1e-6)


def test_resnet_nhwc_forward_matches_nchw():
    """Same weights → same logits in either layout (transposed)."""
    from mxnet_tpu import models
    rng = np.random.RandomState(4)
    s_c = models.get_symbol("resnet", num_classes=7, num_layers=18,
                            image_shape=(3, 32, 32))
    s_l = models.get_symbol("resnet", num_classes=7, num_layers=18,
                            image_shape=(3, 32, 32), layout="NHWC")
    x = rng.rand(2, 3, 32, 32).astype("float32")

    ex_c = s_c.simple_bind(ctx=mx.cpu(), data=(2, 3, 32, 32),
                           grad_req="null")
    ex_l = s_l.simple_bind(ctx=mx.cpu(), data=(2, 32, 32, 3),
                           grad_req="null")
    rng2 = np.random.RandomState(5)
    for name in ex_c.arg_dict:
        if name in ("data", "softmax_label"):
            continue
        v = rng2.randn(*ex_c.arg_dict[name].shape).astype("float32") * 0.1
        ex_c.arg_dict[name][:] = v
        # conv weights transpose OIHW -> OHWI; everything else matches
        if ex_l.arg_dict[name].shape != ex_c.arg_dict[name].shape:
            ex_l.arg_dict[name][:] = np.transpose(v, (0, 2, 3, 1))
        else:
            ex_l.arg_dict[name][:] = v
    ex_c.arg_dict["data"][:] = x
    ex_l.arg_dict["data"][:] = np.transpose(x, (0, 2, 3, 1))
    for ex in (ex_c, ex_l):
        ex.arg_dict["softmax_label"][:] = np.zeros(2, "float32")
    out_c = ex_c.forward(is_train=False)[0].asnumpy()
    out_l = ex_l.forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(out_l, out_c, rtol=2e-3, atol=2e-4)


def test_bn_one_pass_matches_naive_fwd_bwd():
    """Fused BN (E[x^2]-E[x]^2 stats, custom backward) must match
    autodiff of the naive two-pass formulation."""
    rng = np.random.RandomState(6)
    x = (rng.randn(4, 3, 5, 5) * 2 + 1.5).astype("float32")
    g = (rng.rand(3) + 0.5).astype("float32")
    b = rng.randn(3).astype("float32")
    cot = rng.randn(4, 3, 5, 5).astype("float32")
    eps = 1e-3

    def naive(x, g, b):
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.var(x, axis=(0, 2, 3))
        xhat = ((x - mean[None, :, None, None])
                * jax.lax.rsqrt(var + eps)[None, :, None, None])
        return xhat * g[None, :, None, None] + b[None, :, None, None]

    want, vjp = jax.vjp(naive, jnp.asarray(x), jnp.asarray(g),
                        jnp.asarray(b))
    want_dx, want_dg, want_db = vjp(jnp.asarray(cot))

    from mxnet_tpu.ops.nn import _bn_train_fused
    f = _bn_train_fused(red=(0, 2, 3), bshape=(1, 3, 1, 1), eps=eps,
                        fix_gamma=False, n=float(4 * 5 * 5))

    def fused_out(x, g, b):
        return f(x, g, b)[0]

    got, vjp2 = jax.vjp(fused_out, jnp.asarray(x), jnp.asarray(g),
                        jnp.asarray(b))
    got_dx, got_dg, got_db = vjp2(jnp.asarray(cot))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_dx), np.asarray(want_dx),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_dg), np.asarray(want_dg),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_db), np.asarray(want_db),
                               rtol=1e-3, atol=1e-3)


def test_bn_fix_gamma_zero_grad():
    from mxnet_tpu.ops.nn import _bn_train_fused
    rng = np.random.RandomState(7)
    x = rng.randn(2, 4, 3).astype("float32")
    g = np.ones(4, "float32")
    b = np.zeros(4, "float32")
    f = _bn_train_fused(red=(0, 2), bshape=(1, 4, 1), eps=1e-3,
                        fix_gamma=True, n=6.0)

    def out(x, g, b):
        return f(x, g, b)[0]

    _, vjp = jax.vjp(out, jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    _, dg, db = vjp(jnp.ones((2, 4, 3), jnp.float32))
    np.testing.assert_array_equal(np.asarray(dg), np.zeros(4))
    assert np.abs(np.asarray(db)).sum() > 0


def test_bn_bf16_io_fp32_stats():
    """bf16 in/out; statistics still accumulate in fp32."""
    rng = np.random.RandomState(8)
    x = (rng.randn(8, 4, 16) + 3.0).astype("float32")
    xb = jnp.asarray(x, jnp.bfloat16)
    from mxnet_tpu.ops.nn import _bn_train_fused
    f = _bn_train_fused(red=(0, 2), bshape=(1, 4, 1), eps=1e-3,
                        fix_gamma=False, n=float(8 * 16))
    out, mean, var = f(xb, jnp.ones(4, jnp.bfloat16),
                       jnp.zeros(4, jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    assert mean.dtype == jnp.float32 and var.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(mean), x.mean(axis=(0, 2)),
                               rtol=2e-2)
    np.testing.assert_allclose(np.asarray(var), x.var(axis=(0, 2)),
                               rtol=6e-2, atol=3e-2)


def test_transformer_symbol_trains():
    """The transformer LM (models/transformer.py) memorizes a batch."""
    from mxnet_tpu import models
    from mxnet_tpu.parallel import TrainStep
    symb = models.get_symbol("transformer", num_classes=61, num_layers=2,
                             d_model=32, num_heads=4, seq_len=12)
    opt = mx.optimizer.Adam(learning_rate=2e-3)
    B, S = 4, 12
    ts = TrainStep(symb, opt, data_shapes={"data": (B, S)},
                   label_shapes={"softmax_label": (B * S,)})
    ts.init_params(mx.init.Xavier())
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 61, (B, S)).astype("float32")
    labels = np.roll(tokens, -1, axis=1).reshape(-1)
    batch = {"data": tokens, "softmax_label": labels}

    def loss_of(outs):
        prob = np.asarray(outs[0])
        return -np.log(np.maximum(
            prob[np.arange(B * S), labels.astype(int)], 1e-9)).mean()

    first = loss_of(ts.step(batch))
    for _ in range(60):
        outs = ts.step(batch)
    assert loss_of(outs) < first * 0.5


def test_causal_attention_op_matches_reference():
    from mxnet_tpu.parallel.ring_attention import attention_reference
    rng = np.random.RandomState(9)
    B, S, H, D = 2, 8, 2, 4
    d = H * D
    qkv = rng.randn(B, S, 3 * d).astype("float32") * 0.3
    op = get_op("_contrib_CausalSelfAttention").fn
    got = np.asarray(op(jnp.asarray(qkv), num_heads=H))
    q, k, v = np.split(qkv, 3, axis=-1)
    ref = attention_reference(jnp.asarray(q.reshape(B, S, H, D)),
                              jnp.asarray(k.reshape(B, S, H, D)),
                              jnp.asarray(v.reshape(B, S, H, D)),
                              causal=True)
    np.testing.assert_allclose(got, np.asarray(ref).reshape(B, S, d),
                               rtol=2e-4, atol=2e-5)
