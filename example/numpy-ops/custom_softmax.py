#!/usr/bin/env python
"""CustomOp in pure numpy (reference example/numpy-ops/custom_softmax.py):
a user-defined softmax forward/backward runs inside a compiled graph via
the CustomOp trampoline (operator.py -> jax.pure_callback +
custom_vjp), and an MLP using it trains through Module.fit.

Run: JAX_PLATFORMS=cpu python \
         example/numpy-ops/custom_softmax.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def define_op():
    import mxnet_tpu as mx

    class Softmax(mx.operator.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            x = in_data[0].asnumpy()
            e = np.exp(x - x.max(axis=1, keepdims=True))
            y = e / e.sum(axis=1, keepdims=True)
            self.assign(out_data[0], req[0], mx.nd.array(y))

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            # fused softmax+CE gradient: label arrives as in_data[1]
            y = out_data[0].asnumpy().copy()
            label = in_data[1].asnumpy().astype(np.int64)
            y[np.arange(y.shape[0]), label] -= 1.0
            self.assign(in_grad[0], req[0], mx.nd.array(y / y.shape[0]))

    @mx.operator.register("demo_softmax")
    class SoftmaxProp(mx.operator.CustomOpProp):
        def __init__(self):
            super().__init__(need_top_grad=False)

        def list_arguments(self):
            return ["data", "label"]

        def list_outputs(self):
            return ["output"]

        def infer_shape(self, in_shape):
            data_shape = in_shape[0]
            label_shape = (in_shape[0][0],)
            return [data_shape, label_shape], [data_shape], []

        def create_operator(self, ctx, shapes, dtypes):
            return Softmax()

    return Softmax


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-epoch", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=32)
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import sym

    define_op()

    # deterministic init: Module's host-side initializer draws from the
    # global numpy RNG
    np.random.seed(42)
    mx.random.seed(42)
    rng = np.random.RandomState(3)
    N = 512
    X = rng.rand(N, 16).astype("float32") * 0.1
    y = rng.randint(0, 4, N)
    for i in range(N):
        X[i, y[i] * 4:(y[i] + 1) * 4] += 1.0

    data = sym.Variable("data")
    fc1 = sym.FullyConnected(data, num_hidden=32, name="fc1")
    act = sym.Activation(fc1, act_type="relu")
    fc2 = sym.FullyConnected(act, num_hidden=4, name="fc2")
    net = sym.Custom(fc2, sym.Variable("softmax_label"),
                     op_type="demo_softmax", name="softmax")

    it = mx.io.NDArrayIter(X, y.astype("float32"), args.batch_size,
                           shuffle=True)
    mod = mx.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=args.num_epoch, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier(), eval_metric="acc")
    it.reset()
    acc = mod.score(it, "acc")[0][1]
    print("custom-softmax val acc %.3f" % acc)
    assert acc > 0.95, acc
    print("numpy-ops custom_softmax example OK")


if __name__ == "__main__":
    main()
