#!/usr/bin/env python
"""DCGAN on synthetic images (reference example/gan/dcgan.py shape).

Generator: FC -> reshape -> 2x Deconvolution upsampling to 16x16.
Discriminator: 2x Convolution -> FC -> logistic. Trained adversarially
through TWO Modules sharing one minibatch per step, exactly the
reference's module-pair flow (modG forward -> modD fwd/bwd on fake +
real -> modG backward with modD's input gradient).

The synthetic "real" distribution is bright centered squares on dark
background; success = discriminator cannot tell generated from real
much better than chance at the end while both losses stay finite.

Run:  JAX_PLATFORMS=cpu python example/gan/dcgan.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def make_generator(ngf=16, code_dim=16):
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    code = sym.Variable("code")                       # (B, code_dim)
    g = sym.FullyConnected(code, num_hidden=ngf * 2 * 4 * 4, name="g_fc")
    g = sym.Activation(g, act_type="relu")
    g = sym.Reshape(g, shape=(-1, ngf * 2, 4, 4), name="g_reshape")
    g = sym.Deconvolution(g, kernel=(4, 4), stride=(2, 2), pad=(1, 1),
                          num_filter=ngf, name="g_deconv1")   # 8x8
    g = sym.BatchNorm(g, fix_gamma=False, name="g_bn1")
    g = sym.Activation(g, act_type="relu")
    g = sym.Deconvolution(g, kernel=(4, 4), stride=(2, 2), pad=(1, 1),
                          num_filter=1, name="g_deconv2")     # 16x16
    return sym.Activation(g, act_type="sigmoid", name="g_out")


def make_discriminator(ndf=16):
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    data = sym.Variable("data")                       # (B, 1, 16, 16)
    d = sym.Convolution(data, kernel=(4, 4), stride=(2, 2), pad=(1, 1),
                        num_filter=ndf, name="d_conv1")
    d = sym.LeakyReLU(d, act_type="leaky", slope=0.2)
    d = sym.Convolution(d, kernel=(4, 4), stride=(2, 2), pad=(1, 1),
                        num_filter=ndf * 2, name="d_conv2")
    d = sym.LeakyReLU(d, act_type="leaky", slope=0.2)
    d = sym.FullyConnected(sym.Flatten(d), num_hidden=1, name="d_fc")
    label = sym.Variable("label")
    return sym.LogisticRegressionOutput(d, label, name="dloss")


def real_batch(rng, batch):
    """Bright 6x6..10x10 squares centered-ish on a dark field."""
    x = rng.rand(batch, 1, 16, 16).astype("float32") * 0.1
    for i in range(batch):
        s = rng.randint(3, 6)
        cy, cx = rng.randint(4, 12, 2)
        x[i, 0, max(0, cy - s):cy + s, max(0, cx - s):cx + s] = \
            0.8 + 0.2 * rng.rand()
    return x


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--num-iter", type=int, default=120)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--code-dim", type=int, default=16)
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    # deterministic init: Module's host-side initializer draws from the
    # global numpy RNG
    np.random.seed(11)
    mx.random.seed(11)

    B = args.batch_size
    gen = make_generator(code_dim=args.code_dim)
    dis = make_discriminator()

    modG = mx.Module(gen, data_names=["code"], label_names=[],
                     context=mx.cpu())
    modG.bind(data_shapes=[("code", (B, args.code_dim))])
    modG.init_params(mx.initializer.Normal(0.05))
    modG.init_optimizer(optimizer="adam",
                        optimizer_params={"learning_rate": args.lr,
                                          "beta1": 0.5})

    modD = mx.Module(dis, data_names=["data"], label_names=["label"],
                     context=mx.cpu())
    modD.bind(data_shapes=[("data", (B, 1, 16, 16))],
              label_shapes=[("label", (B,))], inputs_need_grad=True)
    modD.init_params(mx.initializer.Normal(0.05))
    modD.init_optimizer(optimizer="adam",
                        optimizer_params={"learning_rate": args.lr,
                                          "beta1": 0.5})

    from mxnet_tpu.io.io import DataBatch
    rng = np.random.RandomState(0)
    ones = nd.ones((B,))
    zeros = nd.zeros((B,))

    def d_acc(outs, want_real):
        p = outs[0].asnumpy().reshape(-1)
        return float(((p > 0.5) == want_real).mean())

    accs = []
    for it in range(args.num_iter):
        code = nd.array(rng.randn(B, args.code_dim).astype("float32"))
        modG.forward(DataBatch([code], []), is_train=True)
        fake = modG.get_outputs()[0]

        # train D on fake (label 0)
        modD.forward(DataBatch([fake], [zeros]), is_train=True)
        acc_fake = d_acc(modD.get_outputs(), want_real=False)
        modD.backward()
        modD.update()

        # train D on real (label 1)
        real = nd.array(real_batch(rng, B))
        modD.forward(DataBatch([real], [ones]), is_train=True)
        acc_real = d_acc(modD.get_outputs(), want_real=True)
        modD.backward()
        modD.update()

        # train G: D(fake) should be 1 — reuse D with label 1
        modD.forward(DataBatch([fake], [ones]), is_train=True)
        modD.backward()
        gen_grad = modD.get_input_grads()[0]
        modG.backward([gen_grad])
        modG.update()

        accs.append((acc_fake + acc_real) / 2)
        if it % 20 == 0 or it == args.num_iter - 1:
            fk = fake.asnumpy()
            print("iter %3d: D acc %.2f, fake mean %.3f std %.3f"
                  % (it, accs[-1], fk.mean(), fk.std()))

    fake_np = fake.asnumpy()
    assert np.isfinite(fake_np).all()
    # the generator must have moved off its init (near-uniform 0.5) and
    # produce contrast; the discriminator shouldn't win completely
    assert fake_np.std() > 0.05, fake_np.std()
    tail_acc = float(np.mean(accs[-20:]))
    assert tail_acc < 0.995, tail_acc
    print("dcgan example OK (tail D acc %.3f)" % tail_acc)


if __name__ == "__main__":
    main()
