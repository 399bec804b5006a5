#!/usr/bin/env python
"""CNN text classification (reference example/cnn_text_classification/
text_cnn.py — the Kim-2014 architecture): Embedding -> parallel convs
with window sizes 3/4/5 over the token axis -> max-over-time pooling ->
concat -> dropout -> FC softmax.

Synthetic task: sequences containing the trigram [7, 8, 9] are class 1
— exactly the pattern a width-3 text conv learns. Converges to >95%
in a few epochs on CPU.

Run: JAX_PLATFORMS=cpu python \
         example/cnn_text_classification/text_cnn.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def make_text_cnn(vocab, seq_len, embed_dim=16, num_filter=8,
                  windows=(3, 4, 5), num_classes=2, dropout=0.25):
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    data = sym.Variable("data")                       # (B, seq)
    emb = sym.Embedding(data, input_dim=vocab, output_dim=embed_dim,
                        name="embed")                 # (B, seq, E)
    emb = sym.Reshape(emb, shape=(0, 1, seq_len, embed_dim),
                      name="embed_4d")                # (B, 1, seq, E)
    pooled = []
    for w in windows:
        c = sym.Convolution(emb, kernel=(w, embed_dim),
                            num_filter=num_filter, name="conv%d" % w)
        c = sym.Activation(c, act_type="relu")
        c = sym.Pooling(c, global_pool=True, kernel=(1, 1),
                        pool_type="max", name="pool%d" % w)
        pooled.append(sym.Flatten(c))
    h = sym.Concat(*pooled, dim=1, name="concat")
    if dropout > 0:
        h = sym.Dropout(h, p=dropout, name="drop")
    fc = sym.FullyConnected(h, num_hidden=num_classes, name="fc")
    return sym.SoftmaxOutput(fc, name="softmax")


def synthetic_corpus(rng, n, seq_len, vocab):
    X = rng.randint(10, vocab, (n, seq_len)).astype("float32")
    y = rng.randint(0, 2, n).astype("float32")
    for i in range(n):
        if y[i] == 1:
            pos = rng.randint(0, seq_len - 3)
            X[i, pos:pos + 3] = [7, 8, 9]
    return X, y


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-epoch", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=24)
    ap.add_argument("--vocab", type=int, default=64)
    args = ap.parse_args()

    import mxnet_tpu as mx

    # deterministic init: Module's host-side initializer draws from the
    # global numpy RNG
    np.random.seed(11)
    mx.random.seed(11)
    rng = np.random.RandomState(0)
    X, y = synthetic_corpus(rng, 1024, args.seq_len, args.vocab)
    Xv, yv = synthetic_corpus(rng, 256, args.seq_len, args.vocab)

    net = make_text_cnn(args.vocab, args.seq_len)
    train = mx.io.NDArrayIter(X, y, args.batch_size, shuffle=True)
    val = mx.io.NDArrayIter(Xv, yv, args.batch_size)
    mod = mx.Module(net, context=mx.cpu())
    mod.fit(train, eval_data=val, num_epoch=args.num_epoch,
            optimizer="adam", optimizer_params={"learning_rate": 5e-3},
            initializer=mx.initializer.Xavier(), eval_metric="acc")
    val.reset()
    acc = mod.score(val, "acc")[0][1]
    print("text-cnn val acc %.3f" % acc)
    assert acc > 0.95, acc
    print("text-cnn example OK")


if __name__ == "__main__":
    main()
