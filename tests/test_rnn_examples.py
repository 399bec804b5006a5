"""mx.rnn (BucketSentenceIter/encode_sentences), MakeLoss gradient
contract, and the rnn/ssd example CLIs."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_encode_sentences():
    sents = [["a", "b", "c"], ["b", "c"]]
    encoded, vocab = mx.rnn.encode_sentences(sents, invalid_label=0,
                                             invalid_key="<pad>",
                                             start_label=1)
    assert vocab["<pad>"] == 0
    assert encoded[0][1] == encoded[1][0]  # same token -> same id
    # existing vocab: unknown token raises
    with pytest.raises(ValueError):
        mx.rnn.encode_sentences([["zzz"]], vocab=vocab)


def test_bucket_sentence_iter():
    rng = np.random.RandomState(0)
    sents = [[int(x) for x in rng.randint(1, 20, rng.randint(3, 12))]
             for _ in range(100)]
    it = mx.rnn.BucketSentenceIter(sents, batch_size=8, buckets=[4, 8, 12],
                                   invalid_label=0)
    assert it.default_bucket_key == 12
    seen_keys = set()
    for batch in it:
        key = batch.bucket_key
        seen_keys.add(key)
        assert batch.data[0].shape == (8, key)
        assert batch.label[0].shape == (8, key)
        d = batch.data[0].asnumpy()
        l = batch.label[0].asnumpy()
        # label is data shifted left by one
        np.testing.assert_array_equal(l[:, :-1], d[:, 1:])
    assert len(seen_keys) >= 2
    it.reset()
    assert len(list(it)) > 0


def test_make_loss_gradient_contract():
    """MakeLoss backward seeds grad_scale, ignoring head grads
    (reference make_loss.cc)."""
    from mxnet_tpu import autograd
    x = nd.array(np.array([1.0, -2.0, 3.0], np.float32))
    x.attach_grad()
    with autograd.record():
        y = nd.make_loss(x * 2.0, grad_scale=0.5)
    y.backward(nd.array(np.array([100.0, 100.0, 100.0], np.float32)))
    # d/dx (2x) with seeded grad 0.5 (head grad ignored) = 1.0
    np.testing.assert_allclose(x.grad.asnumpy(), [1.0, 1.0, 1.0])


def _run_example(rel, *args, timeout=480):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.basename(rel)] + list(args),
        cwd=os.path.join(ROOT, os.path.dirname(rel)),
        env=env, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout + proc.stderr


def test_lstm_bucketing_example():
    # default path = the symbolic cell zoo (SequentialRNNCell of LSTMCells
    # unrolled per bucket), matching the reference example's construction
    out = _run_example("example/rnn/lstm_bucketing.py",
                       "--num-epochs", "2", "--batch-size", "16")
    assert "Train-perplexity" in out


def test_lstm_bucketing_example_fused():
    out = _run_example("example/rnn/lstm_bucketing.py",
                       "--num-epochs", "2", "--batch-size", "16", "--fused")
    assert "Train-perplexity" in out


def test_ssd_example():
    out = _run_example("example/ssd/train_ssd.py", "--num-epochs", "6")
    assert "mean IoU" in out
    iou = float(out.split("mean IoU of top detection:")[1].split(";")[0])
    assert iou > 0.5, out
