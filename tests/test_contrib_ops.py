"""SSD contrib ops + CTC tests (reference
tests/python/unittest/test_operator.py multibox/ctc subsets) and the
example-script CLIs."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_multibox_prior_layout():
    feat = nd.zeros((1, 8, 4, 4))
    anchors = nd.MultiBoxPrior(feat, sizes=(0.4, 0.2), ratios=(1, 2, 0.5))
    # num_anchors = sizes + ratios - 1 = 4
    assert anchors.shape == (1, 4 * 4 * 4, 4)
    a = anchors.asnumpy()[0]
    # cell (0,0) first anchor: center (.125,.125), half extent .2
    np.testing.assert_allclose(a[0], [-0.075, -0.075, 0.325, 0.325],
                               atol=1e-6)
    clipped = nd.MultiBoxPrior(feat, sizes=(0.4,), clip=True).asnumpy()
    assert clipped.min() >= 0 and clipped.max() <= 1


def test_multibox_target_matching_and_encoding():
    feat = nd.zeros((1, 8, 2, 2))
    anchors = nd.MultiBoxPrior(feat, sizes=(0.5,), ratios=(1,))
    # gt perfectly equals anchor 0 -> zero offsets, positive mask, class+1
    label = nd.array(np.array([[[3.0, 0.0, 0.0, 0.5, 0.5],
                                [-1.0, 0, 0, 0, 0]]], np.float32))
    cls_pred = nd.zeros((1, 5, 4))
    loc_t, loc_m, cls_t = nd.MultiBoxTarget(anchors, label, cls_pred)
    assert cls_t.shape == (1, 4) and loc_t.shape == (1, 16)
    ct = cls_t.asnumpy()[0]
    assert ct[0] == 4.0  # class 3 + 1
    assert (ct[1:] == 0).all()
    np.testing.assert_allclose(loc_t.asnumpy()[0][:4], 0.0, atol=1e-5)
    np.testing.assert_array_equal(loc_m.asnumpy()[0][:4], 1.0)
    assert loc_m.asnumpy()[0][4:].sum() == 0


def test_multibox_target_best_anchor_fallback():
    """A gt below the IoU threshold still claims its best anchor
    (reference two-stage matching)."""
    feat = nd.zeros((1, 8, 2, 2))
    anchors = nd.MultiBoxPrior(feat, sizes=(0.5,), ratios=(1,))
    # small box overlapping anchor 0 with IoU < 0.5
    label = nd.array(np.array([[[0.0, 0.0, 0.0, 0.2, 0.2]]], np.float32))
    _, _, cls_t = nd.MultiBoxTarget(anchors, label,
                                    nd.zeros((1, 2, 4)))
    assert cls_t.asnumpy()[0][0] == 1.0


def test_multibox_detection_decode_and_nms():
    feat = nd.zeros((1, 8, 2, 2))
    # two sizes -> 2 anchors per cell, heavily overlapping (IoU 0.64)
    anchors = nd.MultiBoxPrior(feat, sizes=(0.5, 0.4), ratios=(1,))
    probs = np.zeros((1, 3, 8), np.float32)
    probs[0, 1, 0] = 0.9   # class 0, cell-0 anchor 0
    probs[0, 1, 1] = 0.7   # same class, same cell anchor 1 -> suppressed
    probs[0, 2, 5] = 0.8   # class 1 elsewhere
    det = nd.MultiBoxDetection(nd.array(probs), nd.zeros((1, 32)), anchors,
                               nms_threshold=0.3)
    d = det.asnumpy()[0]
    kept = d[d[:, 0] >= 0]
    scores = sorted(kept[:, 1].tolist())
    # anchor-1 detection suppressed by anchor 0 (IoU > 0.3, same class)
    assert scores == pytest.approx([0.8, 0.9])
    # zero loc_pred decodes to the anchors themselves
    a = anchors.asnumpy()[0]
    best = kept[kept[:, 1] > 0.85][0]
    np.testing.assert_allclose(best[2:], np.clip(a[0], 0, 1), atol=1e-5)


def test_box_nms():
    data = nd.array(np.array([[[0, 0.9, 0.1, 0.1, 0.5, 0.5],
                               [0, 0.8, 0.12, 0.12, 0.5, 0.5],
                               [1, 0.7, 0.1, 0.1, 0.5, 0.5],
                               [0, 0.6, 0.6, 0.6, 0.9, 0.9]]], np.float32))
    out = nd.box_nms(data, overlap_thresh=0.5, coord_start=2,
                     score_index=1, id_index=0)
    o = out.asnumpy()[0]
    # second box suppressed (same class, high IoU); class-1 box kept
    kept_scores = sorted(o[o[:, 1] > 0][:, 1].tolist())
    assert kept_scores == pytest.approx([0.6, 0.7, 0.9])
    forced = nd.box_nms(data, overlap_thresh=0.5, coord_start=2,
                        score_index=1, id_index=0, force_suppress=True)
    f = forced.asnumpy()[0]
    assert sorted(f[f[:, 1] > 0][:, 1].tolist()) == pytest.approx([0.6, 0.9])


def test_ctc_loss_analytic():
    # uniform logits, T=2, blank=0, label [1]:
    # paths: (b,1),(1,b),(1,1) -> p = 3*(1/3)^2
    data = nd.zeros((2, 1, 3))
    label = nd.array(np.array([[1.0, 0.0]], np.float32))
    loss = nd.ctc_loss(data, label)
    np.testing.assert_allclose(loss.asnumpy()[0], -np.log(3.0 / 9.0),
                               rtol=1e-5)


def test_ctc_loss_peaky_predictions():
    """Confident correct predictions → near-zero loss; wrong → large."""
    T, B, C = 6, 2, 4
    logits = np.full((T, B, C), -10.0, np.float32)
    # example 0: emit label 2 at t=0, blanks elsewhere (correct)
    logits[0, 0, 2] = 10.0
    for t in range(1, T):
        logits[t, 0, 0] = 10.0
    # example 1: all blanks, but label says 1 (wrong)
    for t in range(T):
        logits[t, 1, 0] = 10.0
    label = nd.array(np.array([[2.0, 0.0], [1.0, 0.0]], np.float32))
    loss = nd.ctc_loss(nd.array(logits), label).asnumpy()
    assert loss[0] < 0.1
    assert loss[1] > 5.0


def test_ctc_loss_gradient_flows():
    from mxnet_tpu import autograd
    data = nd.array(np.random.RandomState(0).randn(4, 2, 5)
                    .astype(np.float32))
    label = nd.array(np.array([[1.0, 2.0], [3.0, 0.0]], np.float32))
    data.attach_grad()
    with autograd.record():
        loss = nd.ctc_loss(data, label)
    loss.backward(nd.ones((2,)))
    g = data.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_ssd_ops_inside_symbol_graph():
    """The trio composes in a symbol graph (the SSD training head)."""
    data = sym.Variable("data")
    label = sym.Variable("label")
    anchors = sym.MultiBoxPrior(data, sizes=(0.5, 0.3), ratios=(1, 2))
    cls_pred = sym.Variable("cls_pred")
    loc_t = sym.MultiBoxTarget(anchors, label, cls_pred, name="target")
    grp = sym.Group(list(loc_t))
    exe = grp.simple_bind(ctx=mx.cpu(), data=(1, 8, 2, 2),
                          label=(1, 2, 5), cls_pred=(1, 3, 12))
    exe.arg_dict["label"][:] = np.array(
        [[[1.0, 0.0, 0.0, 0.5, 0.5], [-1, 0, 0, 0, 0]]], np.float32)
    outs = exe.forward()
    assert outs[0].shape == (1, 48)
    assert outs[2].shape == (1, 12)


def test_train_mnist_cli():
    """The reference's train_mnist.py CLI runs end to end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "train_mnist.py", "--num-epochs", "2",
         "--batch-size", "64"],
        cwd=os.path.join(ROOT, "example", "image-classification"),
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "Validation-accuracy" in proc.stderr or \
           "Validation-accuracy" in proc.stdout
