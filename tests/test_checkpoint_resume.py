"""Checkpoint/resume fidelity on a non-MNIST workload (VERDICT r3
item 8).

Train the CIFAR-shaped ResNet on the deterministic synthetic dataset
(example/image-classification/train_synthetic_cifar.py), kill at epoch
K, resume from the checkpoint (params + optimizer states), and assert
the CONTINUED per-batch loss curve is BIT-IDENTICAL to the
uninterrupted run. Reference: model.py:384-414 save/load_checkpoint +
module.py save_checkpoint/load with optimizer states.
"""
import os
import sys

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import models

sys.path.insert(0, os.path.join(
    os.path.dirname(__file__), "..", "example", "image-classification"))

from train_synthetic_cifar import synthetic_cifar  # noqa: E402


def _iter(X, y, batch):
    return mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False)


class _LossRecorder:
    """Batch-end callback recording the exact training metric value."""

    def __init__(self):
        self.values = []

    def __call__(self, param):
        if param.eval_metric is not None:
            self.values.append(param.eval_metric.get()[1])


def _fit(mod, train, epochs, begin=0, prefix=None, ckpt_epoch=None):
    rec = _LossRecorder()
    cbs = []
    if prefix is not None:
        def ckpt(iter_no, sym=None, arg=None, aux=None):
            if iter_no + 1 == ckpt_epoch:
                mod.save_checkpoint(prefix, iter_no + 1,
                                    save_optimizer_states=True)
        cbs.append(ckpt)
    mod.fit(train, num_epoch=epochs, begin_epoch=begin,
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "wd": 1e-4},
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2),
            eval_metric="ce",
            epoch_end_callback=cbs,
            batch_end_callback=rec)
    return rec.values


def test_resume_is_bit_identical(tmp_path):
    (X, y), _ = synthetic_cifar(n_train=256, n_val=64)
    batch, total_epochs, kill_at = 64, 4, 2
    sym = models.get_symbol("resnet", num_classes=10, num_layers=8,
                            image_shape=(3, 28, 28))
    prefix = str(tmp_path / "ck")

    # uninterrupted run, checkpointing at the kill epoch along the way
    mx.random.seed(0)
    np.random.seed(0)
    mod_a = mx.Module(sym, context=mx.cpu())
    full = _fit(mod_a, _iter(X, y, batch), total_epochs,
                prefix=prefix, ckpt_epoch=kill_at)

    # the "killed" job: a FRESH module resumed from the checkpoint
    assert os.path.exists("%s-%04d.params" % (prefix, kill_at))
    assert os.path.exists("%s-%04d.states" % (prefix, kill_at))
    mx.random.seed(0)
    np.random.seed(0)
    mod_b = mx.Module.load(prefix, kill_at, context=mx.cpu(),
                           load_optimizer_states=True)
    resumed = _fit(mod_b, _iter(X, y, batch), total_epochs, begin=kill_at)

    steps_per_epoch = len(full) // total_epochs
    tail_full = full[kill_at * steps_per_epoch:]
    assert len(resumed) == len(tail_full)
    # bit-identical: the resumed curve equals the uninterrupted tail
    # EXACTLY (same params, same optimizer state incl. momentum, same
    # deterministic batches -> same XLA programs -> same floats)
    for i, (a, b) in enumerate(zip(tail_full, resumed)):
        assert a == b, "step %d diverged after resume: %r vs %r" % (i, a, b)

    # and the final parameters agree bit-for-bit too
    arg_a, aux_a = mod_a.get_params()
    arg_b, aux_b = mod_b.get_params()
    for k in arg_a:
        assert np.array_equal(arg_a[k].asnumpy(), arg_b[k].asnumpy()), k
    for k in aux_a:
        assert np.array_equal(aux_a[k].asnumpy(), aux_b[k].asnumpy()), k


def test_resume_cli_entrypoint(tmp_path):
    """The example CLI's --resume flag drives the same flow."""
    import subprocess
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    script = os.path.join(root, "example", "image-classification",
                          "train_synthetic_cifar.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    prefix = str(tmp_path / "cli")
    p1 = subprocess.run(
        [sys.executable, script, "--num-layers", "8", "--epochs", "2",
         "--prefix", prefix], env=env, capture_output=True, text=True,
        timeout=500)
    assert p1.returncode == 0, p1.stderr[-2000:]
    p2 = subprocess.run(
        [sys.executable, script, "--num-layers", "8", "--epochs", "3",
         "--resume", "2", "--prefix", prefix], env=env,
        capture_output=True, text=True, timeout=500)
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "epoch 3: val_acc=" in p2.stdout
