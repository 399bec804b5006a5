"""Pretrained-weights story (VERDICT r3 item 6).

tools/convert_params.py maps a reference-gluon-named ``.params`` file
(flat 1.x name-manager names like ``resnetv10_conv0_weight``, in
declaration order) onto this framework's hierarchical parameter names
and writes it into the local model store; ``pretrained=True, root=...``
then loads it. Reference: gluon/model_zoo/model_store.py:1 +
save_params naming.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
LOGITS_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                              "resnet18_v1_pretrained_logits.npy")


def _make_reference_style_file(path, classes=4):
    """Emit a ref-flavored flat-named params file for resnet18_v1:
    deterministic values, reference alias spellings (conv<N> not
    conv2d<N>), declaration order — the shape a 1.2 model-zoo
    checkpoint has."""
    net = gluon.model_zoo.vision.resnet18_v1(classes=classes)
    net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=2))
    net(mx.nd.zeros((1, 3, 32, 32)))
    flat = {}
    for name, p in net.collect_params().items():
        ref_name = name.replace("conv2d", "conv")
        flat[ref_name] = p.data()
    from mxnet_tpu.serialization import save_ndarray_file
    save_ndarray_file(path, flat)
    return net


def test_convert_and_load_pretrained(tmp_path):
    ref_file = str(tmp_path / "resnet18_v1-ref.params")
    store = str(tmp_path / "models")
    src_net = _make_reference_style_file(ref_file)

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "convert_params.py"),
         "--model", "resnet18_v1", "--in", ref_file, "--root", store,
         "--classes", "4"],
        capture_output=True, text=True, timeout=400,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0
    assert os.path.exists(os.path.join(store, "resnet18_v1.params"))

    net = gluon.model_zoo.vision.resnet18_v1(pretrained=True, root=store,
                                             classes=4)
    x = mx.nd.array(np.random.RandomState(0)
                    .uniform(-1, 1, (2, 3, 32, 32)).astype(np.float32))
    got = net(x).asnumpy()
    want = src_net(x).asnumpy()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    # committed-logits regression pin: the deterministic fixture
    # (seeded init under conftest) must keep producing the same logits
    # through convert -> store -> pretrained load
    if os.path.exists(LOGITS_FIXTURE):
        np.testing.assert_allclose(got, np.load(LOGITS_FIXTURE),
                                   rtol=1e-4, atol=1e-5)
    else:                                    # first run: write it
        np.save(LOGITS_FIXTURE, got)


def test_pretrained_missing_store_is_actionable():
    with pytest.raises(mx.MXNetError, match="convert_params"):
        gluon.model_zoo.vision.resnet18_v1(pretrained=True,
                                           root="/nonexistent/store")


def test_converter_alias_and_shape_mapping_unit():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import convert_params as cp

    src = {"net0_conv0_weight": np.zeros((4, 3, 3, 3), np.float32),
           "net0_batchnorm0_gamma": np.ones((4,), np.float32),
           "net0_batchnorm0_running_mean": np.zeros((4,), np.float32),
           "net0_dense0_weight": np.zeros((2, 4), np.float32)}
    targets = ["net0_conv2d0_weight", "net0_batchnorm0_gamma",
               "net0_batchnorm0_running_mean", "net0_dense0_weight"]
    shapes = {"net0_conv2d0_weight": (4, 3, 3, 3),
              "net0_batchnorm0_gamma": (4,),
              "net0_batchnorm0_running_mean": (4,),
              "net0_dense0_weight": (2, 4)}
    out = cp.map_params(src, targets, shapes, logger=lambda *a: None)
    assert set(out) == set(targets)

    # leftover source params must be an error, not silence
    src2 = dict(src)
    src2["net0_extra_weight"] = np.zeros((9,), np.float32)
    with pytest.raises(SystemExit, match="unused"):
        cp.map_params(src2, targets, shapes, logger=lambda *a: None)
