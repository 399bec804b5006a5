"""RecordIO + mx.image + ImageRecordIter + im2rec tests.

Mirrors tests/python/unittest/test_recordio.py and test_image.py; the
end-to-end case feeds an ImageRecordIter into Module.fit (the reference's
ImageNet flow, iter_image_recordio_2.cc).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio, sym
from PIL import Image


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "t.rec")
    rec = recordio.MXRecordIO(path, "w")
    for i in range(5):
        rec.write("record_%d" % i)
    rec.close()
    rec = recordio.MXRecordIO(path, "r")
    for i in range(5):
        assert rec.read() == b"record_%d" % i
    assert rec.read() is None
    rec.reset()
    assert rec.read() == b"record_0"
    rec.close()


def test_recordio_multipart_alignment(tmp_path):
    # records of every length mod 4, checking padding logic
    path = str(tmp_path / "pad.rec")
    rec = recordio.MXRecordIO(path, "w")
    bufs = [b"x" * n for n in (1, 2, 3, 4, 5, 1023)]
    for b in bufs:
        rec.write(b)
    rec.close()
    rec = recordio.MXRecordIO(path, "r")
    for b in bufs:
        assert rec.read() == b
    rec.close()


def test_indexed_recordio(tmp_path):
    idx = str(tmp_path / "t.idx")
    path = str(tmp_path / "t.rec")
    rec = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(10):
        rec.write_idx(i, "rec_%d" % i)
    rec.close()
    rec = recordio.MXIndexedRecordIO(idx, path, "r")
    assert rec.keys == list(range(10))
    assert rec.read_idx(7) == b"rec_7"
    assert rec.read_idx(2) == b"rec_2"
    rec.close()


def test_pack_unpack_label():
    hdr = recordio.IRHeader(0, 3.0, 42, 0)
    s = recordio.pack(hdr, b"payload")
    hdr2, data = recordio.unpack(s)
    assert hdr2.label == 3.0 and hdr2.id == 42 and data == b"payload"
    # array label
    hdr = recordio.IRHeader(0, np.array([1.0, 2.0, 3.0], np.float32), 7, 0)
    s = recordio.pack(hdr, b"img")
    hdr2, data = recordio.unpack(s)
    np.testing.assert_array_equal(hdr2.label, [1.0, 2.0, 3.0])
    assert data == b"img"


def _rand_img(rng, h=40, w=48):
    return rng.randint(0, 255, (h, w, 3)).astype(np.uint8)


def test_pack_img_unpack_img():
    rng = np.random.RandomState(0)
    img = _rand_img(rng)
    s = recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img,
                          quality=100, img_fmt=".png")
    hdr, img2 = recordio.unpack_img(s, iscolor=1)
    assert hdr.label == 1.0
    np.testing.assert_array_equal(img, img2)  # png is lossless


def test_image_basics(tmp_path):
    rng = np.random.RandomState(1)
    img = _rand_img(rng, 64, 80)
    p = str(tmp_path / "a.png")
    Image.fromarray(img).save(p)
    loaded = mx.image.imread(p)
    np.testing.assert_array_equal(loaded.asnumpy(), img)

    r = mx.image.imresize(loaded, 20, 10)
    assert r.shape == (10, 20, 3)
    rs = mx.image.resize_short(loaded, 32)
    assert min(rs.shape[:2]) == 32
    c, rect = mx.image.center_crop(loaded, (30, 20))
    assert c.shape == (20, 30, 3)
    rc, rect = mx.image.random_crop(loaded, (30, 20))
    assert rc.shape == (20, 30, 3)
    rsc, rect = mx.image.random_size_crop(loaded, (30, 20), (0.5, 1.0),
                                          (0.75, 1.33))
    assert rsc.shape == (20, 30, 3)
    n = mx.image.color_normalize(loaded, np.array([127.0, 127.0, 127.0]),
                                 np.array([64.0, 64.0, 64.0]))
    assert abs(float(n.asnumpy().mean())) < 1.5


def test_create_augmenter_pipeline():
    augs = mx.image.CreateAugmenter((3, 24, 24), resize=30, rand_crop=True,
                                    rand_mirror=True, mean=True, std=True,
                                    brightness=0.1, contrast=0.1,
                                    saturation=0.1, hue=0.1, pca_noise=0.05,
                                    rand_gray=0.5)
    rng = np.random.RandomState(2)
    img = mx.nd.array(_rand_img(rng, 50, 60))
    out = img
    for aug in augs:
        out = aug(out)
    assert out.shape == (24, 24, 3)
    assert out.dtype == np.float32
    for aug in augs:
        assert isinstance(aug.dumps(), str)


def _make_rec(tmp_path, n=32, size=36, label_width=1):
    """Write a tiny .rec/.idx of colored squares; label = dominant color."""
    rng = np.random.RandomState(3)
    idxp = str(tmp_path / "d.idx")
    recp = str(tmp_path / "d.rec")
    rec = recordio.MXIndexedRecordIO(idxp, recp, "w")
    for i in range(n):
        label = i % 3
        img = rng.randint(0, 60, (size, size, 3)).astype(np.uint8)
        img[:, :, label] = 220
        if label_width > 1:
            hdr = recordio.IRHeader(
                0, np.arange(label, label + label_width, dtype=np.float32),
                i, 0)
        else:
            hdr = recordio.IRHeader(0, float(label), i, 0)
        rec.write_idx(i, recordio.pack_img(hdr, img, img_fmt=".png"))
    rec.close()
    return recp, idxp


def test_image_record_iter(tmp_path):
    recp, idxp = _make_rec(tmp_path, n=32)
    it = mx.io.ImageRecordIter(
        path_imgrec=recp, path_imgidx=idxp, data_shape=(3, 28, 28),
        batch_size=8, shuffle=True, seed=7, rand_crop=True, rand_mirror=True,
        mean_r=123, mean_g=117, mean_b=104, std_r=58, std_g=57, std_b=57,
        preprocess_threads=2, prefetch_buffer=2)
    seen = 0
    labels = []
    for batch in it:
        assert batch.data[0].shape == (8, 3, 28, 28)
        assert batch.label[0].shape == (8,)
        labels.extend(batch.label[0].asnumpy().tolist())
        seen += 8 - (batch.pad or 0)
    assert seen == 32
    assert sorted(set(labels)) == [0.0, 1.0, 2.0]
    # second epoch works after reset
    it.reset()
    assert next(it).data[0].shape == (8, 3, 28, 28)
    it.close()


def test_image_record_iter_round_batch(tmp_path):
    recp, idxp = _make_rec(tmp_path, n=10)
    it = mx.io.ImageRecordIter(path_imgrec=recp, path_imgidx=idxp,
                               data_shape=(3, 28, 28), batch_size=4,
                               preprocess_threads=1)
    batches = list(it)
    assert len(batches) == 3
    assert batches[-1].pad == 2  # 10 = 4+4+2(+2 wrapped)
    it.close()


def test_image_record_iter_multilabel_and_parts(tmp_path):
    recp, idxp = _make_rec(tmp_path, n=24, label_width=3)
    it = mx.io.ImageRecordIter(path_imgrec=recp, path_imgidx=idxp,
                               label_width=3, data_shape=(3, 36, 36),
                               batch_size=6, num_parts=2, part_index=1,
                               preprocess_threads=1)
    n = sum(b.data[0].shape[0] - (b.pad or 0) for b in it)
    assert n == 12
    it.close()


def test_image_iter_imglist(tmp_path):
    rng = np.random.RandomState(5)
    files = []
    for i in range(8):
        p = "img%d.png" % i
        Image.fromarray(_rand_img(rng, 40, 40)).save(str(tmp_path / p))
        files.append((float(i % 2), p))
    it = mx.image.ImageIter(batch_size=4, data_shape=(3, 32, 32),
                            imglist=files, path_root=str(tmp_path))
    b = it.next()
    assert b.data[0].shape == (4, 3, 32, 32)
    assert b.label[0].shape == (4, 1)


def test_im2rec_cli(tmp_path):
    rng = np.random.RandomState(6)
    for cls in ("cat", "dog"):
        os.makedirs(str(tmp_path / "imgs" / cls))
        for i in range(4):
            Image.fromarray(_rand_img(rng, 50, 50)).save(
                str(tmp_path / "imgs" / cls / ("%d.jpg" % i)))
    root = str(tmp_path / "imgs")
    prefix = str(tmp_path / "data")
    tool = os.path.join(os.path.dirname(__file__), "..", "tools", "im2rec.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, tool, prefix, root, "--list",
                    "--recursive"], check=True, env=env)
    assert os.path.exists(prefix + ".lst")
    subprocess.run([sys.executable, tool, prefix, root, "--resize", "32",
                    "--num-thread", "2"], check=True, env=env)
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")
    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               path_imgidx=prefix + ".idx",
                               data_shape=(3, 32, 32), batch_size=4,
                               preprocess_threads=1)
    labels = []
    for b in it:
        labels.extend(b.label[0].asnumpy().tolist())
    assert set(labels) == {0.0, 1.0}
    it.close()


def test_record_iter_feeds_module_fit(tmp_path):
    """End-to-end: .rec file → ImageRecordIter → Module.fit converges on
    a trivially separable task (dominant-color classification)."""
    recp, idxp = _make_rec(tmp_path, n=48, size=16)
    it = mx.io.ImageRecordIter(path_imgrec=recp, path_imgidx=idxp,
                               data_shape=(3, 16, 16), batch_size=16,
                               shuffle=True, seed=1, scale=1.0 / 255,
                               preprocess_threads=2)
    data = sym.Variable("data")
    net = sym.Pooling(data, kernel=(16, 16), pool_type="avg", name="gap")
    net = sym.FullyConnected(sym.Flatten(net), num_hidden=3, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=30, optimizer="adam",
            optimizer_params={"learning_rate": 0.05},
            initializer=mx.initializer.Xavier())
    it.reset()
    assert mod.score(it, "acc")[0][1] > 0.9
    it.close()


def test_gluon_image_record_dataset(tmp_path):
    """The gluon RecordFileDataset/ImageRecordDataset path (previously a
    dangling import) now works over the real recordio module."""
    recp, idxp = _make_rec(tmp_path, n=8)
    ds = mx.gluon.data.vision.ImageRecordDataset(recp)
    img, label = ds[3]
    assert img.shape == (36, 36, 3)
    assert label == 0.0
    loader = mx.gluon.data.DataLoader(ds, batch_size=4)
    batches = list(loader)
    assert len(batches) == 2


def test_image_det_iter(tmp_path):
    """ImageDetIter: reference det label wire format, padded object
    labels, box-aware flip (reference image/detection.py)."""
    rng = np.random.RandomState(11)
    idxp, recp = str(tmp_path / "det.idx"), str(tmp_path / "det.rec")
    rec = recordio.MXIndexedRecordIO(idxp, recp, "w")
    for i in range(8):
        img = rng.randint(0, 255, (32, 32, 3)).astype(np.uint8)
        nobj = 1 + i % 3
        objs = []
        for j in range(nobj):
            objs += [float(j % 2), 0.1, 0.2, 0.5, 0.6]
        # reference wire format: [header_width, object_width, <header>, objs]
        label = np.array([2.0, 5.0] + objs, np.float32)
        rec.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, label, i, 0), img, img_fmt=".png"))
    rec.close()
    it = mx.image.ImageDetIter(batch_size=4, data_shape=(3, 28, 28),
                               path_imgrec=recp, path_imgidx=idxp)
    assert it.provide_label[0].shape == (4, 3, 5)  # max 3 objects
    n = 0
    for batch in it:
        assert batch.data[0].shape == (4, 3, 28, 28)
        lab = batch.label[0].asnumpy()
        assert lab.shape == (4, 3, 5)
        valid = lab[lab[:, :, 0] >= 0]
        assert (valid[:, 1:] >= 0).all() and (valid[:, 1:] <= 1).all()
        n += 4 - (batch.pad or 0)
    assert n == 8

    # flip aug mirrors boxes
    aug = mx.image.DetHorizontalFlipAug(p=1.0)
    img = np.zeros((10, 10, 3), np.float32)
    lab = np.array([[0, 0.1, 0.2, 0.5, 0.6]], np.float32)
    _, flipped = aug(img, lab)
    np.testing.assert_allclose(flipped[0], [0, 0.5, 0.2, 0.9, 0.6],
                               rtol=1e-6)

    # crop clips + renormalizes boxes into [0, 1]
    crop = mx.image.DetRandomCropAug(min_crop_scale=0.5)
    img2 = np.zeros((20, 20, 3), np.float32)
    lab2 = np.array([[1, 0.25, 0.25, 0.75, 0.75]], np.float32)
    out_img, out_lab = crop(img2, lab2)
    if len(out_lab):
        assert (out_lab[:, 1:] >= -1e-6).all() \
            and (out_lab[:, 1:] <= 1 + 1e-6).all()


def test_record_iter_batches_on_cpu_context(tmp_path):
    # reference iterator contract: batches live on the HOST (cpu
    # context); the executor moves them to the bind device exactly once.
    # On an accelerator platform, yielding device arrays would force a
    # device round trip on any consumer that reads them.
    rec_path, idx_path = _make_rec(tmp_path, n=8, size=12)
    it = mx.io.ImageRecordIter(path_imgrec=str(rec_path),
                               path_imgidx=str(idx_path),
                               data_shape=(3, 12, 12), batch_size=4)
    batch = next(it)
    assert batch.data[0].context.device_type == "cpu"
    assert batch.label[0].context.device_type == "cpu"
    # and cpu-context arrays actually live on a cpu jax device
    assert all(d.platform == "cpu" for d in batch.data[0]._data.devices())


def test_cpu_context_maps_to_cpu_backend():
    import jax
    dev = mx.cpu().jax_device
    assert dev.platform == "cpu"
    a = mx.nd.array(np.ones((4,), np.float32), ctx=mx.cpu())
    assert all(d.platform == "cpu" for d in a._data.devices())
