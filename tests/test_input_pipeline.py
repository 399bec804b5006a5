"""Input-pipeline proofs (VERDICT r3 item 4).

(a) decode thread-scaling: runs only on multi-core hosts (skips here);
(b) prefetch overlap: batch N+1 is being produced while "step" N runs;
(c) process-based DataLoader workers with shared-memory transport.

Reference: src/io/iter_image_recordio_2.cc:50-762 (OMP-parallel decode),
iter_prefetcher.h (background prefetch), gluon/data/dataloader.py:26-96
(worker processes + shared-memory NDArray passing).
"""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon


# ----------------------------------------------------------------------
# (c) process-based DataLoader workers
# ----------------------------------------------------------------------
class _SquareDataset(gluon.data.Dataset):
    """Deterministic dataset; records which PID computed each item."""

    def __init__(self, n=64, d=6):
        self._n, self._d = n, d

    def __len__(self):
        return self._n

    def __getitem__(self, idx):
        x = np.full((self._d,), float(idx), np.float32)
        return x * x, np.float32(idx % 4)


def test_dataloader_process_workers_match_serial():
    ds = _SquareDataset()
    serial = [(d.asnumpy(), l.asnumpy()) for d, l in
              gluon.data.DataLoader(ds, batch_size=8, num_workers=0)]
    multi = [(d.asnumpy(), l.asnumpy()) for d, l in
             gluon.data.DataLoader(ds, batch_size=8, num_workers=3)]
    assert len(serial) == len(multi) == 8
    for (d0, l0), (d1, l1) in zip(serial, multi):
        np.testing.assert_array_equal(d0, d1)   # strict sampler order
        np.testing.assert_array_equal(l0, l1)


def test_dataloader_workers_are_processes():
    """num_workers>0 (default mode) must fork real processes — the
    reference's GIL-free worker model — not threads."""
    pids = set()

    class PidDataset(gluon.data.Dataset):
        def __len__(self):
            return 32

        def __getitem__(self, idx):
            return np.full((2,), float(os.getpid()), np.float64), 0

    for d, _l in gluon.data.DataLoader(PidDataset(), batch_size=4,
                                       num_workers=2):
        pids.update(int(p) for p in np.unique(d.asnumpy()))
    assert os.getpid() not in pids, "batches were built in the parent"
    assert len(pids) >= 1


def test_dataloader_thread_pool_mode_still_works():
    ds = _SquareDataset(32)
    out = list(gluon.data.DataLoader(ds, batch_size=8, num_workers=2,
                                     thread_pool=True))
    assert len(out) == 4


def test_dataloader_worker_error_propagates():
    class Bad(gluon.data.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, idx):
            if idx == 5:
                raise ValueError("boom at 5")
            return np.zeros(3, np.float32), 0

    with pytest.raises(RuntimeError, match="boom at 5"):
        list(gluon.data.DataLoader(Bad(), batch_size=4, num_workers=2))


def test_dataloader_custom_batchify_through_workers():
    ds = _SquareDataset(16, d=3)

    def bfn(samples):
        xs = np.stack([s[0] for s in samples])
        return xs.sum(axis=0)

    out = list(gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                                     batchify_fn=bfn))
    ref = list(gluon.data.DataLoader(ds, batch_size=4, num_workers=0,
                                     batchify_fn=bfn))
    for a, b in zip(out, ref):
        # a custom batchify returning numpy must stay numpy in BOTH modes
        assert isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
        np.testing.assert_allclose(a, b)


# ----------------------------------------------------------------------
# (b) prefetch overlap
# ----------------------------------------------------------------------
class _TimedIter(mx.io.DataIter):
    """Iterator that records the wall-clock window of every next()."""

    def __init__(self, n_batches=6, delay=0.15, batch_size=4):
        super().__init__(batch_size)
        self.windows = []
        self._n = n_batches
        self._i = 0
        self._delay = delay

    @property
    def provide_data(self):
        return [mx.io.DataDesc("data", (self.batch_size, 2), np.float32)]

    @property
    def provide_label(self):
        return [mx.io.DataDesc("softmax_label", (self.batch_size,),
                               np.float32)]

    def reset(self):
        self._i = 0

    def next(self):
        if self._i >= self._n:
            raise StopIteration
        t0 = time.perf_counter()
        time.sleep(self._delay)            # simulated decode work
        t1 = time.perf_counter()
        self.windows.append((self._i, t0, t1))
        self._i += 1
        return mx.io.DataBatch(
            [mx.nd.full((self.batch_size, 2), float(self._i))],
            [mx.nd.zeros((self.batch_size,))])


def test_prefetching_iter_overlaps_decode_with_step():
    """While the consumer 'runs step N' the background thread must
    already be decoding batch N+1 (reference iter_prefetcher.h)."""
    base = _TimedIter(n_batches=6, delay=0.15)
    it = mx.io.PrefetchingIter(base)
    step_windows = []
    n = 0
    for _batch in it:
        t0 = time.perf_counter()
        time.sleep(0.15)                   # simulated device step
        step_windows.append((n, t0, time.perf_counter()))
        n += 1
    assert n == 6
    # for at least half the steps, the decode of batch i+1 must START
    # inside (or before) step i's window — i.e. strictly before step i
    # ends
    overlaps = 0
    for i, s0, s1 in step_windows[:-1]:
        nxt = [w for w in base.windows if w[0] == i + 1]
        if nxt and nxt[0][1] < s1:
            overlaps += 1
    assert overlaps >= len(step_windows[:-1]) // 2, \
        "prefetch did not overlap decode with compute: %d/%d" % (
            overlaps, len(step_windows) - 1)
    # and the whole run must take ~max(decode,step)*N, not the sum
    total = step_windows[-1][2] - base.windows[0][1]
    serial = 6 * 0.3
    assert total < serial * 0.85, \
        "pipeline ran serially: %.2fs vs serial %.2fs" % (total, serial)


def test_prefetching_iter_shards_across_devices():
    """With ``ctx`` a multi-device list, the prefetch worker shards each
    batch over a dp mesh of those devices at prefetch time (the fused
    fit step consumes the shards as-is), instead of splitting on the
    fit thread. Values must round-trip unchanged."""
    import jax
    devs = jax.devices()
    assert len(devs) == 8, "conftest should force 8 host devices"
    X = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
    y = np.arange(16, dtype=np.float32)
    ctxs = [mx.cpu(i) for i in range(8)]

    base = mx.io.NDArrayIter(X, y, batch_size=16)
    it = mx.io.PrefetchingIter(base, ctx=ctxs)
    batch = next(iter(it))
    assert set(batch.data[0]._data.devices()) == set(devs)
    assert set(batch.label[0]._data.devices()) == set(devs)
    np.testing.assert_array_equal(batch.data[0].asnumpy(), X)
    np.testing.assert_array_equal(batch.label[0].asnumpy(), y)

    # a batch not divisible by the device count falls back to device 0
    base2 = mx.io.NDArrayIter(X[:6], y[:6], batch_size=6)
    it2 = mx.io.PrefetchingIter(base2, ctx=ctxs)
    b2 = next(iter(it2))
    assert len(b2.data[0]._data.devices()) == 1
    np.testing.assert_array_equal(b2.data[0].asnumpy(), X[:6])

    # single-context behavior is unchanged
    base3 = mx.io.NDArrayIter(X, y, batch_size=16)
    it3 = mx.io.PrefetchingIter(base3, ctx=mx.cpu(0))
    b3 = next(iter(it3))
    assert len(b3.data[0]._data.devices()) == 1


# ----------------------------------------------------------------------
# (a) decode work is spread over the pool's threads
# ----------------------------------------------------------------------
def test_native_decode_thread_scaling(tmp_path):
    """ImageRecordIter's decode pool really runs `preprocess_threads`
    records at once, each on a thread of its own (reference
    iter_image_recordio_2.cc OMP decode), and the batches do not depend
    on the thread count. The witness is a barrier inside the decode call,
    not a wall-clock ratio: it holds whatever else loads the machine."""
    import io as pyio
    from PIL import Image
    from mxnet_tpu import recordio

    rec_path = str(tmp_path / "s.rec")
    idx_path = str(tmp_path / "s.idx")
    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    rng = np.random.RandomState(0)
    for i in range(64):
        img = rng.randint(0, 255, (224, 224, 3), dtype=np.uint8)
        buf = pyio.BytesIO()
        Image.fromarray(img).save(buf, "JPEG", quality=90)
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 10), i, 0), buf.getvalue()))
    rec.close()

    def epoch(nthreads):
        it = mx.io.ImageRecordIter(
            path_imgrec=rec_path, path_imgidx=idx_path,
            data_shape=(3, 224, 224), batch_size=32,
            preprocess_threads=nthreads)
        inner, idents, lock = it._process, set(), threading.Lock()
        meet = threading.Barrier(nthreads)
        early = [0]

        def process(offset):
            # the first `nthreads` calls wait for each other: they only
            # get past if that many pool threads hold a record at once
            with lock:
                idents.add(threading.get_ident())
                early[0] += 1
                wait = early[0] <= nthreads
            if wait:
                meet.wait(timeout=120)
            return inner(offset)

        it._process = process
        it.reset()              # the constructor's prefetch ran unwatched
        out = [(b.data[0].asnumpy(), b.label[0].asnumpy()) for b in it]
        return out, idents

    one, ids1 = epoch(1)
    many, ids4 = epoch(4)
    assert len(ids1) == 1 and len(ids4) == 4, (ids1, ids4)
    assert len(one) == len(many) == 2
    for (d1, l1), (dn, ln) in zip(one, many):
        np.testing.assert_array_equal(d1, dn)
        np.testing.assert_array_equal(l1, ln)
