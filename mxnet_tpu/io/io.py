"""Data iterators.

Reference parity: python/mxnet/io.py (DataIter protocol :182, NDArrayIter
:546, PrefetchingIter :349, MXDataIter :766) and src/io/ C++ iterators
(MNISTIter, CSVIter, ImageRecordIter). All iterators yield ``DataBatch``
with ``data``/``label`` NDArray lists and ``pad`` for final-batch handling.
"""
from __future__ import annotations

import os
import queue
import struct
import threading

import numpy as _np

from ..base import MXNetError
from ..ndarray import NDArray, array as nd_array
from ..ndarray.ndarray import concatenate

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "ResizeIter",
           "PrefetchingIter", "MXDataIter", "CSVIter", "LibSVMIter",
           "MNISTIter", "ImageRecordIter"]


class DataDesc:
    """Name+shape(+dtype+layout) of one input (reference io.py DataDesc)."""

    def __init__(self, name, shape, dtype=_np.float32, layout="NCHW"):
        self.name = name
        self.shape = tuple(shape)
        self.dtype = dtype
        self.layout = layout

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape, self.dtype,
                                          self.layout)

    def __iter__(self):  # tuple-compat: name, shape
        yield self.name
        yield self.shape

    def __getitem__(self, i):
        return (self.name, self.shape)[i]

    def __len__(self):
        return 2

    def __eq__(self, other):
        if isinstance(other, DataDesc):
            return self.name == other.name and self.shape == other.shape
        if isinstance(other, tuple):
            return tuple(self) == other
        return NotImplemented

    def __hash__(self):
        return hash((self.name, self.shape))

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types=None):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict.get(x[0], _np.float32))
                    for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


class DataBatch:
    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __str__(self):
        return "DataBatch: data shapes %s label shapes %s" % (
            [d.shape for d in self.data] if self.data else None,
            [l.shape for l in self.label] if self.label else None)


class DataIter:
    """Iterator protocol (reference io.py:182)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError


def _init_data(data, allow_empty, default_name):
    if data is None:
        if not allow_empty:
            raise ValueError("data cannot be None")
        return []
    if isinstance(data, (_np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, (list, tuple)):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, list or dict")
    out = []
    for k, v in data.items():
        if not isinstance(v, NDArray):
            v = nd_array(_np.asarray(v))
        out.append((k, v))
    return out


class NDArrayIter(DataIter):
    """In-memory iterator (reference io.py:546): shuffle, pad/discard/
    roll_over last-batch handling."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)
        self.num_data = self.data[0][1].shape[0]
        self.idx = _np.arange(self.num_data)
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self.cursor = -batch_size
        self.num_pad = 0
        if last_batch_handle == "discard":
            self.num_batches = self.num_data // batch_size
        else:
            self.num_batches = (self.num_data + batch_size - 1) // batch_size
        self._cache_np = {k: v.asnumpy() for k, v in self.data + self.label}
        if shuffle:
            self._shuffle_data()

    @property
    def provide_data(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, (self.batch_size,) + v.shape[1:], v.dtype)
                for k, v in self.label]

    def _shuffle_data(self):
        _np.random.shuffle(self.idx)

    def reset(self):
        if self.shuffle:
            self._shuffle_data()
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            # leftover of the wrapped last batch starts the next epoch
            # (reference io.py:700 reset)
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % \
                self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        if self.last_batch_handle == "discard":
            return self.cursor + self.batch_size <= self.num_data
        return self.cursor < self.num_data

    def _slice(self, kv_list):
        out = []
        for k, _ in kv_list:
            src = self._cache_np[k]
            start = self.cursor
            end = self.cursor + self.batch_size
            if end <= self.num_data:
                part = src[self.idx[start:end]]
                self.num_pad = 0
            else:
                # wrap modulo num_data so the batch is always full even
                # when batch_size exceeds the dataset size
                sel = self.idx[_np.arange(start, end) % self.num_data]
                part = src[sel]
                self.num_pad = end - self.num_data
            out.append(nd_array(part))
        return out

    def getdata(self):
        return self._slice(self.data)

    def getlabel(self):
        return self._slice(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0

    def getindex(self):
        end = min(self.cursor + self.batch_size, self.num_data)
        return self.idx[self.cursor:end]


class ResizeIter(DataIter):
    """Resize an iterator to `size` batches per epoch (reference io.py:288)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        if hasattr(data_iter, "default_bucket_key"):
            self.default_bucket_key = data_iter.default_bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


from .. import telemetry as _telemetry

# prefetch-pipeline health: occupancy sampled at every consumer get()
# (how many decoded batches sat ready), plus a served-batch counter
_PREFETCH_OCC = _telemetry.REGISTRY.gauge(
    "io_prefetch_occupancy",
    "decoded batches waiting in the PrefetchingIter queue at get() time",
    unit="batches")
_PREFETCH_BATCHES = _telemetry.REGISTRY.counter(
    "io_prefetch_batches", "batches served through PrefetchingIter")
_DATA_WAIT_MS = _telemetry.REGISTRY.histogram(
    "io_data_wait_ms",
    "time the consumer blocked waiting for a prefetched batch — the "
    "per-step data-wait the fit loop's io.data_wait trace span renders",
    unit="ms")


class PrefetchingIter(DataIter):
    """Background-thread prefetch (reference io.py:349 + C++
    iter_prefetcher.h): overlaps host-side batch prep with device compute.

    With ``ctx`` set to an accelerator context, the worker ALSO starts
    the host->device transfer (``jax.device_put``) for each prefetched
    batch, double-buffered by ``prefetch_depth``: while the device runs
    step N, batch N+1 is already decoding AND transferring — the
    TPU-native analog of the reference's pinned-memory staging in
    iter_prefetcher.h (transfers are async in jax; dispatching them from
    the worker overlaps them with compute).

    With ``ctx`` a LIST of contexts, the worker shards each batch along
    its leading axis over a ``dp`` mesh of those devices at prefetch
    time, so a multi-device training step (executor_group /
    module/fused_fit.py) receives device-resident shards instead of
    splitting the batch on the fit thread. A batch whose leading dim
    does not divide the device count falls back to the first device."""

    def __init__(self, iters, rename_data=None, rename_label=None,
                 prefetch_depth=2, ctx=None):
        if not isinstance(iters, (list, tuple)):
            iters = [iters]
        super().__init__(iters[0].batch_size)
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self._depth = prefetch_depth
        self._mesh = None
        if isinstance(ctx, (list, tuple)):
            ctx = list(ctx)
            if len(ctx) > 1:
                import numpy as _np
                from jax.sharding import Mesh
                self._mesh = Mesh(_np.array([c.jax_device for c in ctx]),
                                  ("dp",))
            ctx = ctx[0] if ctx else None
        self._ctx = ctx
        self._queue = queue.Queue(maxsize=prefetch_depth)
        self._stop = threading.Event()
        self._thread = None
        self._start()

    def _to_device(self, batches):
        if self._ctx is None:
            return batches
        import jax
        from ..ndarray.ndarray import NDArray
        dev = self._ctx.jax_device
        mesh = self._mesh

        def place_dev0(nd):
            return NDArray(jax.device_put(nd._data, dev), self._ctx)

        def _batch_place(b):
            """Sharding is decided per BATCH, not per array: either every
            array (data and label) shards over the mesh or the whole
            batch stays on device 0 — a mixed batch would hand the
            consuming jitted step a new input-sharding combination
            (extra compile + resharding transfers)."""
            if mesh is None:
                return place_dev0
            ndev = mesh.devices.size
            arrays = list(b.data) + list(b.label or [])
            if not all(a.shape and a.shape[0] % ndev == 0 for a in arrays):
                return place_dev0
            from jax.sharding import NamedSharding, PartitionSpec as P
            bsh = NamedSharding(mesh, P("dp"))
            return lambda nd: NDArray(jax.device_put(nd._data, bsh),
                                      self._ctx)

        out = []
        for b in batches:
            place = _batch_place(b)
            out.append(DataBatch([place(d) for d in b.data],
                                 ([place(l) for l in b.label]
                                  if b.label is not None else None),
                                 b.pad, b.index,
                                 bucket_key=getattr(b, "bucket_key", None),
                                 provide_data=getattr(b, "provide_data", None),
                                 provide_label=getattr(b, "provide_label",
                                                       None)))
        return out

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(r, dict) else x
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(r, dict) else x
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def _start(self):
        def worker():
            while not self._stop.is_set():
                try:
                    batches = [i.next() for i in self.iters]
                except StopIteration:
                    self._queue.put(None)
                    return
                self._queue.put(self._to_device(batches))
        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def reset(self):
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        if self._thread is not None:
            self._thread.join(timeout=5)
        for i in self.iters:
            i.reset()
        self._stop = threading.Event()
        self._queue = queue.Queue(maxsize=self._depth)
        self._start()

    def next(self):
        import time as _time
        with _telemetry.tracing.span("io.data_wait") as sp:
            t0 = _time.perf_counter()
            batches = self._queue.get()
            wait_ms = (_time.perf_counter() - t0) * 1e3
            sp.set(occupancy=self._queue.qsize())
        _DATA_WAIT_MS.observe(wait_ms)
        # occupancy AFTER the get: batches still staged for future steps
        # — 0 here while the device is busy means the input pipeline is
        # the bottleneck (docs/OBSERVABILITY.md)
        _PREFETCH_OCC.set(self._queue.qsize())
        if batches is None:
            raise StopIteration
        _PREFETCH_BATCHES.inc()
        batch = batches[0]
        if len(batches) > 1:
            data = sum([b.data for b in batches], [])
            label = sum([b.label for b in batches], [])
            return DataBatch(data, label, batch.pad, batch.index)
        return batch

    def iter_next(self):
        try:
            self._peek = self.next()
            return True
        except StopIteration:
            return False


class CSVIter(DataIter):
    """CSV file iterator (reference src/io/iter_csv.cc)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, dtype="float32", **kwargs):
        super().__init__(batch_size)
        data = _np.loadtxt(data_csv, delimiter=",", dtype=dtype, ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = _np.loadtxt(label_csv, delimiter=",", dtype=dtype, ndmin=2)
            label = label.reshape((-1,) + tuple(label_shape))
            if label.shape[-1] == 1:
                label = label.reshape(label.shape[:-1])
        else:
            label = _np.zeros((data.shape[0],), dtype=dtype)
        self._inner = NDArrayIter(data, label, batch_size,
                                  last_batch_handle="pad" if round_batch else "discard",
                                  data_name="data", label_name="label")

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def next(self):
        return self._inner.next()

    def reset(self):
        self._inner.reset()

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label


class LibSVMIter(DataIter):
    """Zero-based-indexed LibSVM text file → CSR data batches
    (reference src/io/iter_libsvm.cc). Labels are the leading scalar of
    each data line unless ``label_libsvm`` names a separate LibSVM file
    (multi-dimensional labels, returned dense). ``num_parts``/
    ``part_index`` partition rows round-robin for distributed reading
    (the reference partitions the byte stream via dmlc InputSplit)."""

    def __init__(self, data_libsvm, data_shape, batch_size=1,
                 label_libsvm=None, label_shape=(1,), num_parts=1,
                 part_index=0, round_batch=True, dtype="float32", **kwargs):
        super().__init__(batch_size)
        if num_parts <= 0 or not 0 <= part_index < num_parts:
            raise ValueError("invalid num_parts=%s part_index=%s"
                             % (num_parts, part_index))
        self._dtype = dtype
        self._dim = int(data_shape[0]) if isinstance(
            data_shape, (tuple, list)) else int(data_shape)
        labels, rows = self._parse(data_libsvm)
        if label_libsvm is not None:
            ldim = int(label_shape[0]) if isinstance(
                label_shape, (tuple, list)) else int(label_shape)
            lab_vals, lab_rows = self._parse(label_libsvm)
            labels = [self._densify(r, ldim) for r in lab_rows]
        else:
            labels = [[l] for l in labels]
        labels = _np.asarray(labels, dtype=dtype)
        if labels.shape[-1] == 1:
            labels = labels.reshape(labels.shape[:-1])
        self._rows = rows[part_index::num_parts]
        self._labels = labels[part_index::num_parts]
        self._round_batch = round_batch
        self._cursor = 0
        self._provide_data = [DataDesc("data", (batch_size, self._dim),
                                       dtype)]
        self._provide_label = [DataDesc(
            "label", (batch_size,) + tuple(labels.shape[1:]), dtype)]

    @staticmethod
    def _parse(path):
        labels, rows = [], []
        with open(path) as f:
            for line in f:
                parts = line.split()
                if not parts:
                    continue
                if ":" in parts[0]:
                    label, feats = 0.0, parts
                else:
                    label, feats = float(parts[0]), parts[1:]
                row = []
                for tok in feats:
                    idx, val = tok.split(":")
                    row.append((int(idx), float(val)))
                labels.append(label)
                rows.append(row)
        return labels, rows

    def _densify(self, row, dim):
        out = [0.0] * dim
        for idx, val in row:
            out[idx] = val
        return out

    @property
    def provide_data(self):
        return self._provide_data

    @property
    def provide_label(self):
        return self._provide_label

    def reset(self):
        self._cursor = 0

    def next(self):
        n = len(self._rows)
        if self._cursor >= n:
            raise StopIteration
        take = list(range(self._cursor,
                          min(self._cursor + self.batch_size, n)))
        pad = self.batch_size - len(take)
        if pad:
            if self._round_batch:
                # wrap to the beginning, repeatedly if batch_size
                # exceeds the partition size
                take += [j % n for j in range(pad)]
            else:
                self._cursor = n
                raise StopIteration
        self._cursor += self.batch_size

        # assemble one CSR batch
        from ..ndarray import sparse as _sp
        indptr, indices, values = [0], [], []
        for i in take:
            for idx, val in self._rows[i]:
                indices.append(idx)
                values.append(val)
            indptr.append(len(indices))
        data = _sp.csr_matrix(
            (_np.asarray(values, dtype=self._dtype),
             _np.asarray(indices, dtype=_np.int64),
             _np.asarray(indptr, dtype=_np.int64)),
            shape=(self.batch_size, self._dim))
        label = nd_array(self._labels[_np.asarray(take)], dtype=self._dtype)
        return DataBatch(data=[data], label=[label], pad=pad,
                         provide_data=self._provide_data,
                         provide_label=self._provide_label)


class MNISTIter(DataIter):
    """MNIST idx-format iterator (reference src/io/iter_mnist.cc)."""

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128, shuffle=True,
                 flat=False, silent=False, seed=None, input_shape=None, **kwargs):
        super().__init__(batch_size)
        imgs = self._read_idx(image)
        labels = self._read_idx(label)
        imgs = imgs.astype("float32") / 255.0
        if flat:
            imgs = imgs.reshape(imgs.shape[0], -1)
        else:
            imgs = imgs.reshape(imgs.shape[0], 1, imgs.shape[1], imgs.shape[2])
        if input_shape is not None:
            imgs = imgs.reshape((imgs.shape[0],) + tuple(input_shape))
        self._inner = NDArrayIter(imgs, labels.astype("float32"), batch_size,
                                  shuffle=shuffle, last_batch_handle="discard")

    @staticmethod
    def _read_idx(path):
        if not os.path.exists(path):
            raise MXNetError("MNIST file not found: %s" % path)
        import gzip
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            magic = struct.unpack(">I", f.read(4))[0]
            ndim = magic & 0xFF
            dims = struct.unpack(">" + "I" * ndim, f.read(4 * ndim))
            data = _np.frombuffer(f.read(), dtype=_np.uint8)
            return data.reshape(dims)

    def next(self):
        return self._inner.next()

    def reset(self):
        self._inner.reset()

    def iter_next(self):
        return self._inner.iter_next()

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label


def MXDataIter(handle, **kwargs):  # pragma: no cover - compat shim
    raise MXNetError("MXDataIter wraps C++ iterators in the reference; use "
                     "the Python-native iterators (NDArrayIter, "
                     "ImageRecordIter, CSVIter, MNISTIter) instead")


def ImageRecordIter(**kwargs):
    """RecordIO image iterator — implemented in image/record_iter.py over the
    native recordio reader (reference src/io/iter_image_recordio_2.cc)."""
    from ..image.record_iter import ImageRecordIter as _Impl
    return _Impl(**kwargs)
