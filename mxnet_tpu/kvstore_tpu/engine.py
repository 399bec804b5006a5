"""Cross-host compiled bucket engine for kvstore='tpu'.

Extends the PR2 bucketed engine (kvstore_fused.FusedBucketEngine — the
pending queue, priority packing, streaming flush, and flat
error-feedback residual ownership are all inherited unchanged) with a
cross-host reduction stage. Two transports (docs/KVSTORE.md):

* **GSPMD** (TPU ICI/DCN; also every single-process world, so the CPU
  container and tier-1 exercise this exact path): each bucket is ONE
  jitted program spanning the process mesh —

      2-bit quantize per (process, device-stream) against its own
      DONATED flat error-feedback residual
        -> sequential stream sum (same order as single-host)
        -> cross-host all-reduce (``sum`` over the sharded 'dp' axis;
           XLA lowers it onto ICI/DCN)
        -> per-key fused optimizer apply on the replicated weights

  Per-process arrays lift into global arrays METADATA-ONLY: the mesh
  has one device per process, so a local ``(s0, ...)`` block is exactly
  one shard of a global ``(P*s0, ...)`` array sharded on axis 0, and a
  local replicated copy is exactly one shard of a ``P()``-sharded
  global array. No extra device launches, no copies.

* **Host** (multi-process on the CPU backend, whose XLA runtime cannot
  execute cross-process programs): the same quantize+local-reduce runs
  as one LOCAL jitted program per bucket, the flat contribution crosses
  hosts through the coordination-service allgather (rank-order
  deterministic sum), and a second local program applies the optimizer.
  2 launches + 1 host sync per bucket — the portability path, priced in
  ``kvstore_tpu_allgather_ms``; on real accelerator backends the GSPMD
  path is chosen automatically.

Semantics match single-host 2-bit training bit-for-bit modulo reduction
order: the quantize op sequence is the shared ``two_bit_quantize`` and
residuals stay host-local per (process, device-stream).
"""
from __future__ import annotations

import threading

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ndarray import NDArray
from .. import telemetry as _telemetry
from .. import fused_update as _fused
from ..kvstore_fused import (FusedBucketEngine, two_bit_quantize,
                             _note_retrace, _SITE,
                             DISPATCH_MS, _on_device)
from . import dist

__all__ = ["TPUBucketEngine"]

HOSTS = _telemetry.REGISTRY.gauge(
    "kvstore_tpu_hosts", "process count of the tpu kvstore's world")
CROSSHOST_BYTES = _telemetry.REGISTRY.counter(
    "kvstore_tpu_crosshost_bytes",
    "bytes this process contributed to cross-host gradient reduction "
    "(0 in a single-process world)", unit="bytes")
ALLGATHER_MS = _telemetry.REGISTRY.histogram(
    "kvstore_tpu_allgather_ms",
    "host wall time of one coordination-service allgather (the CPU-"
    "backend transport; unused when reduction rides GSPMD)", unit="ms")


class _OverlapPipeline:
    """FIFO worker thread carrying the host transport's wire+apply
    stages so bucket N's coordination-service transfer overlaps the
    quantize of bucket N+1 on the main thread (docs/KVSTORE.md
    "Overlapped push").

    Ordering is the correctness load-bearing property: every rank
    submits buckets in the same program order (SPMD push semantics) and
    the single worker executes them FIFO, so the ``kvpush`` collective
    sequence numbers pair across ranks exactly as the serial transport
    paired them. When the pipeline is active, the MAIN thread never
    issues a ``kvpush`` collective itself — mixed-thread issue orders of
    one tag would pair different ranks' epochs against each other.

    A job failure parks the exception and poisons the queue; the next
    ``submit``/``drain`` (every kvstore sync point drains) re-raises on
    the main thread.
    """

    def __init__(self):
        self._cv = threading.Condition()
        self._jobs = []
        self._active = 0           # queued + in-flight jobs
        self._exc = None
        self._thread = None

    def _ensure_thread(self):
        # caller holds _cv
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name="mx-kvstore-overlap")
            self._thread.start()

    def _raise_pending(self):
        # caller holds _cv
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def submit(self, job):
        with self._cv:
            self._raise_pending()
            self._ensure_thread()
            self._jobs.append(job)
            self._active += 1
            self._cv.notify_all()

    def drain(self):
        """Block until every submitted job has completed (or one of
        them failed, in which case its exception surfaces here)."""
        with self._cv:
            while self._active and self._exc is None:
                self._cv.wait()
            self._raise_pending()

    def _run(self):
        while True:
            with self._cv:
                while not self._jobs:
                    self._cv.wait()
                job = self._jobs.pop(0)
            try:
                job()
            except BaseException as e:      # park for the main thread
                with self._cv:
                    self._exc = e
                    self._jobs.clear()
                    self._active = 0
                    self._cv.notify_all()
                continue
            with self._cv:
                self._active -= 1
                if not self._active:
                    self._cv.notify_all()


def _build_tpu_step(layout, n_dev, nproc, threshold, mode, tpls, mp_flags,
                    use_wd):
    """ONE GSPMD program per bucket: compress -> cross-host all-reduce
    -> optimizer apply. Inputs arrive as global arrays over the process
    mesh: grads/residuals sharded on axis 0 ('dp'), weights/states
    replicated. For nproc == 1 this is semantically identical to the
    single-host bucket program (kvstore_fused._build_step): the same
    ``two_bit_quantize`` per stream, the same sequential stream-sum
    order, and ``sum(axis=0)`` over one process is exact."""
    n_keys = len(layout)

    def _reduce(residuals, grads):
        """(per-key replicated reduced list, new sharded residuals)."""
        if threshold is None:
            reduced = []
            for i, (_off, _size, shape) in enumerate(layout):
                acc = grads[0][i]
                for d in range(1, n_dev):
                    acc = acc + grads[d][i]
                # (P*s0, ...) -> (P, s0, ...) is a local reshape (row-
                # major blocks == shards); the axis-0 sum is the cross-
                # host all-reduce
                reduced.append(acc.reshape((nproc,) + tuple(shape))
                               .sum(axis=0))
            return reduced, ()
        dev_q, new_res = [], []
        for d in range(n_dev):
            parts = [grads[d][i].reshape(nproc, -1).astype(jnp.float32)
                     for i in range(n_keys)]
            g = parts[0] if n_keys == 1 \
                else jnp.concatenate(parts, axis=1)
            q, r = two_bit_quantize(residuals[d].reshape(nproc, -1), g,
                                    threshold)
            new_res.append(r.reshape(-1))
            dev_q.append(q)
        flat = dev_q[0]
        for q in dev_q[1:]:
            flat = flat + q
        flat = flat.sum(axis=0)          # cross-host all-reduce
        reduced = [lax.slice(flat, (off,), (off + size,)).reshape(shape)
                   for off, size, shape in layout]
        return reduced, tuple(new_res)

    if mode is None:
        def step(residuals, grads):
            _note_retrace()
            reduced, new_res = _reduce(residuals, grads)
            return tuple(reduced), new_res
        return jax.jit(step, donate_argnums=(0,))

    upd = _fused.build(mode)

    def step(weights, states, residuals, grads, lr_vec, wd_vec, rescale,
             extra):
        _note_retrace()
        reduced, new_res = _reduce(residuals, grads)
        new_ws, new_ss = [], []
        for i in range(n_keys):
            st = _fused.unflatten(tpls[i], states[i])
            e = extra[i] if upd.n_extra else ()
            new_w, new_s = _fused.apply_one(
                upd, weights[i], reduced[i], st, mp_flags[i],
                lr_vec[i], wd_vec[i], rescale, e, use_wd)
            new_ws.append(new_w)
            new_ss.append(tuple(_fused.flatten_state(new_s)[0]))
        return tuple(new_ws), tuple(new_ss), new_res
    return jax.jit(step, donate_argnums=(1, 2))


def _build_local_reduce(layout, n_dev, threshold):
    """Host-transport stage 1 (one LOCAL program): quantize per stream
    against the donated flat residuals, sequential stream sum, flat
    output ready for the wire. Dense buckets flatten too — the payload
    must be one buffer either way."""
    n_keys = len(layout)

    def step(residuals, grads):
        _note_retrace()
        if threshold is None:
            dev_flat = []
            for d in range(n_dev):
                dev_flat.append(
                    grads[d][0].reshape(-1) if n_keys == 1
                    else jnp.concatenate([grads[d][i].reshape(-1)
                                          for i in range(n_keys)]))
            flat = dev_flat[0]
            for f in dev_flat[1:]:
                flat = flat + f
            return flat, ()
        dev_q, new_res = [], []
        for d in range(n_dev):
            parts = [grads[d][i].reshape(-1).astype(jnp.float32)
                     for i in range(n_keys)]
            g = parts[0] if n_keys == 1 else jnp.concatenate(parts)
            q, r = two_bit_quantize(residuals[d], g, threshold)
            new_res.append(r)
            dev_q.append(q)
        flat = dev_q[0]
        for q in dev_q[1:]:
            flat = flat + q
        return flat, tuple(new_res)
    return jax.jit(step, donate_argnums=(0,))


def _build_local_apply(layout, tpls, mp_flags, use_wd, mode):
    """Host-transport stage 2 (one LOCAL program): slice the globally
    reduced flat gradient per key and run the fused optimizer apply."""
    upd = _fused.build(mode)

    # analyze: ok(retrace) upd is a pure memoized function of `mode`, which is a builder parameter and part of every compile-cache key
    def step(weights, states, red_flat, lr_vec, wd_vec, rescale, extra):
        _note_retrace()
        new_ws, new_ss = [], []
        for i, (off, size, shape) in enumerate(layout):
            g = lax.slice(red_flat, (off,), (off + size,)).reshape(shape)
            st = _fused.unflatten(tpls[i], states[i])
            e = extra[i] if upd.n_extra else ()
            new_w, new_s = _fused.apply_one(
                upd, weights[i], g, st, mp_flags[i],
                lr_vec[i], wd_vec[i], rescale, e, use_wd)
            new_ws.append(new_w)
            new_ss.append(tuple(_fused.flatten_state(new_s)[0]))
        return tuple(new_ws), tuple(new_ss)
    return jax.jit(step, donate_argnums=(1,))


class TPUBucketEngine(FusedBucketEngine):
    """FusedBucketEngine + cross-host reduction over the process mesh."""

    def __init__(self, kv):
        super().__init__(kv)
        self._nproc = dist.world_size()
        self._gspmd = dist.gspmd_supported()
        self._mesh = dist.process_mesh() if self._gspmd else None
        self._local_dev = jax.local_devices()[0]
        # host-transport overlap: the wire+apply of each bucket rides a
        # FIFO pipeline thread so transfers overlap the next bucket's
        # quantize (GSPMD buckets are XLA-async already and need none)
        self._pipeline = _OverlapPipeline() \
            if (self._overlap and not self._gspmd) else None
        HOSTS.set(self._nproc)

    def synchronize(self):
        """Land every pipelined wire+apply before the caller reads
        weights or optimizer state (kvstore sync points call this right
        after ``flush``)."""
        if self._pipeline is not None:
            self._pipeline.drain()

    # -- global-array lifting (metadata-only, no device launches) ------
    def _shard_spec(self):
        return NamedSharding(self._mesh, P("dp"))

    def _repl_spec(self):
        return NamedSharding(self._mesh, P())

    def _lift_shard(self, x):
        """Local (s0, ...) block -> global (P*s0, ...) sharded on axis 0."""
        if self._nproc == 1 and not x.shape:
            x = x.reshape(1)
        gshape = (self._nproc * x.shape[0],) + tuple(x.shape[1:])
        return jax.make_array_from_single_device_arrays(
            gshape, self._shard_spec(), [x])

    def _lift_repl(self, x):
        """Local full copy -> global replicated array."""
        return jax.make_array_from_single_device_arrays(
            x.shape, self._repl_spec(), [x])

    def _unlift(self, x):
        """Back to this process' addressable single-device view."""
        return x.addressable_data(0) if self._nproc > 1 else x

    # -- eligibility ----------------------------------------------------
    def ineligible_reason(self, key, vlist, mode):
        reason = super().ineligible_reason(key, vlist, mode)
        if reason is None and self._gspmd and not vlist[0].shape:
            # a 0-d value has no axis to shard the process dimension
            # onto; the eager path cross-host-reduces it correctly
            return "scalar_value"
        return reason

    # -- dispatch -------------------------------------------------------
    def _dispatch_inner(self, bucket, mode):
        # normalize every stream onto this process' mesh device FIRST so
        # residual seeding and global-array lifting see one placement
        for it in bucket:
            it.data = [_on_device(d, self._local_dev) for d in it.data]
        if self._gspmd:
            self._dispatch_gspmd(bucket, mode)
        else:
            self._dispatch_host(bucket, mode)

    def _bucket_layout(self, bucket):
        layout, off = [], 0
        for it in bucket:
            layout.append((off, it.size, it.shape))
            off += it.size
        return tuple(layout), off

    def _wire_bytes(self, nbytes):
        if self._nproc > 1:
            CROSSHOST_BYTES.inc(nbytes)

    def _dispatch_gspmd(self, bucket, mode):
        kv = self._kv
        comp = kv._compression
        threshold = comp.threshold if comp is not None else None
        n_dev = bucket[0].n_dev
        layout, flat_len = self._bucket_layout(bucket)

        grads = tuple(tuple(self._lift_shard(it.data[d]) for it in bucket)
                      for d in range(n_dev))
        residuals, keys_tuple = (), None
        if comp is not None:
            keys_tuple = tuple(it.key for it in bucket)
            residuals = tuple(
                self._lift_shard(r) for r in self._flat_residuals(
                    keys_tuple, layout, n_dev, bucket))
        self._wire_bytes(flat_len * bucket[0].itemsize)

        ctx0 = bucket[0].likes[0].context
        if mode is None:
            sig = ("tpu", None, threshold, n_dev, layout)
            fn = self._steps.get(sig)
            if fn is None:
                fn = self._steps[sig] = _build_tpu_step(
                    layout, n_dev, self._nproc, threshold, None, None,
                    None, False)
                _telemetry.programs.record("kvstore_tpu", fn,
                                           (residuals, grads))
            outs, new_res = fn(residuals, grads)
            for it, out in zip(bucket, outs):
                kv._store[it.key] = NDArray(self._unlift(out), ctx0)
        else:
            (weights_nd, state_leaves, tpls, mp_flags, lr_vec, wd_vec,
             extra, use_wd, rescale) = self._updater_inputs(bucket)
            sig = ("tpu", mode, threshold, n_dev, layout, tpls,
                   mp_flags, use_wd)
            fn = self._steps.get(sig)
            fresh = fn is None
            if fresh:
                fn = self._steps[sig] = _build_tpu_step(
                    layout, n_dev, self._nproc, threshold, mode,
                    tpls, mp_flags, use_wd)
            weights = tuple(self._lift_repl(
                _on_device(w._data, self._local_dev)) for w in weights_nd)
            states = tuple(
                tuple(self._lift_repl(_on_device(l._data,
                                                 self._local_dev))
                      for l in leaves) for leaves in state_leaves)
            if fresh:
                _telemetry.programs.record(
                    "kvstore_tpu", fn,
                    (weights, states, residuals, grads, lr_vec, wd_vec,
                     rescale, extra))
            new_ws, new_ss, new_res = fn(weights, states, residuals,
                                         grads, lr_vec, wd_vec, rescale,
                                         extra)
            for w, leaves, nw, ns in zip(weights_nd, state_leaves,
                                         new_ws, new_ss):
                w._set_data(self._unlift(nw))
                for l, nl in zip(leaves, ns):
                    l._set_data(self._unlift(nl))
        if keys_tuple is not None:
            self._flat_res[keys_tuple]["res"] = [self._unlift(r)
                                                 for r in new_res]

    def _dispatch_host(self, bucket, mode):
        """CPU-backend multi-process transport: local quantize program
        -> host allgather (rank-order sum) -> local apply program.

        With overlap on (the default), the wire+apply stages run as ONE
        FIFO pipeline job so bucket N's coordination-service transfer
        overlaps bucket N+1's quantize on the main thread; the payload
        fetch (the device sync on the quantize output) moves onto the
        pipeline thread too. Everything ORDER-SENSITIVE on the host —
        program-cache fills, residual record updates, the updater's
        update-count/lr/wd side effects — stays on the main thread in
        push order, so overlapped and serial runs are bit-identical;
        the job only reads weight/state ``._data`` AFTER the previous
        bucket's apply wrote them (FIFO), exactly like the serial
        interleaving."""
        import time
        from ..executor import _count_dispatch
        kv = self._kv
        comp = kv._compression
        threshold = comp.threshold if comp is not None else None
        n_dev = bucket[0].n_dev
        layout, flat_len = self._bucket_layout(bucket)

        grads = tuple(tuple(it.data[d] for it in bucket)
                      for d in range(n_dev))
        residuals, keys_tuple = (), None
        if comp is not None:
            keys_tuple = tuple(it.key for it in bucket)
            residuals = tuple(self._flat_residuals(keys_tuple, layout,
                                                   n_dev, bucket))

        sig = ("tpu-host-reduce", threshold, n_dev, layout)
        fn = self._steps.get(sig)
        if fn is None:
            fn = self._steps[sig] = _build_local_reduce(layout, n_dev,
                                                        threshold)
            _telemetry.programs.record("kvstore_tpu", fn,
                                       (residuals, grads))
        flat_q, new_res = fn(residuals, grads)
        if keys_tuple is not None:
            self._flat_res[keys_tuple]["res"] = list(new_res)

        ctx0 = bucket[0].likes[0].context
        if mode is None:
            apply_inputs = None
        else:
            apply_inputs = self._updater_inputs(bucket)
            tpls, mp_flags, use_wd = (apply_inputs[2], apply_inputs[3],
                                      apply_inputs[7])
            sig = ("tpu-host-apply", mode, layout, tpls, mp_flags,
                   use_wd)
            fn_apply = self._steps.get(sig)
            if fn_apply is None:
                fn_apply = self._steps[sig] = _build_local_apply(
                    layout, tpls, mp_flags, use_wd, mode)

        def wire_and_apply():
            # analyze: ok(hostsync) the host transport crosses the wire by design (CPU-backend multiprocess); priced in kvstore_tpu_allgather_ms
            payload = _np.ascontiguousarray(_np.asarray(flat_q))
            self._wire_bytes(payload.nbytes)
            t0 = time.perf_counter()
            red_np = dist.allreduce_sum_np("kvpush", payload)
            ALLGATHER_MS.observe((time.perf_counter() - t0) * 1e3)
            if apply_inputs is None:
                for it, (off, size, shape) in zip(bucket, layout):
                    kv._store[it.key] = NDArray(
                        jnp.asarray(red_np[off:off + size]
                                    .reshape(shape)), ctx0)
                return
            (weights_nd, state_leaves, _tpls, _mp, lr_vec, wd_vec,
             extra, _use_wd, rescale) = apply_inputs
            _count_dispatch()   # the apply is a second device launch
            weights = tuple(w._data for w in weights_nd)
            states = tuple(tuple(l._data for l in leaves)
                           for leaves in state_leaves)
            new_ws, new_ss = _SITE.timed(
                fn_apply, weights, states, jnp.asarray(red_np), lr_vec,
                wd_vec, rescale, extra, dispatch_hist=DISPATCH_MS)
            for w, leaves, nw, ns in zip(weights_nd, state_leaves,
                                         new_ws, new_ss):
                w._set_data(nw)
                for l, nl in zip(leaves, ns):
                    l._set_data(nl)

        if self._pipeline is not None:
            # ALL kvpush wire traffic rides the pipeline when overlap is
            # on (not just streaming-flushed buckets): one FIFO issue
            # order per rank keeps the collective sequence numbers
            # paired across ranks
            self._pipeline.submit(wire_and_apply)
        else:
            wire_and_apply()
