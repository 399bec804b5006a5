"""Compiled bucketed kvstore hot path (docs/KVSTORE.md).

The eager ``KVStore.push`` is a per-key Python loop: one compression
round-trip, one add-chain, and one updater dispatch per parameter. MXNet's
CommDevice got its speed from bucketed big-array reduction; this module
reproduces that shape, compiled: same-dtype gradients are packed into
size-capped buckets (``MXNET_KVSTORE_BIGARRAY_BOUND`` bytes, the analog
of MXNet's big-array bound) and each bucket runs ONE jitted computation
per step:

    2-bit quantize (error-feedback residual, donated)
      -> dequantize -> cross-device reduce
      -> fused optimizer apply (or plain assign when no updater is set)

Step functions are cached by (keyset, shapes, dtype, compression config,
optimizer signature) so steady-state training hits the compile cache with
zero retraces — ``TRACE_COUNT`` increments only when a bucket program is
(re)traced, and tests pin that it stays flat after the first step.

Priorities finally do something: pushes carry ``priority=`` into the
pending queue, buckets are formed and dispatched in descending priority,
and XLA's async dispatch overlaps the bucket computations with whatever
host work (remaining backward) follows the push. ``pull``/``barrier``/
state save are the sync points that flush pending work.

The optimizer apply is built from the SHARED fused-update builder
(fused_update.py): any optimizer describing its update via
``Optimizer._fused_sig`` — SGD, Adam, LAMB, RMSProp, ... including
multi-precision ``(inner, weight32)`` state tuples and f16/bf16
weights with f32 masters — runs inside the bucket program. 2-bit
error-feedback residuals always live in f32 (the master-gradient
view), so compression semantics are dtype-independent.

Fallbacks stay eager per-key (and correct): row_sparse values,
custom updaters, and optimizers without a fused signature (slug
``unfused_optimizer:<Name>`` on the kvstore_fallbacks counter).
"""
from __future__ import annotations

import os

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from .ndarray import NDArray
from . import profiler
from . import telemetry as _telemetry
from . import fused_update as _fused

__all__ = ["FusedBucketEngine", "bucket_byte_cap", "TRACE_COUNT",
           "two_bit_quantize", "fused_sgd_apply", "overlap_enabled",
           "OVERLAP_DISPATCHES", "OVERLAP_WINDOW_MS"]


def two_bit_quantize(residual, grad, threshold):
    """Error-feedback 2-bit quantize for one device stream: returns
    ``(q, new_residual)``. The op sequence (add, exact-constant selects,
    subtract) matches TwoBitCompressor.compress_decompress bit-for-bit;
    it is SHARED by the bucketed kvstore step and the fused fit step
    (module/fused_fit.py) so cross-path parity is structural, not
    maintained by hand in two places.  ``MXNET_Q2BIT_IMPL`` selects the
    fused Pallas kernel (pallas/quant.py — same op sequence, so still
    bit-exact) instead of this elementwise XLA chain."""
    from .pallas import two_bit_quantize_fused, use_q2bit_pallas
    kernel = use_q2bit_pallas()
    if kernel:
        return two_bit_quantize_fused(residual, grad, threshold,
                                      interpret=kernel == "interpret")
    t = jnp.asarray(threshold, dtype=grad.dtype)
    acc = residual + grad
    q = jnp.where(acc > t, t, jnp.where(acc < -t, -t, jnp.zeros_like(acc)))
    return q, acc - q


def fused_sgd_apply(w, g_reduced, state, lr, wd, rescale, momentum, clip,
                    use_wd):
    """One key's SGD(-momentum) apply, identical op sequence to
    ops/optimizer_ops.py sgd(_mom)_update (rescale -> clip -> wd ->
    momentum); shared by the bucket program and the fused fit step.
    ``state`` None means plain SGD. Returns (new_w, new_state|None)."""
    g = g_reduced.astype(jnp.float32) * rescale
    if clip is not None and clip >= 0:
        g = jnp.clip(g, -clip, clip)
    if use_wd:
        g = g + wd * w.astype(jnp.float32)
    if state is not None:
        new_mom = momentum * state.astype(jnp.float32) - lr * g
        new_w = w.astype(jnp.float32) + new_mom
        return new_w.astype(w.dtype), new_mom.astype(state.dtype)
    new_w = w.astype(jnp.float32) - lr * g
    return new_w.astype(w.dtype), None

# incremented inside each bucket step function at trace time only; a
# steady-state step that hits the jit cache leaves it untouched. The
# count lives in the mx.telemetry registry (kvstore_bucket_retraces);
# the module-level ``TRACE_COUNT`` name stays a live alias via
# __getattr__ below, so existing zero-retrace pins keep working.
BUCKET_RETRACES = _telemetry.REGISTRY.counter(
    "kvstore_bucket_retraces",
    "compiled bucket-program (re)traces (the TRACE_COUNT witness)",
    vital=True)
DISPATCH_MS = _telemetry.REGISTRY.histogram(
    "kvstore_dispatch_ms",
    "host wall time to dispatch one bucket program (async enqueue)",
    unit="ms")
# backward-overlap witness (docs/KVSTORE.md "Overlapped push"): a bucket
# dispatched by the STREAMING flush leaves the host before the final
# backward bucket's grads have even been enqueued — comms provably
# overlap the remaining backward walk. Ticked only there (never by the
# end-of-push flush), so a positive delta IS the overlap proof the
# tests gate on (tests/test_kvstore_fused.py).
OVERLAP_DISPATCHES = _telemetry.REGISTRY.counter(
    "kvstore_overlap_dispatches",
    "bucket programs dispatched by the streaming flush BEFORE the final "
    "backward bucket landed (the comm/compute overlap witness)",
    vital=True)
OVERLAP_WINDOW_MS = _telemetry.REGISTRY.histogram(
    "kvstore_overlap_window_ms",
    "host wall time from the first overlapped bucket dispatch of a push "
    "walk to the walk's final flush (the window comms had to hide in "
    "backward)", unit="ms")


def overlap_enabled():
    """Backward-overlapped bucket dispatch (``MXNET_KVSTORE_OVERLAP``,
    default on). 0 restores the serial shape: streaming-flushed buckets
    still dispatch in availability order, but the cross-host wire (tpu
    host transport) runs inline and the overlap witness stays silent."""
    return os.environ.get("MXNET_KVSTORE_OVERLAP", "1") != "0"
# shared RetraceSite semantics with executor / fused_fit: step bodies
# call _note_retrace() at trace time; _dispatch times through it.
# _dispatch wraps a non-jitted inner, so bucket programs register with
# the compiled-program registry at their cache-miss sites below
_SITE = _telemetry.RetraceSite(BUCKET_RETRACES, _telemetry.JIT_COMPILE_MS,
                               site="kvstore_bucket")
_note_retrace = _SITE.note


def __getattr__(name):
    if name == "TRACE_COUNT":
        return int(BUCKET_RETRACES.value)
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))


_DEFAULT_BUCKET_BYTES = 4 << 20


def bucket_byte_cap():
    """Flat-bucket size cap in bytes (env ``MXNET_KVSTORE_BIGARRAY_BOUND``,
    default 4 MiB). A single value larger than the cap gets its own
    bucket, like the reference's big-array bypass."""
    return int(os.environ.get("MXNET_KVSTORE_BIGARRAY_BOUND",
                              _DEFAULT_BUCKET_BYTES))


# kvstore profiler counters (thread-safe Counter; emitted into the chrome
# trace whenever the profiler is running, readable as .value always)
_domain = profiler.Domain("kvstore")
BYTES_PUSHED = _domain.new_counter("kvstore_bytes_pushed", vital=True)
COMPRESS_RATIO = _domain.new_counter("kvstore_compress_ratio", vital=True)
BUCKET_COUNT = _domain.new_counter("kvstore_bucket_count", vital=True)


def _single_device(x):
    """The one device an array is committed/placed on, or None when the
    array is mesh-sharded (left where it is — XLA handles it SPMD)."""
    try:
        ds = x.devices()
    except AttributeError:
        return None
    return next(iter(ds)) if len(ds) == 1 else None


def _on_device(x, dev):
    if dev is None or _single_device(x) in (dev, None):
        return x
    return jax.device_put(x, dev)


def _build_step(layout, n_dev, threshold, mode, tpls, mp_flags, use_wd,
                sentinel=False):
    """Compile-once bucket program: the whole bucket — 2-bit compress with
    error feedback, cross-device reduce, and the optimizer apply for every
    key — is ONE jitted computation.

    Without compression the per-key arrays are NOT physically
    concatenated: XLA fuses each key's reduce+update chain into one kernel
    either way, and a real flatten would read+write every gradient byte an
    extra time purely to rearrange memory (measured 0.8x vs eager on CPU;
    per-key-in-one-program wins).

    With compression the bucket IS physically flat: each device's
    gradients concatenate into one flat f32 buffer (the master-gradient
    view — low-precision gradients are cast first, so residual semantics
    are dtype-independent), quantize against a single DONATED flat
    error-feedback residual per device, reduce flat, and only the
    optimizer apply slices back per key. That turns n_keys × n_dev
    tiny quantize kernels — plus as many residual output buffers and
    host-side writebacks — into n_dev of each.

    layout: tuple of (offset, size, shape) per key — the flat layout.
    mode: None for plain assign (no updater), or the optimizer's fused
    signature, e.g. ("sgd", momentum, clip) — built into the per-key
    apply via the SHARED fused-update builder (fused_update.py).
    rescale_grad / lr / wd / per-key extra scalars are runtime
    arguments, not compile keys, so per-batch rewrites (gluon
    Trainer.step) and schedule steps never retrace.
    tpls: per-key state template (fused_update.state_template) — states
    cross the jit boundary as flat leaf tuples and are rebuilt inside.
    mp_flags: per-key static multi-precision flag — True where the state
    is an ``(inner, weight32)`` master-weight tuple.
    """
    n_keys = len(layout)

    def _reduce(residuals, grads):
        """Compress (error feedback) then sum over devices; returns
        (per-key reduced list, new flat residuals). The op sequence
        mirrors TwoBitCompressor.compress_decompress and
        KVStore._local_reduce exactly (elementwise quantize, sequential
        adds in device order) so results are bit-identical to the eager
        path."""
        if threshold is None:
            reduced = []
            for i in range(n_keys):
                acc = grads[0][i]
                for d in range(1, n_dev):
                    acc = acc + grads[d][i]
                reduced.append(acc)
            return reduced, ()
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
        reduced = [lax.slice(flat, (off,), (off + size,)).reshape(shape)
                   for off, size, shape in layout]
        return reduced, tuple(new_res)

    def _nonfinite(grads):
        """Per-bucket isfinite witness (docs/OBSERVABILITY.md): count of
        non-finite gradient elements across every device stream, folded
        into the SAME bucket program as a single scalar — no extra
        dispatch, read only at sync boundaries via a donated
        accumulator."""
        nf = jnp.float32(0.0)
        for d in range(n_dev):
            for i in range(n_keys):
                nf = nf + jnp.sum(
                    (~jnp.isfinite(grads[d][i])).astype(jnp.float32))
        return nf

    if mode is None:
        if sentinel:
            def step(residuals, grads, nf_acc):
                _note_retrace()
                reduced, new_res = _reduce(residuals, grads)
                return tuple(reduced), new_res, nf_acc + _nonfinite(grads)
            return jax.jit(step, donate_argnums=(0, 2))

        def step(residuals, grads):
            _note_retrace()
            reduced, new_res = _reduce(residuals, grads)
            return tuple(reduced), new_res
        return jax.jit(step, donate_argnums=(0,))

    upd = _fused.build(mode)

    def _apply(weights, states, residuals, grads, lr_vec, wd_vec,
               rescale, extra):
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

    if sentinel:
        def step(weights, states, residuals, grads, lr_vec, wd_vec,
                 rescale, extra, nf_acc):
            _note_retrace()
            new_ws, new_ss, new_res = _apply(
                weights, states, residuals, grads, lr_vec, wd_vec,
                rescale, extra)
            return new_ws, new_ss, new_res, nf_acc + _nonfinite(grads)
        return jax.jit(step, donate_argnums=(1, 2, 8))

    def step(weights, states, residuals, grads, lr_vec, wd_vec, rescale,
             extra):
        _note_retrace()
        return _apply(weights, states, residuals, grads, lr_vec, wd_vec,
                      rescale, extra)
    return jax.jit(step, donate_argnums=(1, 2))


class _Pending:
    # grad buffers are SNAPSHOTTED at push time (MXNet's push-at-call
    # semantics): a later in-place write to the pushed NDArray rebinds
    # its ._data and must not change what an async flush applies
    __slots__ = ("key", "data", "likes", "priority", "seq", "size",
                 "shape", "itemsize")

    def __init__(self, key, vlist, priority, seq):
        self.key = key
        self.data = [v._data for v in vlist]
        self.likes = vlist          # shape/dtype/context templates only
        self.priority = priority
        self.seq = seq
        self.shape = vlist[0].shape
        self.size = int(_np.prod(self.shape)) if self.shape else 1
        self.itemsize = vlist[0].dtype.itemsize

    @property
    def n_dev(self):
        return len(self.data)


class FusedBucketEngine:
    """Per-store pending queue + bucket planner + compiled-step cache."""

    def __init__(self, kv):
        self._kv = kv
        self._pending = []
        self._pending_keys = set()
        self._pending_bytes = 0
        self._seq = 0
        self._steps = {}     # bucket signature -> jitted step fn
        # flat error-feedback residuals: keys_tuple -> {"layout", "res":
        # [per-device jnp flat buffer]} — donated into the bucket program
        # each step; seeded from / spilled to the eager per-(key,dev)
        # dict so switching paths never loses accumulated residual
        self._flat_res = {}
        self.last_flush_buckets = []   # [[keys]] in dispatch order
        self.stats = {"flushes": 0, "buckets": 0, "keys": 0,
                      "bytes_pushed": 0}
        # comm/compute overlap (docs/KVSTORE.md "Overlapped push"):
        # _streaming marks dispatches issued by the mid-push streaming
        # flush (they overlap the rest of the backward walk by
        # construction); _overlap_t0 opens the per-walk overlap window
        # at the first such dispatch and the next end-of-push flush
        # closes it into kvstore_overlap_window_ms
        self._overlap = overlap_enabled()
        self._streaming = False
        self._overlap_t0 = None
        # in-launch numerics witness: donated f32 scalar accumulating
        # non-finite gradient elements across bucket programs; read only
        # at sync boundaries by publish_sentinels()
        self._nf_acc = None
        self._published_nf = 0.0

    # -- eligibility ----------------------------------------------------
    def _updater_mode(self):
        """None for assign mode, a fused signature tuple for a fusable
        optimizer Updater, or False when updates must stay eager."""
        from .optimizer import Updater
        updater = self._kv._updater
        if updater is None:
            return None
        if not isinstance(updater, Updater):
            return False
        sig = updater.optimizer._fused_bucket_sig()
        return sig if sig is not None else False

    def eligible(self, key, vlist, mode):
        """mode: the result of _updater_mode(), computed once per push
        call by the caller (it cannot change mid-call)."""
        return self.ineligible_reason(key, vlist, mode) is None

    def ineligible_reason(self, key, vlist, mode):
        """None when the push may take the compiled bucketed path, else
        a BOUNDED reason slug (it becomes a telemetry label on the
        ``kvstore_fallbacks`` counter — keep key names and shapes out)."""
        if mode is False:
            from .optimizer import Updater
            updater = self._kv._updater
            if not isinstance(updater, Updater):
                return "custom_updater"
            return ("unfused_optimizer:%s"
                    % type(updater.optimizer).__name__)
        for v in vlist:
            if not isinstance(v, NDArray):
                return "non_ndarray_value"
            if getattr(v, "stype", "default") != "default":
                return "sparse_value"
            if v.dtype != _np.float32:
                # low-precision values fuse only through an optimizer
                # apply (f32 master-gradient view); assign mode stays
                # f32 so stored dtypes can't silently change
                if mode is None or not _fused.is_low_precision(v.dtype):
                    return "non_f32_dtype"
            if v.shape != vlist[0].shape:
                return "mismatched_device_shapes"
        if mode is not None:
            stored = self._kv._store.get(key)
            if stored is None:
                return "key_not_initialized"
            if stored.dtype != vlist[0].dtype \
                    or stored.shape != vlist[0].shape:
                return "stored_value_mismatch"
            from .kvstore import _updater_key
            st = self._kv._updater.states.get(_updater_key(key))
            if st is not None:
                leaves, _ = _fused.flatten_state(st)
                if not all(isinstance(l, NDArray) for l in leaves):
                    return "non_fusable_optimizer_state"
        return None

    # -- queue ----------------------------------------------------------
    @property
    def has_pending(self):
        return bool(self._pending)

    def enqueue(self, key, vlist, priority):
        if key in self._pending_keys:
            # two pushes of the same key without a sync point: preserve
            # push-ordering semantics by flushing the first
            self.flush()
        it = _Pending(key, vlist, priority, self._seq)
        self._pending.append(it)
        self._pending_keys.add(key)
        self._pending_bytes += it.size * it.itemsize
        self._seq += 1
        # streaming flush: once a bucket's worth is pending, dispatch the
        # full buckets NOW (the partial tail stays pending) — enqueue
        # order (executor_group.push_order: backward gradient
        # availability) decides which buckets hit the device while the
        # host is still walking the remaining keys
        if self._pending_bytes >= bucket_byte_cap():
            self.flush(keep_partial=True)

    # -- planning -------------------------------------------------------
    def _pack(self, items):
        """Greedy size-capped packing in (priority desc, arrival) order;
        a new bucket starts when the cap would overflow or the device
        count or dtype changes (a bucket's flat wire layout is
        homogeneous); an oversized value gets its own bucket."""
        cap = bucket_byte_cap()
        buckets, cur, cur_bytes = [], [], 0
        for it in items:
            nbytes = it.size * it.itemsize
            if cur and (cur_bytes + nbytes > cap
                        or it.n_dev != cur[0].n_dev
                        or it.likes[0].dtype != cur[0].likes[0].dtype):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(it)
            cur_bytes += nbytes
            if cur_bytes >= cap:
                buckets.append(cur)
                cur, cur_bytes = [], 0
        if cur:
            buckets.append(cur)
        return buckets

    # -- flush ----------------------------------------------------------
    def flush(self, keep_partial=False):
        """Dispatch pending pushes as compiled buckets (priority desc,
        then arrival). With ``keep_partial`` (the streaming path), a
        trailing bucket still below the byte cap stays pending so
        steady-state bucket shapes don't depend on where mid-push
        flushes landed."""
        if not keep_partial and self._overlap_t0 is not None:
            # the walk that opened an overlap window is landing its
            # final bucket: close the window (time comms had to hide)
            import time
            OVERLAP_WINDOW_MS.observe(
                (time.perf_counter() - self._overlap_t0) * 1e3)
            self._overlap_t0 = None
        if not self._pending:
            return
        items = sorted(self._pending, key=lambda it: (-it.priority, it.seq))
        self._pending = []
        self._pending_keys.clear()
        self._pending_bytes = 0
        buckets = self._pack(items)
        if keep_partial and buckets:
            cap = bucket_byte_cap()
            tail = buckets[-1]
            if sum(it.size * it.itemsize for it in tail) < cap:
                buckets = buckets[:-1]
                for it in tail:
                    self._pending.append(it)
                    self._pending_keys.add(it.key)
                    self._pending_bytes += it.size * it.itemsize
            if not buckets:
                return
        self.last_flush_buckets = [[it.key for it in b] for b in buckets]
        items = [it for b in buckets for it in b]
        mode = self._updater_mode()
        if keep_partial and self._overlap and self._overlap_t0 is None:
            import time
            self._overlap_t0 = time.perf_counter()
        self._streaming = keep_partial
        try:
            for bucket in buckets:
                self._dispatch(bucket, mode)
        finally:
            self._streaming = False
        comp = self._kv._compression
        nbytes = sum(it.size * it.itemsize * it.n_dev for it in items)
        self.stats["flushes"] += 1
        self.stats["buckets"] += len(buckets)
        self.stats["keys"] += len(items)
        self.stats["bytes_pushed"] += nbytes
        BYTES_PUSHED.increment(nbytes)
        # logical wire ratio of the active config (orig bits / 2-bit);
        # the local store never materializes packed bytes, so this is
        # nominal by construction — see docs/KVSTORE.md
        COMPRESS_RATIO.set_value(
            items[0].itemsize * 8 / 2.0 if comp is not None else 1.0)
        BUCKET_COUNT.set_value(len(buckets))

    def _dispatch(self, bucket, mode):
        from .executor import _count_dispatch
        _count_dispatch()       # one compiled bucket program per call
        if self._streaming and self._overlap:
            # dispatched before the final backward bucket landed: the
            # program (XLA-async; the tpu host transport's wire rides
            # the pipeline thread) overlaps the rest of the walk
            OVERLAP_DISPATCHES.inc()
        return _SITE.timed(self._dispatch_inner, bucket, mode,
                           dispatch_hist=DISPATCH_MS)

    def synchronize(self):
        """Block until every dispatched bucket's side effects are
        visible on this host. The base engine's dispatches are XLA-async
        only (jax arrays synchronize at first read), so this is a no-op;
        the tpu engine overrides it to drain its pipelined wire thread.
        Called by the kvstore's sync points (pull/barrier/state save)."""

    def publish_sentinels(self):
        """Fold the donated non-finite witness scalar into the shared
        ``nonfinite_grads`` counter. Reading the scalar is a HOST SYNC —
        this runs only from existing sync boundaries (Module._fit_sync,
        kvstore pull/barrier), never the per-step dispatch path.
        Returns the cumulative count, or None when no witness rode a
        program yet (sentinels off, or nothing dispatched)."""
        acc = self._nf_acc
        if acc is None:
            return None
        # analyze: ok(hostsync) sentinel publish rides an existing sync boundary (_fit_sync / kvstore pull), never the per-dispatch path
        cum = float(_np.asarray(acc))
        delta = int(round(cum - self._published_nf))
        if delta > 0:
            self._published_nf = cum
            from .telemetry import sentinel as _sentinel
            _sentinel.NONFINITE_GRADS.inc(delta)
            from .telemetry.flight import RECORDER
            RECORDER.note("sentinel_trip", source="kvstore_bucket",
                          nonfinite=delta)
        return cum

    def _updater_inputs(self, bucket):
        """Collect the live optimizer-apply inputs for one bucket (and
        perform the per-key update-count side effects) — shared by the
        single-process bucket program and the tpu kvstore's cross-host
        programs (kvstore_tpu/engine.py) so keying/lr/wd semantics can
        never drift between them."""
        from .kvstore import _updater_key
        kv = self._kv
        updater = kv._updater
        opt = updater.optimizer
        ukeys = [_updater_key(it.key) for it in bucket]
        weights_nd, state_leaves, tpls, mp_flags = [], [], [], []
        for it, uk in zip(bucket, ukeys):
            w = kv._store[it.key]
            if uk not in updater.states:
                updater.states[uk] = opt.create_state_multi_precision(
                    uk, w)
                updater.states_synced[uk] = True
            weights_nd.append(w)
            leaves, tpl = _fused.flatten_state(updater.states[uk])
            state_leaves.append(leaves)
            tpls.append(tpl)
            # multi-precision is an EXPLICIT static flag (an Adam
            # (mean, var) pair is structurally ambiguous with an
            # (inner, weight32) master tuple)
            mp_flags.append(bool(opt.multi_precision)
                            and _fused.is_low_precision(w.dtype))
        lr_vec, wd_vec, extra = opt._fused_runtime(ukeys)
        use_wd = bool(_np.any(wd_vec != 0.0))
        return (weights_nd, state_leaves, tuple(tpls), tuple(mp_flags),
                lr_vec, wd_vec, extra, use_wd,
                _np.float32(opt.rescale_grad))

    def _dispatch_inner(self, bucket, mode):
        kv = self._kv
        comp = kv._compression
        threshold = comp.threshold if comp is not None else None
        n_dev = bucket[0].n_dev
        assert mode is not False

        layout, off = [], 0
        for it in bucket:
            layout.append((off, it.size, it.shape))
            off += it.size
        layout = tuple(layout)

        # CommDevice gather: device-committed gradients move to the
        # bucket's reduce device so the single program has one placement
        # (uncommitted and mesh-sharded arrays pass through untouched)
        dev0 = _single_device(bucket[0].data[0])
        grads = tuple(tuple(_on_device(it.data[d], dev0)
                            for it in bucket) for d in range(n_dev))
        residuals, keys_tuple = (), None
        if comp is not None:
            keys_tuple = tuple(it.key for it in bucket)
            residuals = self._flat_residuals(keys_tuple, layout, n_dev,
                                             bucket)

        ctx0 = bucket[0].likes[0].context
        sent = _telemetry.sentinel.numerics_enabled()
        nf = None
        if sent:
            nf = self._nf_acc
            if nf is None:
                nf = jnp.zeros((), jnp.float32)
            nf = _on_device(nf, dev0)
        if mode is None:
            sig = (None, threshold, n_dev, layout, sent)
            fn = self._steps.get(sig)
            if fn is None:
                fn = self._steps[sig] = _build_step(
                    layout, n_dev, threshold, None, None, None, False,
                    sentinel=sent)
                _telemetry.programs.record(
                    "kvstore_bucket", fn,
                    (residuals, grads, nf) if sent
                    else (residuals, grads))
            if sent:
                outs, new_res, self._nf_acc = fn(residuals, grads, nf)
            else:
                outs, new_res = fn(residuals, grads)
            for it, out in zip(bucket, outs):
                kv._store[it.key] = NDArray(out, ctx0)
        else:
            (weights_nd, state_leaves, tpls, mp_flags, lr_vec, wd_vec,
             extra, use_wd, rescale) = self._updater_inputs(bucket)
            sig = (mode, threshold, n_dev, layout, tpls, mp_flags,
                   use_wd, sent)
            fn = self._steps.get(sig)
            fresh = fn is None
            if fresh:
                fn = self._steps[sig] = _build_step(
                    layout, n_dev, threshold, mode, tpls, mp_flags,
                    use_wd, sentinel=sent)
            weights = tuple(w._data for w in weights_nd)
            states = tuple(tuple(l._data for l in leaves)
                           for leaves in state_leaves)
            if fresh:
                _telemetry.programs.record(
                    "kvstore_bucket", fn,
                    (weights, states, residuals, grads, lr_vec, wd_vec,
                     rescale, extra, nf) if sent
                    else (weights, states, residuals, grads, lr_vec,
                          wd_vec, rescale, extra))
            if sent:
                new_ws, new_ss, new_res, self._nf_acc = fn(
                    weights, states, residuals, grads, lr_vec, wd_vec,
                    rescale, extra, nf)
            else:
                new_ws, new_ss, new_res = fn(
                    weights, states, residuals, grads, lr_vec, wd_vec,
                    rescale, extra)
            for w, leaves, nw, ns in zip(weights_nd, state_leaves,
                                         new_ws, new_ss):
                w._set_data(nw)
                for l, nl in zip(leaves, ns):
                    l._set_data(nl)
        if keys_tuple is not None:
            self._flat_res[keys_tuple]["res"] = list(new_res)

    # -- flat error-feedback residuals ---------------------------------
    def _flat_residuals(self, keys_tuple, layout, n_dev, bucket):
        """Donated flat residual buffers for a bucket, one per device.
        First use seeds each buffer from the eager per-(key,dev) residual
        dict (zeros when absent) and takes ownership of those entries; a
        layout/device-count change spills back first so no accumulated
        residual is ever lost."""
        rec = self._flat_res.get(keys_tuple)
        if rec is not None and (rec["layout"] != layout
                                or len(rec["res"]) != n_dev):
            self.spill_residuals()
            rec = None
        if rec is None and self._flat_res:
            # a changed bucket composition may hold some of these keys'
            # residuals inside other flat records — spill everything back
            # to the per-key dict so seeding below picks them up
            ours = set(keys_tuple)
            if any(ours.intersection(kt) for kt in self._flat_res):
                self.spill_residuals()
        if rec is None:
            kv = self._kv
            dev0 = _single_device(bucket[0].data[0])
            res = []
            for d in range(n_dev):
                # residuals live in f32 (the master-gradient view)
                # regardless of the gradient dtype; the cast is a no-op
                # for f32 and defends against pre-f32 restored state
                parts = [_on_device(
                    kv._get_residual((it.key, d), it.likes[d])._data,
                    dev0).reshape(-1).astype(jnp.float32)
                    for it in bucket]
                res.append(parts[0] if len(parts) == 1
                           else jnp.concatenate(parts))
                for it in bucket:
                    kv._compression_residuals.pop((it.key, d), None)
            rec = self._flat_res[keys_tuple] = {"layout": layout,
                                                "res": res}
        return tuple(rec["res"])

    def spill_residuals(self):
        """Write flat residuals back to the eager per-(key,dev) dict (as
        NDArrays) — called before anything that may reroute keys to the
        eager path (updater/compression/bucketing changes)."""
        kv = self._kv
        for keys_tuple, rec in self._flat_res.items():
            for d, flat in enumerate(rec["res"]):
                for key, (off, size, shape) in zip(keys_tuple,
                                                   rec["layout"]):
                    seg = flat[off:off + size].reshape(shape)
                    kv._compression_residuals[(key, d)] = NDArray(seg)
        self._flat_res.clear()
