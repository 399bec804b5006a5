"""ModelServer: the public serving API + optional stdlib HTTP endpoint.

One object wires the subsystem together: a bounded ``RequestQueue``
(admission control), a shared ``DynamicBatcher`` (micro-batching +
bucket padding), and a ``ReplicaPool`` (one Predictor per device).

API surface::

    srv = ModelServer(sym, arg_params, aux_params,
                      input_shapes={"data": (3, 224, 224)},   # per example
                      num_replicas=2, max_batch_size=8)
    fut  = srv.submit({"data": x})            # future of [out_i rows]
    outs = srv.predict({"data": x})           # sync convenience
    outs = await srv.submit_async({"data": x})
    srv.drain(); srv.stop()
    srv.stats()                               # metrics snapshot (dict)
    srv.start_http(port=8123)                 # POST /predict, GET /stats

Observability: every snapshot field is also exported through
``mx.profiler`` user objects (Domain "serving": queue-depth and
batch-occupancy Counters, reject Markers), so a profiler trace shows the
serving control plane alongside the device timeline.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as _np

from ..base import MXNetError
from .. import profiler as _prof
from .batcher import (DeadlineExceededError, DynamicBatcher, QueueFullError,
                      Request, RequestQueue, ServerClosedError, ServingError,
                      normalize_buckets, percentile as _percentile)
from .replica import ReplicaPool

__all__ = ["ModelServer", "ServerStats"]

log = logging.getLogger(__name__)


class ServerStats:
    """Thread-safe metrics sink shared by the queue, batcher and replicas.

    Latency/throughput track a sliding window of recent completions (the
    last ``window`` requests), counters are monotonic totals. The same
    numbers feed ``stats()`` snapshots, the mx.profiler Counters, AND
    the mx.telemetry registry: every hook mirrors into process-wide
    ``serving_*`` series (docs/OBSERVABILITY.md), which is what ``GET
    /metrics`` scrapes. Registry series are shared across ModelServer
    instances and are never reset by :meth:`reset` (Prometheus
    counters must stay monotonic); per-instance ``stats()`` snapshots
    keep their window/reset semantics unchanged.
    """

    def __init__(self, window=4096):
        self._lock = threading.Lock()
        self.settled_cv = threading.Condition(self._lock)
        self.t_start = time.monotonic()
        # monotonic totals
        self.admitted = 0
        self.completed = 0
        self.rejected_queue_full = 0
        self.rejected_deadline = 0
        self.failed = 0
        self.cancelled = 0
        # batching
        self.batches = 0
        self.occupancy_sum = 0
        self.fill_sum = 0.0
        self.per_bucket = {}
        # sliding windows
        self._latencies = deque(maxlen=window)      # seconds
        self._completions = deque(maxlen=window)    # monotonic timestamps
        # profiler export (events only recorded while the profiler runs;
        # the Counters are registry-backed, so these five also appear in
        # /metrics as serving_queue_depth / serving_batch_occupancy / ...)
        dom = _prof.Domain("serving")
        self._c_depth = dom.new_counter("serving.queue_depth")
        self._c_occ = dom.new_counter("serving.batch_occupancy")
        self._c_p50 = dom.new_counter("serving.latency_p50_us")
        self._c_p99 = dom.new_counter("serving.latency_p99_us")
        self._c_qps = dom.new_counter("serving.throughput_qps")
        self._m_reject = dom.new_marker("serving.reject")
        # registry mirror: monotonic totals + the request-latency
        # histogram behind the /metrics scrape
        from .. import telemetry as _tm
        reg = _tm.REGISTRY
        self._r_admitted = reg.counter(
            "serving_admitted", "requests accepted into the queue")
        self._r_completed = reg.counter(
            "serving_completed", "requests completed successfully")
        self._r_rej_full = reg.counter(
            "serving_rejected_queue_full", "requests rejected: queue full")
        self._r_rej_deadline = reg.counter(
            "serving_rejected_deadline", "requests expired before running")
        self._r_failed = reg.counter(
            "serving_failed", "requests failed in a batch")
        self._r_cancelled = reg.counter(
            "serving_cancelled", "requests cancelled by the client")
        self._r_batches = reg.counter(
            "serving_batches", "micro-batches dispatched to replicas")
        self._r_latency = reg.histogram(
            "serving_request_ms",
            "end-to-end request latency (submit -> batch completion)",
            unit="ms")

    # -- hooks ---------------------------------------------------------
    def record_admitted(self, depth):
        with self._lock:
            self.admitted += 1
        self._r_admitted.inc()
        self._c_depth.set_value(depth)

    def record_depth(self, depth):
        self._c_depth.set_value(depth)

    def record_queue_full(self):
        with self._lock:
            self.rejected_queue_full += 1
        self._r_rej_full.inc()
        self._m_reject.mark()

    def record_expired(self, req):
        with self.settled_cv:
            self.rejected_deadline += 1
            self.settled_cv.notify_all()
        self._r_rej_deadline.inc()
        self._m_reject.mark()

    def record_cancelled(self, req):
        with self.settled_cv:
            self.cancelled += 1
            self.settled_cv.notify_all()
        self._r_cancelled.inc()

    def record_batch(self, replica_idx, mb):
        now = time.monotonic()
        done_latencies = []
        with self.settled_cv:
            self.batches += 1
            self.occupancy_sum += mb.n_real
            self.fill_sum += mb.fill
            self.per_bucket[mb.bucket] = self.per_bucket.get(mb.bucket, 0) + 1
            for req in mb.requests:
                if (req.future.done() and not req.future.cancelled()
                        and req.future.exception() is None):
                    self.completed += 1
                    self._latencies.append(now - req.t_submit)
                    self._completions.append(now)
                    done_latencies.append(now - req.t_submit)
            self.settled_cv.notify_all()
        self._r_batches.inc()
        if done_latencies:
            self._r_completed.inc(len(done_latencies))
            for lat in done_latencies:
                self._r_latency.observe(lat * 1e3)
        self._c_occ.set_value(mb.n_real)

    def record_failed_batch(self, replica_idx, mb, exc):
        with self.settled_cv:
            self.failed += mb.n_real
            self.settled_cv.notify_all()
        self._r_failed.inc(mb.n_real)

    def reset(self):
        """Zero every counter and window (benchmarks reset after warmup
        so compile-time batches don't bias occupancy/latency). Call only
        while the server is idle — an in-flight request would settle
        against the fresh counters and skew drain accounting."""
        with self.settled_cv:
            self.t_start = time.monotonic()
            self.admitted = self.completed = 0
            self.rejected_queue_full = self.rejected_deadline = 0
            self.failed = self.cancelled = 0
            self.batches = 0
            self.occupancy_sum = 0
            self.fill_sum = 0.0
            self.per_bucket = {}
            self._latencies.clear()
            self._completions.clear()
            self.settled_cv.notify_all()

    # -- drain support -------------------------------------------------
    def settled(self):
        return (self.completed + self.rejected_deadline + self.failed
                + self.cancelled)

    def wait_settled(self, target, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        with self.settled_cv:
            while self.settled() < target:
                left = None if deadline is None else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self.settled_cv.wait(left if left is not None else 0.1)
            return True

    # -- snapshot ------------------------------------------------------
    def snapshot(self, queue_depth=0, replicas=None):
        with self._lock:
            lat = sorted(self._latencies)
            comps = list(self._completions)
            batches = self.batches
            snap = {
                "uptime_s": round(time.monotonic() - self.t_start, 3),
                "queue_depth": queue_depth,
                "requests": {
                    "admitted": self.admitted,
                    "completed": self.completed,
                    "rejected_queue_full": self.rejected_queue_full,
                    "rejected_deadline": self.rejected_deadline,
                    "failed": self.failed,
                    "cancelled": self.cancelled,
                },
                "batches": {
                    "count": batches,
                    "mean_occupancy": (self.occupancy_sum / batches
                                       if batches else None),
                    "mean_fill": (self.fill_sum / batches
                                  if batches else None),
                    "per_bucket": dict(sorted(self.per_bucket.items())),
                },
            }
        to_ms = lambda v: None if v is None else round(v * 1e3, 3)
        snap["latency_ms"] = {
            "p50": to_ms(_percentile(lat, 0.50)),
            "p90": to_ms(_percentile(lat, 0.90)),
            "p99": to_ms(_percentile(lat, 0.99)),
            "mean": to_ms(sum(lat) / len(lat) if lat else None),
            "max": to_ms(lat[-1] if lat else None),
        }
        if len(comps) >= 2 and comps[-1] > comps[0]:
            snap["throughput_qps"] = round(
                (len(comps) - 1) / (comps[-1] - comps[0]), 2)
        else:
            snap["throughput_qps"] = None
        if replicas is not None:
            snap["replicas"] = replicas
        # mirror the derived metrics into the profiler counters so a
        # chrome trace carries p50/p99/qps tracks next to the per-batch
        # queue-depth/occupancy ones (events only record while running)
        if _prof.state() == "run":
            if snap["latency_ms"]["p50"] is not None:
                self._c_p50.set_value(snap["latency_ms"]["p50"] * 1e3)
                self._c_p99.set_value(snap["latency_ms"]["p99"] * 1e3)
            if snap["throughput_qps"] is not None:
                self._c_qps.set_value(snap["throughput_qps"])
        return snap


class ModelServer:
    """Dynamic-batching, multi-replica inference server (module docs).

    Parameters
    ----------
    symbol, arg_params, aux_params : the model (as for ``Predictor``)
    input_shapes : dict of per-EXAMPLE shapes, WITHOUT the batch axis —
        ``{"data": (3, 224, 224)}`` serves batches of (b, 3, 224, 224).
    num_replicas : worker replicas; replica i binds to ``contexts[i]``
        (default: ``mx.tpu(i)`` when accelerators exist, else ``mx.cpu(i)``)
    max_batch_size : micro-batch cap = the top bucket
    max_latency_ms : batching window opened by the first waiting request
    queue_capacity : admission bound; a full queue rejects immediately
    timeout_ms : default per-request deadline (None = no deadline)
    buckets : batch-size ladder (default 1, 2, 4, ..., max_batch_size)
    warmup : pre-compile every bucket shape at construction (threaded
        across (replica, bucket) pairs; MXNET_AOT_WARMUP_THREADS)
    warmup_manifest : AOT manifest path or dict (mx.aot.capture) — warm
        only the buckets a previous process actually served, marking
        their programs ``warmed`` in telemetry.programs(); on a
        restart the warmup disk-loads from the persistent compile cache
        instead of compiling (docs/AOT.md).  Default: the
        MXNET_AOT_MANIFEST knob.
    """

    def __init__(self, symbol, arg_params, aux_params, input_shapes,
                 num_replicas=1, contexts=None, max_batch_size=8,
                 max_latency_ms=5.0, queue_capacity=None, timeout_ms=None,
                 dtype="float32", buckets=None, warmup=True,
                 warmup_manifest=None, decode_engine=None, fleet=None):
        from ..predictor import Predictor

        for name, shape in input_shapes.items():
            if not isinstance(shape, (tuple, list)):
                raise MXNetError("input_shapes[%r] must be a shape tuple "
                                 "(per example, no batch axis)" % name)
        self._example_shapes = {n: tuple(s) for n, s in input_shapes.items()}
        self._dtype = dtype
        self._timeout_ms = timeout_ms
        # one ladder for everyone: the batcher can emit any bucket in it,
        # so the replicas/warmup/top-bind must see the identical list —
        # including a max_batch_size cap the caller's ladder didn't reach
        # (otherwise the first full-load batch would compile mid-traffic)
        self._buckets = normalize_buckets(buckets, max_batch_size)
        if queue_capacity is None:
            queue_capacity = max(64, 4 * max_batch_size * num_replicas)
        self._queue = RequestQueue(queue_capacity)
        self._stats = ServerStats()
        self._batcher = DynamicBatcher(self._queue, max_batch_size,
                                       max_latency_ms, self._buckets)
        self._batcher.on_expired = self._stats.record_expired
        self._batcher.on_cancelled = self._stats.record_cancelled
        self._batcher.on_depth = self._stats.record_depth

        if contexts is None:
            contexts = self._default_contexts(num_replicas)
        if len(contexts) != num_replicas:
            raise MXNetError("need %d contexts, got %d"
                             % (num_replicas, len(contexts)))
        top = self._buckets[-1]

        def make_predictor(ctx):
            return Predictor(
                symbol, arg_params, aux_params,
                {n: (top,) + s for n, s in self._example_shapes.items()},
                ctx=ctx, dtype=dtype)

        # warmup runs through aot_warm below so construction and the
        # explicit mx.aot.warm path share one (threaded) code path
        self._pool = ReplicaPool(contexts, make_predictor, self._buckets,
                                 self._batcher, self._stats, warmup=False)
        if warmup_manifest is None:
            from .. import aot as _aot
            warmup_manifest = _aot.default_path()
        self._warmup_manifest = warmup_manifest
        if warmup_manifest is not None:
            self.aot_warm(warmup_manifest)
        elif warmup:
            self._pool.warmup()
        self._closed = False
        self._http = None
        self._http_thread = None
        # optional mx.decode generative engine: POST /generate streams
        # chunked JSON-lines through it, reload() hot-swaps its weights
        # in lockstep with the replicas (docs/DECODE.md). The caller
        # owns the engine's lifecycle; stop() does not stop it.
        self._decode_engine = decode_engine
        # optional mx.fleet router: /generate requests are PLACED by
        # prefix affinity across the router's decode replicas instead
        # of going to the single attached engine; a `session` field in
        # the request body rides the router's stickiness map
        # (docs/FLEET.md). The caller owns replica lifecycles.
        self._fleet = fleet
        # hot-reload bookkeeping (docs/CHECKPOINT.md): version of the
        # weights currently served (checkpoint tag / epoch), reload count
        self._model_version = None
        self._reloads = 0
        self._reload_lock = threading.Lock()
        from .. import telemetry as _tm
        self._r_reloads = _tm.REGISTRY.counter(
            "serving_reloads", "successful hot weight reloads")
        self._pool.start()

    # ------------------------------------------------------------------
    @staticmethod
    def _default_contexts(n):
        import jax
        from .. import context as _ctx
        if any(d.platform != "cpu" for d in jax.local_devices()):
            return [_ctx.tpu(i % _ctx.num_tpus()) for i in range(n)]
        return [_ctx.cpu(i) for i in range(n)]

    @classmethod
    def load(cls, prefix, epoch, input_shapes, **kwargs):
        """Build a server from ``prefix-symbol.json`` + ``prefix-%04d.params``
        (the MXPredCreate file form)."""
        from .. import model as _model
        sym, arg_params, aux_params = _model.load_checkpoint(prefix, epoch)
        return cls(sym, arg_params, aux_params, input_shapes, **kwargs)

    # ------------------------------------------------------------------
    def _normalize(self, inputs):
        if set(inputs) != set(self._example_shapes):
            raise MXNetError(
                "inputs must provide exactly %s (got %s)"
                % (sorted(self._example_shapes), sorted(inputs)))
        out = {}
        for name, value in inputs.items():
            if hasattr(value, "asnumpy"):     # NDArray
                value = value.asnumpy()
            try:
                arr = _np.asarray(value, dtype=self._dtype)
            except (TypeError, ValueError) as e:
                # keep the structured-error contract: a garbage payload
                # is a client error (HTTP 400), not an internal 500
                raise MXNetError("input %r: cannot convert to a %s array "
                                 "(%s)" % (name, self._dtype, e)) from e
            want = self._example_shapes[name]
            if arr.shape != want:
                raise MXNetError(
                    "input %r: expected per-example shape %s, got %s"
                    % (name, want, arr.shape))
            out[name] = arr
        return out

    def submit(self, inputs=None, timeout_ms=None, **kw_inputs):
        """Enqueue one example; returns a ``concurrent.futures.Future``
        resolving to ``[output_i_row, ...]`` (one numpy array per model
        output). Raises ``QueueFullError`` (backpressure) or
        ``ServerClosedError`` immediately; the future fails with
        ``DeadlineExceededError`` when the deadline expires first."""
        if inputs is None:
            inputs = kw_inputs
        elif kw_inputs:
            raise MXNetError("pass inputs as one dict or as kwargs, not both")
        if self._closed:
            raise ServerClosedError("server is stopped")
        arrays = self._normalize(inputs)
        timeout_ms = self._timeout_ms if timeout_ms is None else timeout_ms
        deadline = (time.monotonic() + timeout_ms / 1e3
                    if timeout_ms is not None else None)
        fut = Future()
        req = Request(arrays, fut, deadline)
        from .. import telemetry as _tm
        if _tm.tracing.enabled():
            # admission -> settle span; parent = the submitting thread's
            # context (the HTTP handler's span, or a caller's trace)
            span = _tm.tracing.start_span("serving.request", rid=req.rid)
            req.span = span
            fut.add_done_callback(
                lambda f: span.end(
                    outcome=("cancelled" if f.cancelled() else
                             type(f.exception()).__name__
                             if f.exception() is not None else "ok")))
        try:
            self._queue.put(req)
        except QueueFullError:
            self._stats.record_queue_full()
            if req.span is not None:
                req.span.end(outcome="queue_full")
            raise
        self._stats.record_admitted(len(self._queue))
        return fut

    def predict(self, inputs=None, timeout_ms=None, **kw_inputs):
        """Synchronous convenience: submit + wait."""
        fut = self.submit(inputs, timeout_ms=timeout_ms, **kw_inputs)
        return fut.result()

    async def submit_async(self, inputs=None, timeout_ms=None, **kw_inputs):
        """Asyncio form: ``outs = await srv.submit_async({...})``."""
        import asyncio
        fut = self.submit(inputs, timeout_ms=timeout_ms, **kw_inputs)
        return await asyncio.wrap_future(fut)

    # ------------------------------------------------------------------
    def drain(self, timeout=None):
        """Block until everything admitted so far has settled (completed,
        expired, or failed). Returns False on timeout."""
        with self._stats._lock:
            target = self._stats.admitted
        return self._stats.wait_settled(target, timeout)

    def stop(self, drain=True, timeout=None):
        """Stop the server. ``drain=True`` (graceful) finishes queued work
        first; ``drain=False`` fails queued requests with
        ``ServerClosedError``. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.stop_http()
        if drain:
            self.drain(timeout)
            self._queue.close()
        else:
            self._queue.close()
            n_failed, n_raced = self._queue.reject_all(
                lambda req: ServerClosedError("server stopped before "
                                              "request %d ran" % req.rid))
            if n_failed or n_raced:
                with self._stats.settled_cv:
                    self._stats.failed += n_failed
                    self._stats.cancelled += n_raced
                    self._stats.settled_cv.notify_all()
                self._stats._r_failed.inc(n_failed)
                self._stats._r_cancelled.inc(n_raced)
        self._pool.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------------
    def reload(self, prefix, tag=None, epoch=None):
        """Hot-swap every replica to newer weights WITHOUT dropping
        queued requests (docs/CHECKPOINT.md).

        ``prefix`` names an mx.checkpoint prefix: ``tag=None`` resolves
        the newest checksum-intact checkpoint via
        ``checkpoint.latest`` (a torn in-progress write is skipped, not
        an error); ``epoch`` instead loads a legacy
        ``prefix-%04d.params`` file directly. Params are validated
        against the bound model before any replica is touched, then
        swapped in place per replica under its forward lock — compiled
        executors, queue and in-flight batches all survive. Returns the
        version served (tag/epoch)."""
        from ..checkpoint import resolve_params
        with self._reload_lock:
            arg_params, aux_params, version = resolve_params(
                prefix, tag, epoch, what="reload")
            base = self._pool.replicas[0]._base
            missing = [n for n in base._exe.arg_dict
                       if n not in arg_params
                       and n not in self._example_shapes
                       and not n.endswith("label")]
            missing += [n for n in base._exe.aux_dict
                        if n not in (aux_params or {})]
            if missing:
                raise MXNetError("reload: checkpoint is missing params %s"
                                 % sorted(missing))
            # shape-validate EVERYTHING before any replica is touched:
            # a mid-swap failure would leave replicas half-swapped with
            # no rollback, corrupting live traffic
            bad = []
            for params, live in ((arg_params, base._exe.arg_dict),
                                 (aux_params or {}, base._exe.aux_dict)):
                for name, v in params.items():
                    dst = live.get(name)
                    if dst is None or name in self._example_shapes:
                        continue
                    shape = getattr(v, "shape", None)
                    if shape is None:
                        shape = _np.shape(v)
                    if tuple(shape) != tuple(dst.shape):
                        bad.append(name)
            if bad:
                raise MXNetError(
                    "reload: checkpoint shapes do not match the bound "
                    "model for %s" % sorted(bad))
            from ..ndarray import NDArray
            arg_params = {k: v if isinstance(v, NDArray)
                          else NDArray(_np.asarray(v))
                          for k, v in arg_params.items()}
            aux_params = {k: v if isinstance(v, NDArray)
                          else NDArray(_np.asarray(v))
                          for k, v in (aux_params or {}).items()}
            # the attached decode engine must accept the checkpoint too
            # (same architecture => its paged-cache layout is preserved);
            # validate BEFORE any replica swaps so a mismatch is a clean
            # 409 with zero state touched
            if self._decode_engine is not None:
                self._decode_engine.check_params(arg_params)
            for rep in self._pool.replicas:
                rep.swap_params(arg_params, aux_params)
            if self._decode_engine is not None:
                self._decode_engine.swap_params(arg_params, version=version)
            self._model_version = version
            self._reloads += 1
            self._r_reloads.inc()
            return version

    # ------------------------------------------------------------------
    def _resolve_manifest(self, manifest):
        """Load + compatibility-gate an AOT manifest.  Incompatible or
        mismatched manifests resolve to None (full cold warmup) — a
        stale manifest must never fail a deploy (docs/AOT.md)."""
        from .. import aot as _aot
        m = manifest if manifest is not None else self._warmup_manifest
        if isinstance(m, str):
            try:
                m = _aot.load(m)
            except MXNetError as e:
                log.warning("serving: ignoring AOT manifest (%s)", e)
                return None
        if m is not None:
            ok, reason = _aot.compatible(m)
            if not ok:
                log.warning("serving: AOT manifest incompatible (%s); "
                            "warming the full bucket ladder instead",
                            reason)
                return None
        return m

    def aot_warm(self, manifest=None):
        """Compile (or, on a restart, disk-load from the cache) every
        (replica, bucket) program BEFORE the server accepts traffic —
        the mx.aot warmup hook (docs/AOT.md).  ``manifest`` defaults to
        the server's ``warmup_manifest``; programs dispatched here are
        flagged ``warmed`` in telemetry.programs().  Returns the number
        of programs dispatched."""
        from ..telemetry import programs as _programs
        m = self._resolve_manifest(manifest)
        with _programs.warming():
            return self._pool.warmup(manifest=m)

    def add_replica(self, ctx=None):
        """Scale up by one replica.  The new replica binds, AOT-warms
        its bucket ladder (through the server's manifest and the
        persistent cache, like startup) and only THEN starts pulling
        from the shared batcher — scale-up traffic never lands on a
        compiling replica.  Returns the new replica's index."""
        from ..telemetry import programs as _programs
        with self._reload_lock:
            if self._closed:
                raise MXNetError("cannot add a replica to a stopped server")
            if ctx is None:
                n = len(self._pool.replicas)
                ctx = self._default_contexts(n + 1)[n]
            m = self._resolve_manifest(None)
            with _programs.warming():
                rep = self._pool.add_replica(ctx, manifest=m)
            return rep.index

    # ------------------------------------------------------------------
    def stats(self):
        """Metrics snapshot: queue depth, admission/served counters, batch
        occupancy, latency percentiles, throughput, per-replica detail
        (glossary in docs/SERVING.md)."""
        snap = self._stats.snapshot(queue_depth=len(self._queue),
                                    replicas=self._pool.snapshot())
        snap["model_version"] = self._model_version
        # per-instance count; the registry's serving_reloads series is
        # process-global and shared across servers
        snap["reloads"] = self._reloads
        if self._decode_engine is not None:
            snap["decode"] = self._decode_engine.stats()
        if self._fleet is not None:
            snap["fleet"] = self._fleet.stats()
        return snap

    def reset_stats(self):
        """Zero the metrics (e.g. after a warmup phase); the server must
        be idle — drain() first if unsure."""
        self._stats.reset()

    # ------------------------------------------------------------------
    # optional JSON-over-HTTP endpoint (stdlib only)
    # ------------------------------------------------------------------
    def start_http(self, port=8123, host="127.0.0.1"):
        """Serve ``POST /predict`` ({"inputs": {...}, "timeout_ms": n}),
        ``GET /stats``, ``GET /metrics`` (Prometheus text exposition of
        the whole mx.telemetry registry — serving, kvstore, fit-step and
        HBM series; docs/OBSERVABILITY.md), ``GET /pod_metrics`` (the
        aggregated fleet view — rank-labeled scalars, bucket-merged
        histograms) and ``GET /health`` (which carries any open
        sentinel SLO incidents) on a daemon thread. Returns the bound
        (host, port)."""
        if self._http is not None:
            raise MXNetError("HTTP endpoint already running")
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        from .. import telemetry as _tm

        server = self

        class Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 for chunked transfer on /generate; every other
            # reply carries an exact Content-Length, so keep-alive is
            # safe.  The timeout reaps idle persistent connections —
            # without it every keep-alive client pins a server thread
            # and fd forever
            protocol_version = "HTTP/1.1"
            timeout = 60

            def log_message(self, *a):   # keep pytest/console output clean
                pass

            def _reply(self, code, doc):
                body = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _read_json(self):
                """Parse the POST body; replies 400 and returns None
                when it isn't a JSON object (callers just return)."""
                n = int(self.headers.get("Content-Length", 0) or 0)
                try:
                    doc = json.loads(self.rfile.read(n) or b"{}")
                except ValueError as e:
                    self._reply(400, {"error": "invalid JSON: %s" % e,
                                      "type": "bad_request"})
                    return None
                if not isinstance(doc, dict):
                    self._reply(400, {"error": "body must be a JSON "
                                      "object", "type": "bad_request"})
                    return None
                return doc

            def _chunk(self, data):
                self.wfile.write(b"%x\r\n" % len(data))
                self.wfile.write(data)
                self.wfile.write(b"\r\n")
                self.wfile.flush()

            def _do_generate(self, doc):
                """POST /generate — streamed autoregressive generation
                through the attached mx.decode engine.  Body:
                ``{"tokens": [...], "max_new_tokens": n, "stream": true,
                "eos_id"/"temperature"/"timeout_ms"/"seed": optional,
                "speculative": false}`` — the last opts one request out
                of draft-verify spans on a spec-enabled engine
                (docs/DECODE.md).
                Streaming replies are chunked JSON-lines: one
                ``{"index": i, "token": t}`` object per generated token
                and a final ``{"done": true, ...}`` summary line (an
                in-flight failure becomes a ``{"done": true, "error":
                ...}`` tail instead of a broken connection)."""
                eng = server._decode_engine
                if eng is None and server._fleet is None:
                    self._reply(404, {"error": "no decode engine attached "
                                      "(ModelServer(decode_engine=...) or "
                                      "ModelServer(fleet=...))",
                                      "type": "no_decode"})
                    return
                tokens = doc.get("tokens")
                if not isinstance(tokens, list) or not tokens:
                    self._reply(400, {"error": "generate needs a non-empty "
                                      "'tokens' list", "type": "bad_request"})
                    return
                replica = None
                if server._fleet is not None:
                    # cache-aware placement: the router picks the
                    # replica whose prefix trie best matches the
                    # prompt; a `session` field pins a conversation to
                    # the replica that holds its history (docs/FLEET.md)
                    try:
                        replica, eng = server._fleet.route(
                            tokens, session=doc.get("session"))
                    except MXNetError as e:
                        self._reply(503, {"error": str(e),
                                          "type": "no_replicas"})
                        return
                kwargs = {}
                if "eos_id" in doc:
                    kwargs["eos_id"] = doc["eos_id"]
                try:
                    handle = eng.submit(
                        tokens,
                        max_new_tokens=doc.get("max_new_tokens"),
                        timeout_ms=doc.get("timeout_ms"),
                        temperature=float(doc.get("temperature", 0.0)),
                        seed=doc.get("seed"),
                        speculative=bool(doc.get("speculative", True)),
                        **kwargs)
                except QueueFullError as e:
                    self._reply(429, {"error": str(e), "type": "queue_full"})
                    return
                except ServerClosedError as e:
                    self._reply(503, {"error": str(e), "type": "closed"})
                    return
                except (MXNetError, TypeError, ValueError) as e:
                    # TypeError/ValueError: malformed field types
                    # (non-int tokens, non-numeric temperature) — a
                    # client error, same as any other validation miss.
                    # MXNetError here is only a prompt the cache can
                    # NEVER hold (>= max_context, or more blocks than
                    # exist): long-but-servable prompts are admitted
                    # and prefilled in chunks (docs/DECODE.md)
                    self._reply(400, {"error": str(e), "type": "bad_request"})
                    return
                if not doc.get("stream", True):
                    # a client-supplied timeout_ms is enforced BY THE
                    # ENGINE (DeadlineExceededError below); the server
                    # backstop only has to outlast it, it must never
                    # undercut an explicit longer deadline
                    t_ms = doc.get("timeout_ms")
                    wait_s = 600.0 if t_ms is None else t_ms / 1e3 + 30.0
                    try:
                        toks = handle.result(timeout=wait_s)
                    except DeadlineExceededError as e:
                        self._reply(504, {"error": str(e),
                                          "type": "deadline"})
                        return
                    except TimeoutError as e:
                        # server-side backstop tripped: stop generating
                        # into a handle nobody will read (frees the
                        # slot + cache blocks at the next iteration)
                        handle.cancel()
                        self._reply(504, {"error": str(e),
                                          "type": "deadline"})
                        return
                    except Exception as e:   # noqa: BLE001
                        self._reply(500, {"error": str(e),
                                          "type": "internal"})
                        return
                    body = {"tokens": toks,
                            "finish_reason": handle.finish_reason,
                            "ttft_ms": handle.ttft_ms}
                    if replica is not None:
                        body["replica"] = replica
                    self._reply(200, body)
                    return
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                tail = None
                try:
                    for i, tok in enumerate(handle):
                        try:
                            self._chunk((json.dumps(
                                {"index": i, "token": tok}) + "\n").encode())
                        except OSError:
                            # client went away mid-stream: release the
                            # slot + cache blocks instead of generating
                            # the rest into a queue nobody reads
                            handle.cancel()
                            return
                except Exception as e:   # noqa: BLE001 — error as a tail line
                    tail = {"done": True, "error": str(e),
                            "type": e.__class__.__name__,
                            "tokens": handle.tokens}
                if tail is None:
                    tail = {"done": True,
                            "finish_reason": handle.finish_reason,
                            "tokens": handle.tokens,
                            "ttft_ms": handle.ttft_ms}
                if replica is not None:
                    tail["replica"] = replica
                try:
                    self._chunk((json.dumps(tail) + "\n").encode())
                    self.wfile.write(b"0\r\n\r\n")
                    self.wfile.flush()
                except OSError:
                    handle.cancel()

            def do_GET(self):
                if self.path == "/metrics":
                    body = _tm.generate_text().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     _tm.export.CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/pod_metrics":
                    # the aggregated fleet view (rank-labeled gauges/
                    # counters, bucket-merged histograms) — the local
                    # view when no exchange has happened yet
                    body = _tm.aggregate.pod_text().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     _tm.export.CONTENT_TYPE)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/stats":
                    self._reply(200, server.stats())
                elif self.path == "/fleet":
                    if server._fleet is None:
                        self._reply(404, {"error": "no fleet router "
                                          "attached (ModelServer("
                                          "fleet=...))", "type": "no_fleet"})
                    else:
                        self._reply(200, server._fleet.stats())
                elif self.path == "/health":
                    alerts = _tm.sentinel.SENTINEL.active()
                    ok = not server._closed
                    self._reply(200 if ok else 503,
                                {"status": "ok" if ok else "stopped",
                                 "sentinel_alerts": alerts})
                else:
                    self._reply(404, {"error": "unknown path %s" % self.path})

            def do_POST(self):
                if self.path == "/generate":
                    try:
                        doc = self._read_json()
                        if doc is not None:
                            # W3C traceparent joins the caller's trace;
                            # the span parents the whole decode
                            # lifecycle submitted inside it
                            with _tm.tracing.span(
                                    "http.generate",
                                    parent=_tm.tracing.extract(
                                        self.headers) or "current"):
                                self._do_generate(doc)
                    except Exception as e:   # noqa: BLE001
                        self._reply(500, {"error": str(e),
                                          "type": "internal"})
                    return
                if self.path == "/reload":
                    # admin endpoint: swap replicas to a newer checkpoint
                    # ({"prefix": ..., "tag"|"epoch": optional})
                    try:
                        doc = self._read_json()
                        if doc is None:
                            return
                        if not doc.get("prefix"):
                            self._reply(400, {"error": "reload needs a "
                                              "'prefix'",
                                              "type": "bad_request"})
                            return
                        version = server.reload(doc["prefix"],
                                                tag=doc.get("tag"),
                                                epoch=doc.get("epoch"))
                        self._reply(200, {"status": "ok",
                                          "model_version": version})
                    except MXNetError as e:
                        self._reply(409, {"error": str(e),
                                          "type": "reload_failed"})
                    except Exception as e:   # noqa: BLE001
                        self._reply(500, {"error": str(e),
                                          "type": "internal"})
                    return
                if self.path != "/predict":
                    # HTTP/1.1 keep-alive: drain the unread body first
                    # or its bytes desynchronize the next request on
                    # this connection
                    self.rfile.read(int(self.headers.get("Content-Length",
                                                         0) or 0))
                    self._reply(404, {"error": "unknown path %s" % self.path})
                    return
                try:
                    doc = self._read_json()
                    if doc is None:
                        return
                    with _tm.tracing.span(
                            "http.predict",
                            parent=_tm.tracing.extract(self.headers)
                            or "current"):
                        fut = server.submit(
                            doc.get("inputs") or {},
                            timeout_ms=doc.get("timeout_ms"))
                        outs = fut.result()
                    self._reply(200, {"outputs": [o.tolist() for o in outs]})
                except QueueFullError as e:
                    self._reply(429, {"error": str(e), "type": "queue_full"})
                except DeadlineExceededError as e:
                    self._reply(504, {"error": str(e), "type": "deadline"})
                except ServerClosedError as e:
                    self._reply(503, {"error": str(e), "type": "closed"})
                except ServingError as e:
                    self._reply(400, {"error": str(e), "type": "bad_request"})
                except MXNetError as e:
                    self._reply(400, {"error": str(e), "type": "bad_request"})
                except Exception as e:   # noqa: BLE001 — surface, don't hang
                    self._reply(500, {"error": str(e), "type": "internal"})

        self._http = ThreadingHTTPServer((host, port), Handler)
        self._http_thread = threading.Thread(
            target=self._http.serve_forever, name="mx-serving-http",
            daemon=True)
        self._http_thread.start()
        return self._http.server_address

    def stop_http(self):
        if self._http is not None:
            self._http.shutdown()
            self._http.server_close()
            self._http = None
            self._http_thread = None
