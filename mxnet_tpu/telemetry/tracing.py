"""mx.trace — cross-layer request/step tracing (docs/OBSERVABILITY.md).

Dapper-style distributed tracing for the three hot request shapes this
framework runs: a serving request (admission → batch → forward →
respond), a decode stream (submit → prefill → per-iteration decode →
done), and a training step (data-wait → fused dispatch → kvstore
push/pull → checkpoint tick).  The aggregate counters mx.telemetry
already exports answer "how fast is the fleet"; spans answer "where did
*this* request's 800 ms go".

Design rules (the same overhead contract as the registry):

* **Near-zero when disabled.**  Recording (the ring, ids,
  ``traceparent``) is OFF by default; every instrumentation site goes
  through :func:`span`/:func:`start_span`, which cost one module-global
  check when disabled.  No clock read, no lock, nothing recorded.
* **A set-up span keeps its seconds anyway.**  A site that a process
  walks once (``module.bind``, ``module.init_params``,
  ``module.init_optimizer``, ``fit.build``) passes a counter's child
  as ``seconds_to``: the span's wall seconds are added to it on exit,
  recording on or off (``setup_seconds{phase}``), and while the span is
  open the thread's program builds are laid to its name
  (``program_build_seconds{site}``).  Sites that pass none, every site
  of the steady path, take the path above.
* **Always on the profiler's clock.**  The context form :func:`span`
  enters a ``jax.profiler.TraceAnnotation`` of the span's name whether
  or not recording is enabled, so any ``jax.profiler`` trace (a
  benchmark's, ``mx.profiler`` with ``trace_dir``, a user's) holds the
  program's spans on its host plane, on the device's clock, with no
  switch thrown.  With no trace running an annotation is one inactive
  C++ object (a fraction of a microsecond; PERF.md has the number).
  :func:`start_span` spans overlap and cross threads, which a
  thread-nested annotation cannot: they stay ring-only.
* **Host-only.**  Spans bracket *dispatch* wall time on the host —
  never code inside a traced program — so enabling tracing can never
  add a retrace or a device launch (pinned by
  ``tests/test_trace.py::test_tracing_overhead_guard_*``).  Inside a
  compiled program the stable names are ``jax.named_scope``s
  (docs/OBSERVABILITY.md, "Scope names").
* **Thread-local context + explicit parents.**  Within one thread,
  ``with span(...)`` nests automatically (the fit loop's child spans
  need no plumbing).  Across threads — an HTTP handler submitting to
  the decode engine thread, a serving request crossing the batcher —
  the parent :class:`SpanContext` travels ON the request object and
  children are opened with ``parent=ctx``.
* **W3C traceparent on the wire.**  ``extract(headers)`` /
  ``traceparent()`` speak ``00-<trace_id>-<span_id>-01``, so a
  ``POST /generate`` carrying a ``traceparent`` header joins the
  caller's distributed trace and the whole decode lifecycle renders as
  one connected tree.

Finished spans land in a bounded ring (:func:`spans` /
:func:`drain_spans`) and export through both existing surfaces: the
flight recorder appends them to every dump (``{"span": {...}}`` lines),
and ``profiler.dump()`` renders the ring, once, as chrome-trace ``X``
events with ``trace_id``/``span_id``/``parent_id`` args
(:func:`chrome_events`).
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

from .registry import BUILD_SITE, REGISTRY

__all__ = ["Span", "SpanContext", "enable", "disable", "enabled",
           "span", "start_span", "current", "traceparent", "extract",
           "spans", "drain_spans", "clear", "chrome_events",
           "find_trace", "SPAN_CAPACITY", "SETUP_SECONDS"]

SPAN_CAPACITY = int(os.environ.get("MXNET_TRACE_CAPACITY", "4096") or 4096)

# span volume witness (labeled by the instrumented layer so a runaway
# producer is identifiable from /metrics alone)
SPANS_TOTAL = REGISTRY.counter(
    "trace_spans", "finished trace spans recorded, labeled by `layer` "
    "(the span-name prefix)", unit="spans")
DROPPED = REGISTRY.counter(
    "trace_spans_dropped", "finished spans evicted from the bounded "
    "ring before an export drained them", unit="spans")
# what a process pays once before its first step, kept with recording
# off: the set-up spans' sites pass a child of this as ``seconds_to``
SETUP_SECONDS = REGISTRY.counter(
    "setup_seconds", "wall seconds spent in a set-up phase of the "
    "process, labeled by `phase` (import, bind, init_params, "
    "init_optimizer, fit_build)", unit="s")

_ENABLED = False
_ring = deque(maxlen=SPAN_CAPACITY)
_ring_lock = threading.Lock()
_tls = threading.local()

# one shared 64-bit xorshift state for id generation; ids only need
# uniqueness within a process lifetime plus the entropy seeded below
_id_lock = threading.Lock()
_id_state = int.from_bytes(os.urandom(8), "big") | 1


def _next_id():
    global _id_state
    with _id_lock:
        x = _id_state
        x ^= (x << 13) & 0xFFFFFFFFFFFFFFFF
        x ^= x >> 7
        x ^= (x << 17) & 0xFFFFFFFFFFFFFFFF
        _id_state = x
        return x


def _new_span_id():
    return "%016x" % _next_id()


def _new_trace_id():
    return "%016x%016x" % (_next_id(), _next_id())


def enable():
    """Turn span recording on (also: env ``MXNET_TRACE=1`` at import)."""
    global _ENABLED
    _ENABLED = True


def disable():
    """Back to the default no-op path (one global check per site)."""
    global _ENABLED
    _ENABLED = False


def enabled():
    return _ENABLED


class SpanContext:
    """The propagatable identity of a span: (trace_id, span_id)."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id, span_id):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self):
        return "SpanContext(%s, %s)" % (self.trace_id, self.span_id)


class Span:
    """One live span.  ``end()`` (or exiting the context manager) stamps
    the duration and records the span in the ring.  Thread-compatible:
    a span may be *ended* by a different thread than opened it (a
    serving request settles on the replica thread), but only one thread
    may mutate it at a time — which the single-owner request objects
    guarantee.

    With ``seconds_to`` (a counter's child) the duration is also added
    there, and the context form names the thread's program builds for
    its body.  ``trace_id`` None is such a span while recording is off:
    it feeds its counter and the ring never sees it."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0",
                 "t_mono", "attrs", "_ended", "_tid", "_restore", "_ann",
                 "_seconds_to", "_outer_site")

    def __init__(self, name, trace_id, parent_id, attrs):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        self.t0 = time.time()
        self.t_mono = time.perf_counter()
        self.attrs = attrs
        self._ended = False
        self._tid = threading.get_ident()
        self._restore = None
        self._ann = None        # the profiler annotation (span() form)
        self._seconds_to = None     # set by span(..., seconds_to=)
        self._outer_site = None

    @property
    def context(self):
        return SpanContext(self.trace_id, self.span_id)

    def set(self, **attrs):
        """Attach attributes to a live span."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def end(self, **attrs):
        """Finish the span; idempotent (the first end wins)."""
        if self._ended:
            return self
        self._ended = True
        dur = time.perf_counter() - self.t_mono
        if self._seconds_to is not None:
            self._seconds_to.inc(dur)
        if self.trace_id is None:
            return self
        if attrs:
            self.set(**attrs)
        rec = {"name": self.name, "trace_id": self.trace_id,
               "span_id": self.span_id, "parent_id": self.parent_id,
               "t0": self.t0, "dur_ms": round(dur * 1e3, 4),
               "tid": self._tid & 0xFFFF}
        if self.attrs:
            rec["attrs"] = self.attrs
        _record(rec)
        return self

    # context-manager form publishes this span as the thread's current
    # so children opened in the body nest under it automatically
    def __enter__(self):
        if self.trace_id is not None:
            self._restore = getattr(_tls, "ctx", None)
            _tls.ctx = self.context
        if self._seconds_to is not None:
            self._outer_site, BUILD_SITE.name = BUILD_SITE.name, self.name
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._seconds_to is not None:
            BUILD_SITE.name = self._outer_site
        if self.trace_id is not None:
            _tls.ctx = self._restore
            self._restore = None
        if exc_type is not None:
            self.set(error=exc_type.__name__)
        self.end()
        return False


class _NullSpan:
    """Shared do-nothing span for the disabled path (and as the null
    parent sentinel carried on request objects while tracing is off)."""

    __slots__ = ()
    context = None
    trace_id = span_id = parent_id = None

    def set(self, **attrs):
        return self

    def end(self, **attrs):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()

_annotation_type = None


def _annotation(name):
    """A ``jax.profiler.TraceAnnotation`` of ``name`` that also answers
    the span protocol with no-ops (``set``/``end``/``context``), so the
    disabled path of :func:`span` IS the annotation: one C++ object,
    nothing recorded.  Built at first use: jax stays a lazy import of
    this package."""
    global _annotation_type
    if _annotation_type is None:
        from jax.profiler import TraceAnnotation

        class _Annotation(TraceAnnotation):
            __slots__ = ()
            context = None
            trace_id = span_id = parent_id = None

            def set(self, **attrs):
                return self

            def end(self, **attrs):
                return self

        _annotation_type = _Annotation
    return _annotation_type(name)


def _record(rec):
    layer = rec["name"].split(".", 1)[0]
    SPANS_TOTAL.labels(layer=layer).inc()
    with _ring_lock:
        if len(_ring) == _ring.maxlen:
            DROPPED.inc()
        _ring.append(rec)


def current():
    """The current thread's :class:`SpanContext` (or None)."""
    if not _ENABLED:
        return None
    return getattr(_tls, "ctx", None)


def start_span(name, parent="current", **attrs):
    """Open a span WITHOUT making it the thread's current context — the
    cross-thread form (the caller owns ``end()``).  ``parent`` is a
    :class:`SpanContext`, a :class:`Span`, None for a new root, or the
    default "current" (this thread's context)."""
    if not _ENABLED:
        return NULL_SPAN
    if parent == "current":
        parent = getattr(_tls, "ctx", None)
    elif isinstance(parent, Span):
        parent = parent.context
    if isinstance(parent, SpanContext):
        return Span(name, parent.trace_id, parent.span_id, attrs or None)
    return Span(name, _new_trace_id(), None, attrs or None)


def span(name, parent="current", seconds_to=None, **attrs):
    """Context-managed span that nests children opened in its body
    (thread-local).  The instrumentation workhorse::

        with tracing.span("fit.step", step=n):
            ...                       # children parent automatically

    Its body is always a ``jax.profiler.TraceAnnotation`` of ``name``
    (a running device trace shows it, whoever started the trace); the
    ring records it only when tracing is enabled.  ``seconds_to`` (a
    counter's child) gets the span's wall seconds whether or not it is:
    for the set-up phases, whose one span no trace of a window sees."""
    ann = _annotation(name)
    if _ENABLED:
        sp = start_span(name, parent=parent, **attrs)
    elif seconds_to is None:
        return ann
    else:
        sp = Span(name, None, None, None)
    sp._seconds_to = seconds_to
    sp._ann = ann
    return sp


# ----------------------------------------------------------------------
# W3C traceparent propagation (HTTP endpoints)
# ----------------------------------------------------------------------
def traceparent(ctx=None):
    """``00-<trace_id>-<span_id>-01`` for ``ctx`` (default: current)."""
    ctx = ctx if ctx is not None else current()
    if ctx is None or getattr(ctx, "trace_id", None) is None:
        return None
    if isinstance(ctx, Span):
        ctx = ctx.context
    return "00-%s-%s-01" % (ctx.trace_id, ctx.span_id)


def extract(header):
    """Parse a ``traceparent`` header (or a headers mapping) into a
    :class:`SpanContext`; None when absent/malformed (a bad header must
    never fail a request)."""
    if header is None:
        return None
    if hasattr(header, "get"):
        header = header.get("traceparent")
        if header is None:
            return None
    parts = str(header).strip().split("-")
    if len(parts) < 4:
        return None
    _ver, trace_id, span_id = parts[0], parts[1], parts[2]
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if int(trace_id, 16) == 0 or int(span_id, 16) == 0:
        return None
    return SpanContext(trace_id, span_id)


# ----------------------------------------------------------------------
# export surfaces
# ----------------------------------------------------------------------
def spans():
    """Finished spans currently in the ring (newest last)."""
    with _ring_lock:
        return list(_ring)


def drain_spans():
    """Pop every finished span out of the ring (flight-dump path)."""
    with _ring_lock:
        out = list(_ring)
        _ring.clear()
    return out


def clear():
    """Tests/teardown: empty the ring and the thread's context."""
    with _ring_lock:
        _ring.clear()
    _tls.ctx = None


def find_trace(trace_id, records=None):
    """All spans of one trace, parents before children (topological by
    parent links; ties keep ring order)."""
    recs = [r for r in (records if records is not None else spans())
            if r["trace_id"] == trace_id]
    by_id = {r["span_id"]: r for r in recs}
    out, seen = [], set()

    def add(rec):
        if rec["span_id"] in seen:
            return
        parent = by_id.get(rec.get("parent_id"))
        if parent is not None:
            add(parent)
        seen.add(rec["span_id"])
        out.append(rec)

    for rec in recs:
        add(rec)
    return out


def chrome_events(records=None):
    """Chrome-trace ``X`` events for finished spans — appended to every
    non-empty ``profiler.dump()`` (telemetry/chrome.py) so a trace
    viewer shows request/step spans against the device timeline."""
    recs = records if records is not None else spans()
    if not recs:
        return []
    pid = os.getpid()
    # wall-clock t0 -> the profiler's perf_counter epoch, so span and
    # profiler-event timestamps share one timeline in the viewer
    from .. import profiler as _prof
    now_wall = time.time()
    now_us = _prof._now_us()
    events = []
    for r in recs:
        ts = now_us - (now_wall - r["t0"]) * 1e6
        events.append({
            "name": r["name"], "cat": "trace", "ph": "X",
            "ts": ts, "dur": r["dur_ms"] * 1e3, "pid": pid,
            "tid": r.get("tid", 0),
            "args": {"trace_id": r["trace_id"], "span_id": r["span_id"],
                     "parent_id": r.get("parent_id"),
                     **(r.get("attrs") or {})}})
    return events


if os.environ.get("MXNET_TRACE", "0") == "1":
    enable()
