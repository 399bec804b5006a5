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
* **The fit step alone reads the clock always.**  A fused fit step
  stamps its boundaries into the step timeline (below) whether or not
  recording is on: eight ``perf_counter_ns`` reads, one
  ``thread_time_ns``, one ``gc.get_stats`` and one append a step,
  7-8 us on the sandbox's CPU in a stub loop (docs/OBSERVABILITY.md,
  "The step timeline", has the measurements).  ``telemetry.disable()`` brings it
  to one attribute check a site.  Every other site of the steady path
  keeps the rule above.
* **A set-up span keeps its seconds anyway.**  A site that a process
  walks once (``module.bind``, ``module.init_params``,
  ``module.init_optimizer``, ``fit.build``) passes a counter's child
  as ``seconds_to``: the span's wall seconds are added to it on exit,
  recording on or off (``setup_seconds{phase}``), and while the span is
  open the thread's program builds are laid to its name
  (``program_build_seconds{site}``).  Sites that pass none, every site
  of the steady path, take the path above.  Such a span mints no ids
  and reads no wall clock while recording is off: the ring never sees
  it.
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

**The step timeline.**  Beside the spans, the program keeps one record
a fit step on the host's monotonic clock (:func:`steps`): when the
step passed each of its boundaries, the thread's CPU time, full
garbage collections and program builds over it.  A step runs from one
entry of ``FusedFitStep.step`` to the next, the caller's loop
included, which is the interval a throughput inverts.  At each entry
the step that just ended is held against the median of the last 64
(:func:`_judge`): a slow step is counted by the phase that holds most
of its excess (``fit_slow_steps{phase}``) and logged in one line.
"""
from __future__ import annotations

import gc
import itertools
import logging
import os
import threading
import time
from collections import deque

from . import registry as _registry
from .registry import BUILD_SITE, REGISTRY

__all__ = ["Span", "SpanContext", "enable", "disable", "enabled",
           "span", "start_span", "current", "traceparent", "extract",
           "spans", "drain_spans", "clear", "chrome_events",
           "find_trace", "SPAN_CAPACITY", "SETUP_SECONDS",
           "StepRecord", "step_entry", "open_step", "steps", "phases",
           "clear_steps", "STEP_CAPACITY", "STEP_STAMPS", "STEP_PHASES"]

log = logging.getLogger(__name__)

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
        # ids and the wall clock are the ring's: a set-up span while
        # recording is off (trace_id None) feeds its counter alone
        recorded = trace_id is not None
        self.span_id = _new_span_id() if recorded else None
        self.parent_id = parent_id
        self.t0 = time.time() if recorded else None
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
# the step timeline: one record a fit step, on the host's clock
# ----------------------------------------------------------------------
STEP_CAPACITY = 4096
# a step's boundaries in the order it passes them (perf_counter_ns;
# None for one it did not pass)
STEP_STAMPS = ("entry", "dispatch0", "dispatch1", "rebind1", "wait0",
               "wait1", "transfer1", "next_entry")
# what the stamps cut a step's interval into; ``outside`` is the rest:
# ``update_metric``, ``metric.reset`` and the caller's loop
_PHASE_STAMPS = (("prepare", "entry", "dispatch0"),
                 ("dispatch", "dispatch0", "dispatch1"),
                 ("rebind", "dispatch1", "rebind1"),
                 ("wait", "wait0", "wait1"),
                 ("transfer", "wait1", "transfer1"))
STEP_PHASES = tuple(p for p, _, _ in _PHASE_STAMPS) + ("outside",)
# a slow step: past _SLOW_FACTOR medians of the last _RECENT steps,
# once _JUDGE_AFTER are complete; the median is refreshed every
# _REFRESH steps at most
_SLOW_FACTOR, _RECENT, _JUDGE_AFTER, _REFRESH = 2, 64, 8, 16

SLOW_STEPS = REGISTRY.counter(
    "fit_slow_steps", "fit steps that took over twice the median of "
    "the last 64, labeled by `phase`: the one that holds most of the "
    "excess over its own median (prepare, dispatch, rebind, wait, "
    "transfer, outside)", unit="steps")
SLOW_STEP_SECONDS = REGISTRY.counter(
    "fit_slow_step_seconds", "seconds the slow fit steps took over "
    "the median step", unit="s")

_now = time.perf_counter_ns
_steps = deque(maxlen=STEP_CAPACITY)
_slow_seen = itertools.count(1)     # the process's slow steps, for the log


class StepRecord:
    """One fit step, from one entry of ``FusedFitStep.step`` to the
    next.  The sites write the stamps they pass; the rest stay None."""

    dispatch0 = dispatch1 = rebind1 = None
    wait0 = wait1 = transfer1 = next_entry = None
    cpu_ns = gc2 = builds = None
    fused = False

    def __init__(self, step, entry, cpu, gc2, builds):
        self.step = step
        self.entry = entry
        self._at_entry = (cpu, gc2, builds)

    def _close(self, now, cpu, gc2, builds):
        cpu0, gc0, builds0 = self._at_entry
        self.next_entry = now
        self.cpu_ns = cpu - cpu0
        self.gc2 = gc2 - gc0
        self.builds = builds - builds0

    __getitem__ = object.__getattribute__       # a stamp by its name

    def as_dict(self):
        out = {"step": self.step, "fused": self.fused,
               "open": self.next_entry is None,
               "cpu_ns": self.cpu_ns, "gc2": self.gc2,
               "builds": self.builds}
        for name in STEP_STAMPS:
            out[name] = self[name]
        return out


class _History:
    """What one thread's steps are judged against, and its open step."""

    __slots__ = ("open", "owner", "count", "done", "recent", "median",
                 "refreshed", "builds_metric")

    def __init__(self):
        self.open = None
        self.builds_metric = None
        self.restart(None)

    def restart(self, owner):
        """Another stepping object (its ``id``): a fresh history."""
        self.owner = owner
        self.count = 0              # steps entered
        self.done = 0               # steps ended and judged
        self.recent = deque(maxlen=_RECENT)     # the last of those
        self.median = None          # of their intervals, ns
        self.refreshed = 0          # ``done`` when it was taken


class _Timeline(threading.local):
    """Each thread's :class:`_History` (one attribute: the entry pays
    one thread-local read)."""

    def __init__(self):
        self.history = _History()


_timeline = _Timeline()


def _marks(hist):
    """What is differenced at the entries: the thread's CPU ns, full
    garbage collections, program builds."""
    m = hist.builds_metric
    if m is None:
        # aot/store.py registers it; a process that never built reads 0
        m = hist.builds_metric = REGISTRY.get("program_builds")
    return (time.thread_time_ns(), gc.get_stats()[2]["collections"],
            0 if m is None else m.total)


def step_entry(owner=None):
    """A fit step begins: close the thread's open record at this
    instant (and judge it), open the next and hand it to the caller,
    who stamps the boundaries it passes.  None under
    ``telemetry.disable()``.  ``owner`` is the stepping object: another
    one starts a fresh history (its steps are not the last one's)."""
    if not _registry._ENABLED:
        return None
    hist = _timeline.history
    cpu, gc2, builds = _marks(hist)
    now = _now()        # last: the entry is where ``fit.prepare`` opens
    same = id(owner) == hist.owner
    prev = hist.open
    if prev is not None:
        prev._close(now, cpu, gc2, builds)
        if same:
            _judge(hist, prev, now - prev.entry)
    if not same:
        hist.restart(id(owner))
    hist.count += 1
    rec = hist.open = StepRecord(hist.count, now, cpu, gc2, builds)
    _steps.append(rec)      # no lock: atomic, and readers copy the deque
    return rec


def open_step():
    """The calling thread's open step record, for a site inside the
    step to stamp (``EvalMetric._totals``); None when there is none."""
    if not _registry._ENABLED:
        return None
    return _timeline.history.open


def _median(values):
    return sorted(values)[len(values) // 2]


def _judge(hist, rec, interval):
    """Hold the step that just ended against the thread's median."""
    if hist.median is not None and interval > _SLOW_FACTOR * hist.median:
        _slow_step(hist, rec, interval)
    hist.recent.append(rec)
    hist.done += 1
    if hist.done >= _JUDGE_AFTER and (
            hist.median is None or hist.done - hist.refreshed >= _REFRESH):
        hist.median = _median([r.next_entry - r.entry for r in hist.recent])
        hist.refreshed = hist.done


def _slow_step(hist, rec, interval):
    """Count a slow step under the phase that holds most of its excess
    over that phase's own median, note it in the flight recorder and
    log it: the first five of a process, then every hundredth."""
    mine = phases(rec)
    past = [phases(r) for r in hist.recent]
    over = {p: mine[p] - _median([q[p] for q in past]) for p in STEP_PHASES}
    phase = max(STEP_PHASES, key=over.get)
    SLOW_STEPS.labels(phase=phase).inc()
    SLOW_STEP_SECONDS.inc((interval - hist.median) / 1e9)
    seconds = {p: mine[p] / 1e9 for p in STEP_PHASES}
    cpu_s = rec.cpu_ns / 1e9
    from .flight import RECORDER
    RECORDER.note("slow_step", fit_step=rec.step, phase=phase,
                  seconds=interval / 1e9, median_s=hist.median / 1e9,
                  phases=seconds, cpu_s=cpu_s, gc2=rec.gc2,
                  builds=rec.builds)
    nth = next(_slow_seen)
    if nth > 5 and nth % 100:
        return
    rest = ", ".join("%s %.3g" % (p, seconds[p])
                     for p in STEP_PHASES if p != phase)
    log.warning("fit step %d took %.3g s (median %.3g): %s %.3g s, %s; "
                "thread cpu %.3g s; gc2 %d; builds %d", rec.step,
                interval / 1e9, hist.median / 1e9, phase, seconds[phase],
                rest, cpu_s, rec.gc2, rec.builds)


def phases(rec):
    """``{phase: ns}`` of one of :func:`steps`' records (or a closed
    :class:`StepRecord`), in :data:`STEP_PHASES`: the five stretches
    between the stamps the step passed (0 for one it did not) and
    ``outside``, the rest of its interval, so that they sum to
    ``next_entry - entry``."""
    out = {}
    for phase, a, b in _PHASE_STAMPS:
        t0, t1 = rec[a], rec[b]
        out[phase] = t1 - t0 if t0 is not None and t1 is not None else 0
    out["outside"] = rec["next_entry"] - rec["entry"] - sum(out.values())
    return out


def steps(last=None):
    """The step timeline, newest last (the ``last`` newest when given):
    one dict a step with its ordinal on its thread (``step``),
    ``fused``, the :data:`STEP_STAMPS` and, over its interval, the
    thread's CPU ns (``cpu_ns``), full garbage collections (``gc2``)
    and program builds (``builds``).  A step that no entry has ended
    yet says ``open`` and is closed at the time of the call (its
    ``cpu_ns`` is None when another thread asks)."""
    recs = list(_steps)
    if last is not None:
        recs = recs[-last:] if last > 0 else []
    hist = _timeline.history
    now = _now()
    cpu, gc2, builds = _marks(hist)
    out = []
    for rec in recs:
        row = rec.as_dict()
        if row["open"]:
            cpu0, gc0, builds0 = rec._at_entry
            row.update(next_entry=now,
                       cpu_ns=cpu - cpu0 if rec is hist.open else None,
                       gc2=gc2 - gc0, builds=builds - builds0)
        out.append(row)
    return out


def clear_steps():
    """Tests/teardown: empty the timeline and the thread's history."""
    _steps.clear()
    # analyze: ok(threads) a threading.local: every thread writes its own
    _timeline.history = _History()


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
