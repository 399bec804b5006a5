"""mx.telemetry registry — the single home for every witness/metric.

The reference MXNet carried its operational counters inside the engine
profiler (src/profiler/profiler.h ProfileCounter); this rebuild grew
the same witnesses ad hoc — two module-level ``TRACE_COUNT`` ints, the
``profiler.DEVICE_DISPATCHES`` counter, ``metric.HOST_SYNCS``, serving's
private ``ServerStats`` — with no single place to read them and no
distributions.  This module is that place: a process-wide, thread-safe
:class:`Registry` of

* :class:`Counter`   — monotonic (dispatch counts, retraces, bytes),
* :class:`Gauge`     — set/inc/dec (queue depth, occupancy, HBM bytes),
* :class:`Histogram` — exponential buckets with p50/p95/p99 snapshots
  (step time, request latency, compile wall time),

each with optional labels.  Everything the framework exports goes
through ``REGISTRY`` (enforced by ``tools/check_telemetry.py``); the
legacy names stay live as aliases (``kvstore_fused.TRACE_COUNT``,
``profiler.DEVICE_DISPATCHES``, ``metric.HOST_SYNCS``) so existing
pins keep working.

Overhead contract: an update is a lock + int add on the host — never
inside traced code (a jax tracer fed to ``observe``/``inc`` raises).
``disable()`` turns non-vital instruments into a single attribute
check; *vital* instruments (the correctness witnesses: retrace and
dispatch counters) always count.
"""
from __future__ import annotations

import bisect
import math
import os
import threading

__all__ = ["Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
           "counter", "gauge", "histogram", "enable", "disable", "enabled",
           "sanitize_name", "exponential_buckets", "hist_quantile",
           "TraceTally", "RetraceSite", "BUILD_SITE", "OUTSIDE"]


class _RetraceSuppress(threading.local):
    """Thread-local mute for retrace accounting: the compiled-program
    registry's lazy AOT re-lowering (programs.py) may re-run a traced
    body whose ``note()`` would otherwise bump the vital zero-retrace
    witnesses tests pin.  Analysis is observation — it must not move
    what it observes."""

    def __init__(self):
        self.on = False


RETRACE_SUPPRESS = _RetraceSuppress()

# what a program build belongs to when nothing of this package is open
# on the thread: the caller's own jits
OUTSIDE = "outside"


class _BuildSite(threading.local):
    """Per thread, the innermost thing open that a program build is
    laid to (``program_build_seconds{site}``, aot/store.py): the
    ``site`` of a :meth:`RetraceSite.timed` call, else the name of an
    open set-up span (``tracing.span(..., seconds_to=...)``), else
    :data:`OUTSIDE`.  jax traces, lowers and loads on the dispatching
    thread and reports each from there, so the listener reads this."""

    def __init__(self):
        self.name = OUTSIDE


BUILD_SITE = _BuildSite()


class TraceTally(threading.local):
    """Per-thread (re)trace tally for exact compile detection at a
    dispatch site. jax traces ON the dispatching thread, so bumping
    this next to the global retrace Counter inside a traced body lets
    the dispatcher attribute a compile to ITS OWN call — a global
    counter delta would misfire when another thread traces
    concurrently (e.g. serving replicas compiling different buckets)."""

    def __init__(self):
        self.count = 0


class RetraceSite:
    """One dispatch site's retrace instrumentation bundle: the global
    witness Counter, the per-thread :class:`TraceTally`, and the
    compile-time attribution. The three hot paths (executor, bucketed
    kvstore, fused fit step) share this one implementation so the
    semantics cannot drift:

    * call :meth:`note` INSIDE the traced body (trace-time host code);
    * dispatch through :meth:`timed` — wall time goes to
      ``dispatch_hist`` (when given), its two clock reads to
      ``timeline`` (a ``tracing.StepRecord``, when given), and calls
      during which THIS thread (re)traced also observe into
      ``compile_hist``
      (trace + compile + first run in one sample, exception or not;
      ``program_build_seconds{site, phase}`` has the same call's
      trace, lowering and load apart, from jax's own events).  For the
      call the thread's :data:`BUILD_SITE` is this site.

    With a ``site`` name, calls that (re)traced a directly-dispatched
    jitted callable also register the program in the compiled-program
    registry (telemetry/programs.py) — compile-path-only, so the
    steady state never touches it.
    """

    def __init__(self, counter, compile_hist=None, site=None):
        self.counter = counter
        self._compile_hist = compile_hist
        self.site = site
        self._tally = TraceTally()

    def note(self):
        if RETRACE_SUPPRESS.on:
            return
        self.counter.inc()
        self._tally.count += 1

    def timed(self, fn, *args, dispatch_hist=None, timeline=None):
        import time
        r0 = self._tally.count
        outer = BUILD_SITE.name
        if self.site is not None:
            # analyze: ok(threads) a threading.local: every thread writes its own
            BUILD_SITE.name = self.site
        t0 = time.perf_counter_ns()
        try:
            return fn(*args)
        finally:
            # analyze: ok(threads) a threading.local: every thread writes its own
            BUILD_SITE.name = outer
            t1 = time.perf_counter_ns()
            if timeline is not None:
                # the step timeline's record of this call's step
                # (telemetry/tracing.py): the same two clock reads
                timeline.dispatch0, timeline.dispatch1 = t0, t1
            dt_ms = (t1 - t0) / 1e6
            if dispatch_hist is not None:
                dispatch_hist.observe(dt_ms)
            if self._compile_hist is not None and self._tally.count > r0:
                self._compile_hist.observe(dt_ms)
            if (self.site is not None and self._tally.count > r0
                    and hasattr(fn, "lower")):
                # jitted callables dispatched directly register the
                # freshly-compiled program; wrapper callables (the
                # bucketed kvstore's _dispatch_inner) register at
                # their own cache-miss sites instead
                from . import programs as _programs
                _programs.record(self.site, fn, args, compile_ms=dt_ms)

_ENABLED = True


def enable():
    """(Re-)enable non-vital instruments (the default state)."""
    global _ENABLED
    _ENABLED = True


def disable():
    """Turn every non-vital instrument into a no-op (one attribute
    check per update). Vital witnesses — retrace/dispatch/sync counters
    that tests pin — keep counting regardless."""
    global _ENABLED
    _ENABLED = False


def enabled():
    return _ENABLED


def sanitize_name(name):
    """Prometheus-legal series name: [a-zA-Z_:][a-zA-Z0-9_:]*.  Legacy
    dotted profiler-counter names (``serving.queue_depth``) map onto
    underscores so both spellings address one series."""
    out = []
    for i, ch in enumerate(str(name)):
        ok = ("a" <= ch <= "z") or ("A" <= ch <= "Z") or ch in "_:" \
            or ("0" <= ch <= "9")
        if i == 0 and "0" <= ch <= "9":
            out.append("_")
        out.append(ch if ok else "_")
    return "".join(out)


def exponential_buckets(start, factor, count):
    """``count`` upper bounds growing by ``factor`` from ``start``."""
    if start <= 0 or factor <= 1 or count < 1:
        raise ValueError("need start > 0, factor > 1, count >= 1")
    bounds, v = [], float(start)
    for _ in range(count):
        bounds.append(v)
        v *= factor
    return tuple(bounds)


# default ms-scale ladder: 0.05 ms .. ~7 min, factor 2
DEFAULT_MS_BUCKETS = exponential_buckets(0.05, 2.0, 23)

# per-metric labeled-series cap: a buggy label loop (request ids, raw
# paths...) must not grow a long-running server's registry without
# bound. Past the cap, ``labels()`` hands back a detached overflow
# child (updates land nowhere visible) and bumps
# ``telemetry_series_dropped``. Module attribute so tests can lower it.
MAX_SERIES = int(os.environ.get("MXNET_TELEMETRY_MAX_SERIES", "1024")
                 or 1024)


def _fmt_label_key(kv):
    names = tuple(sorted(kv))
    return names, tuple(str(kv[k]) for k in names)


class _Metric:
    """Shared shell: identity, lock, label children."""

    kind = "untyped"

    def __init__(self, name, help="", unit="", vital=False,
                 label_names=(), label_values=()):
        self.name = sanitize_name(name)
        self.help = help
        self.unit = unit
        self.vital = vital
        self.label_names = tuple(label_names)
        self.label_values = tuple(label_values)
        self._lock = threading.Lock()
        self._children = {}
        self._overflow = None     # shared detached child past MAX_SERIES

    def _make_child(self, names, values):
        raise NotImplementedError

    def labels(self, **kv):
        """Child instrument for one label set (created on first use).

        Past ``MAX_SERIES`` distinct label sets the call degrades to a
        shared DETACHED child: updates still type-check and never
        raise, but the series is not registered (not exported, not
        snapshotted) and ``telemetry_series_dropped`` counts the
        overflow — cardinality bugs surface as one counter, not an
        OOM."""
        if not kv:
            return self
        names, values = _fmt_label_key(kv)
        with self._lock:
            child = self._children.get((names, values))
            if child is not None:
                return child
            if MAX_SERIES and len(self._children) >= MAX_SERIES:
                if self._overflow is None:
                    self._overflow = self._make_child(names, values)
                child = self._overflow
            else:
                child = self._make_child(names, values)
                self._children[(names, values)] = child
                return child
        # dropped: count outside this metric's lock (SERIES_DROPPED is
        # itself a registry counter with its own lock)
        SERIES_DROPPED.inc()
        return child

    def children(self):
        with self._lock:
            return [self._children[k] for k in sorted(self._children)]

    def _active(self):
        return _ENABLED or self.vital


class Counter(_Metric):
    """Monotonic counter. ``inc`` only; negative deltas raise."""

    kind = "counter"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._value = 0

    def _make_child(self, names, values):
        return Counter(self.name, self.help, self.unit, self.vital,
                       names, values)

    def inc(self, delta=1):
        if not self._active():
            return self._value
        if delta < 0:
            raise ValueError("Counter %s: negative increment %r"
                             % (self.name, delta))
        with self._lock:
            self._value += delta
            return self._value

    @property
    def value(self):
        return self._value

    @property
    def total(self):
        """This counter's value plus its labeled children's (a plain
        read of each: a count, not a snapshot)."""
        return self._value + sum(
            [c._value for c in tuple(self._children.values())])


class Gauge(_Metric):
    """Set/inc/dec instrument for instantaneous values."""

    kind = "gauge"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._value = 0

    def _make_child(self, names, values):
        return Gauge(self.name, self.help, self.unit, self.vital,
                     names, values)

    def set(self, value):
        if not self._active():
            return self._value
        with self._lock:
            self._value = value
            return self._value

    def inc(self, delta=1):
        if not self._active():
            return self._value
        with self._lock:
            self._value += delta
            return self._value

    def dec(self, delta=1):
        if not self._active():
            return self._value
        with self._lock:
            self._value -= delta
            return self._value

    @property
    def value(self):
        return self._value


class Histogram(_Metric):
    """Exponential-bucket histogram with quantile estimates.

    ``observe(v)`` files ``v`` into the bucket with the smallest upper
    bound >= v (overflow bucket past the last bound).  Quantiles come
    from linear interpolation inside the selected bucket, clamped to
    the observed min/max — accurate to one bucket's width (factor 2 by
    default; pass finer ``bounds`` where it matters).
    """

    kind = "histogram"

    def __init__(self, name, help="", unit="", vital=False,
                 label_names=(), label_values=(), bounds=None):
        super().__init__(name, help, unit, vital, label_names, label_values)
        self.bounds = tuple(bounds) if bounds is not None \
            else DEFAULT_MS_BUCKETS
        if any(b <= a for a, b in zip(self.bounds, self.bounds[1:])):
            raise ValueError("histogram bounds must be increasing")
        self._counts = [0] * (len(self.bounds) + 1)
        self._sum = 0.0
        self._count = 0
        self._min = math.inf
        self._max = -math.inf

    def _make_child(self, names, values):
        return Histogram(self.name, self.help, self.unit, self.vital,
                         names, values, bounds=self.bounds)

    def observe(self, value):
        if not self._active():
            return
        value = float(value)   # a jax tracer raises here — by design:
        # registry updates must never happen inside traced code
        with self._lock:
            self._counts[bisect.bisect_left(self.bounds, value)] += 1
            self._sum += value
            self._count += 1
            self._min = min(self._min, value)
            self._max = max(self._max, value)

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def snapshot(self):
        """Immutable view: bucket counts + aggregates + p50/p95/p99."""
        with self._lock:
            snap = {"bounds": self.bounds, "counts": tuple(self._counts),
                    "count": self._count, "sum": self._sum,
                    "min": self._min if self._count else None,
                    "max": self._max if self._count else None}
        for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            snap[key] = hist_quantile(snap, q)
        return snap

    def quantile(self, q, since=None):
        """Estimated q-quantile; ``since`` (an earlier ``snapshot()``)
        restricts the estimate to observations made after it."""
        return hist_quantile(self.snapshot(), q, since=since)


def hist_quantile(snap, q, since=None):
    """Quantile estimate from a histogram snapshot (optionally the
    delta against an earlier snapshot of the same histogram)."""
    if not 0.0 <= q <= 1.0:
        raise ValueError("q must be in [0, 1]")
    counts = list(snap["counts"])
    if since is not None:
        if tuple(since["bounds"]) != tuple(snap["bounds"]):
            raise ValueError("snapshots come from different histograms")
        counts = [c - p for c, p in zip(counts, since["counts"])]
    total = sum(counts)
    if total <= 0:
        return None
    bounds = snap["bounds"]
    lo_clamp = snap.get("min")
    hi_clamp = snap.get("max")
    target = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= target:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else \
                (hi_clamp if hi_clamp is not None else bounds[-1])
            if lo_clamp is not None:
                lo = max(lo, min(lo_clamp, hi))
            if hi_clamp is not None:
                hi = min(hi, max(hi_clamp, lo))
            frac = (target - cum) / c
            return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
        cum += c
    return hi_clamp if hi_clamp is not None else bounds[-1]


class Registry:
    """Name -> instrument map. Registration is get-or-create: asking
    for an existing name returns the existing instrument (so e.g. every
    ``ServerStats`` instance shares one ``serving_admitted`` series);
    asking with a different kind raises."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    def _register(self, cls, name, help, unit, vital, **kw):
        key = sanitize_name(name)
        with self._lock:
            m = self._metrics.get(key)
            if m is not None:
                if type(m) is not cls:
                    raise TypeError(
                        "metric %r already registered as %s, not %s"
                        % (key, type(m).__name__, cls.__name__))
                bounds = kw.get("bounds")
                if bounds is not None and tuple(bounds) != m.bounds:
                    # silently returning the old layout would compute
                    # quantiles at the wrong resolution — fail loudly
                    raise ValueError(
                        "histogram %r already registered with different "
                        "bounds" % key)
                return m
            m = cls(key, help=help, unit=unit, vital=vital, **kw)
            self._metrics[key] = m
            return m

    def counter(self, name, help="", unit="", vital=False):
        return self._register(Counter, name, help, unit, vital)

    def gauge(self, name, help="", unit="", vital=False):
        return self._register(Gauge, name, help, unit, vital)

    def histogram(self, name, help="", unit="", vital=False, bounds=None):
        return self._register(Histogram, name, help, unit, vital,
                              bounds=bounds)

    def get(self, name):
        return self._metrics.get(sanitize_name(name))

    def names(self):
        with self._lock:
            return sorted(self._metrics)

    def collect(self):
        with self._lock:
            return [self._metrics[n] for n in sorted(self._metrics)]

    def unregister(self, name):
        """Drop a series (tests / teardown only)."""
        with self._lock:
            self._metrics.pop(sanitize_name(name), None)

    def snapshot(self):
        """JSON-able flat view: scalars for counters/gauges, compact
        aggregate dicts for histograms (what the flight recorder logs)."""
        out = {}
        for m in self.collect():
            entries = [m] + m.children()
            for e in entries:
                key = e.name
                if e.label_names:
                    key += "{%s}" % ",".join(
                        "%s=%s" % (k, v) for k, v in
                        zip(e.label_names, e.label_values))
                if isinstance(e, Histogram):
                    s = e.snapshot()
                    out[key] = {k: s[k] for k in
                                ("count", "sum", "min", "max",
                                 "p50", "p95", "p99")}
                else:
                    out[key] = e.value
        return out


REGISTRY = Registry()

# overflow witness for the MAX_SERIES cap (module doc above labels());
# vital so a disabled registry still surfaces cardinality bugs
SERIES_DROPPED = REGISTRY.counter(
    "telemetry_series_dropped",
    "label sets dropped by the per-metric MXNET_TELEMETRY_MAX_SERIES "
    "cardinality cap", vital=True)


def counter(name, help="", unit="", vital=False):
    return REGISTRY.counter(name, help, unit, vital)


def gauge(name, help="", unit="", vital=False):
    return REGISTRY.gauge(name, help, unit, vital)


def histogram(name, help="", unit="", vital=False, bounds=None):
    return REGISTRY.histogram(name, help, unit, vital, bounds=bounds)
