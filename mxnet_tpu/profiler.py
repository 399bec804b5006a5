"""mx.profiler — profiling with chrome://tracing output over jax.profiler.

Reference parity: python/mxnet/profiler.py:28-127 (set_config / set_state /
pause / resume / dump / dumps) and the user-definable objects (Domain, Task,
Frame, Counter, Marker) from src/profiler/profiler.h. Two layers:

* **Host events** — eager op dispatch (profile_imperative), executor
  forward/backward spans (profile_symbolic), and user Task/Frame/Counter/
  Marker objects are recorded host-side and dumped as chrome://tracing JSON
  to ``filename``, exactly like the reference's profiler output format
  (src/profiler/profiler.h:87,437). Host spans measure *dispatch* time —
  XLA executes asynchronously, so a span closes when the op is enqueued,
  not when the device finishes (the reference's engine instrumented actual
  kernel completion; XLA hides that from the host).
* **Device timeline** — when ``trace_dir`` is set, start()/stop() also run
  ``jax.profiler.start_trace``/``stop_trace``, producing an xplane/perfetto
  trace with real per-kernel device timing (the TPU-native replacement for
  the reference's per-op GPU stats; view with tensorboard or perfetto).

Env autostart parity: MXNET_PROFILER_AUTOSTART=1 (docs/faq/env_var.md:131).
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time

__all__ = ["set_config", "set_state", "start", "stop", "pause", "resume",
           "dump", "dumps", "state", "Domain", "Task", "Frame", "Counter",
           "Marker", "scope"]

_lock = threading.Lock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": False,
    "trace_dir": None,          # xplane/perfetto device trace output dir
    "continuous_dump": False,
}
_state = "stop"         # 'run' | 'stop' (pause() => 'pause')
_events = []            # chrome trace events
_aggregate = {}         # name -> [count, total_us, min_us, max_us]
_epoch = time.perf_counter()
_device_trace_on = False

# fast-path flags consulted by the dispatch/executor hooks
IMPERATIVE_ON = False
SYMBOLIC_ON = False


def _now_us():
    return (time.perf_counter() - _epoch) * 1e6


def _refresh_flags():
    global IMPERATIVE_ON, SYMBOLIC_ON
    running = _state == "run"
    IMPERATIVE_ON = running and (_config["profile_imperative"]
                                 or _config["profile_all"])
    SYMBOLIC_ON = running and (_config["profile_symbolic"]
                               or _config["profile_all"])


def set_config(**kwargs):
    """Configure the profiler (reference profiler.py set_config). Accepts
    the reference kwargs plus ``trace_dir`` for the device xplane trace.

    Setting ``trace_dir`` while the profiler is already running (or
    paused — pause never ends the device trace) starts the device
    xplane trace IMMEDIATELY; it used to silently wait for the next
    stop/start cycle."""
    import logging
    global _device_trace_on
    with _lock:
        # _config is read by every profiled dispatch on other threads;
        # writes hold the module lock (mx.analyze threads pass)
        for k, v in kwargs.items():
            if k not in _config:
                # reference-valid options we don't distinguish (e.g.
                # profile_process='worker'|'server') are accepted with
                # a note
                logging.warning("profiler.set_config: option '%s' is "
                                "accepted but has no effect here", k)
                continue
            _config[k] = v
    _refresh_flags()
    if _state in ("run", "pause") and _config["trace_dir"]:
        if not _device_trace_on:
            import jax
            jax.profiler.start_trace(_config["trace_dir"])
            _device_trace_on = True
        elif "trace_dir" in kwargs:
            logging.warning(
                "profiler.set_config: a device trace is already running; "
                "the new trace_dir takes effect at the next stop/start "
                "cycle")


def state():
    return _state


def set_state(new_state="stop"):
    """'run' or 'stop' (reference profiler.py set_state)."""
    global _state, _device_trace_on
    if new_state not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if new_state == _state:
        return
    _state = new_state
    _refresh_flags()
    if new_state == "run" and _config["trace_dir"] and not _device_trace_on:
        import jax
        jax.profiler.start_trace(_config["trace_dir"])
        _device_trace_on = True
    elif new_state == "stop":
        if _device_trace_on:
            import jax
            jax.profiler.stop_trace()
            _device_trace_on = False
        if _config["continuous_dump"]:
            dump(finished=False)


def start():
    set_state("run")


def stop():
    set_state("stop")


def pause():
    """Suspend host-event recording without ending the device trace."""
    global _state
    if _state == "run":
        _state = "pause"
        _refresh_flags()


def resume():
    global _state
    if _state == "pause":
        _state = "run"
        _refresh_flags()


def add_event(name, cat, ts_us, dur_us, tid=None, args=None, ph="X"):
    if _state != "run":
        # nothing is recorded while stopped/paused — user Counter/Task
        # objects may outlive the profiled window without leaking events
        return
    ev = {"name": name, "cat": cat, "ph": ph, "ts": ts_us,
          "pid": os.getpid(),
          "tid": tid if tid is not None else threading.get_ident() & 0xFFFF}
    if ph == "X":
        ev["dur"] = dur_us
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)
        if _config["aggregate_stats"] and ph == "X":
            st = _aggregate.setdefault(name, [0, 0.0, float("inf"), 0.0])
            st[0] += 1
            st[1] += dur_us
            st[2] = min(st[2], dur_us)
            st[3] = max(st[3], dur_us)


class scope:
    """Context manager recording one chrome-trace span."""

    def __init__(self, name, cat="operator"):
        self.name, self.cat = name, cat

    def __enter__(self):
        self._t0 = _now_us()
        return self

    def __exit__(self, *exc):
        add_event(self.name, self.cat, self._t0, _now_us() - self._t0)
        return False


def record_op(name, t0_us, t1_us):
    add_event(name, "operator", t0_us, t1_us - t0_us)


def dump(finished=True):
    """Write the chrome-trace JSON to ``filename`` (reference dump()).

    Non-empty dumps also carry closing counter-track samples of every
    mx.telemetry registry series (telemetry/chrome.py), so host metrics
    line up with the trace without a separate scrape."""
    with _lock:
        events = list(_events)
        if finished:
            _events.clear()
    if events:
        try:
            from .telemetry import chrome as _tchrome
            events.extend(_tchrome.dump_events())
        except Exception:
            pass
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    with open(_config["filename"], "w") as f:
        json.dump(doc, f)


def dumps(reset=False):
    """Return the aggregate-stats table as a string (reference dumps();
    requires set_config(aggregate_stats=True))."""
    with _lock:
        rows = sorted(_aggregate.items(), key=lambda kv: -kv[1][1])
        if reset:
            _aggregate.clear()
    lines = ["%-40s %8s %12s %12s %12s %12s" %
             ("Name", "Calls", "Total(us)", "Avg(us)", "Min(us)", "Max(us)")]
    for name, (cnt, tot, mn, mx) in rows:
        lines.append("%-40s %8d %12.1f %12.1f %12.1f %12.1f" %
                     (name[:40], cnt, tot, tot / max(cnt, 1), mn, mx))
    return "\n".join(lines)


# ----------------------------------------------------------------------
# user-definable profiler objects (reference src/profiler/profiler.h
# ProfileTask/ProfileFrame/ProfileCounter/ProfileMarker)
# ----------------------------------------------------------------------
class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(name, self)

    def new_frame(self, name):
        return Frame(name, self)

    def new_counter(self, name, value=None, vital=False):
        """``vital=True`` marks a pinned correctness witness: its
        registry series keeps counting through ``telemetry.disable()``
        (which otherwise no-ops every instrument)."""
        return Counter(self, name, value, vital=vital)

    def new_marker(self, name):
        return Marker(self, name)


class _Span:
    _cat = "task"

    def __init__(self, name, domain=None):
        self.name = name
        self.domain = domain
        self._t0 = None

    def start(self):
        self._t0 = _now_us()

    def stop(self):
        if self._t0 is None:
            return
        cat = self.domain.name if self.domain else self._cat
        add_event(self.name, cat, self._t0, _now_us() - self._t0)
        self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class Task(_Span):
    _cat = "task"


class Frame(_Span):
    _cat = "frame"


class Counter:
    """Thread-safe: serving replicas and user threads may bump the same
    counter concurrently (reference ProfileCounter is atomic too,
    src/profiler/profiler.h).

    Storage lives in the mx.telemetry registry (a Gauge — profiler
    counters allow set/decrement): ``Domain.new_counter(name)`` is now
    a live VIEW over ``telemetry.REGISTRY`` series ``name`` (dots map
    to underscores), so ``DEVICE_DISPATCHES``/``HOST_SYNCS``/the
    kvstore counters show up in ``GET /metrics`` and the flight
    recorder while ``.value`` and chrome-trace emission behave exactly
    as before. Two Counters with one name share one series."""

    def __init__(self, domain, name, value=None, vital=False):
        self.domain, self.name = domain, name
        self._vlock = threading.Lock()
        from . import telemetry as _tm
        self._metric = _tm.REGISTRY.gauge(
            name, "profiler counter (domain %s)"
            % (domain.name if domain else "counter"), vital=vital)
        if value is not None:
            self._emit(self._metric.set(value))

    @property
    def value(self):
        return self._metric.value

    def _emit(self, value):
        add_event(self.name, self.domain.name if self.domain else "counter",
                  _now_us(), 0, ph="C", args={self.name: value})

    # _emit stays inside the lock so trace samples record in value order
    # (an emit outside would let a stale value land last in the trace);
    # add_event's module lock never takes _vlock, so no ordering cycle
    def set_value(self, value):
        with self._vlock:
            self._emit(self._metric.set(value))

    def increment(self, delta=1):
        with self._vlock:
            self._emit(self._metric.inc(delta))

    def decrement(self, delta=1):
        with self._vlock:
            self._emit(self._metric.dec(delta))

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


# global device-launch witness (docs/TRAINING.md): every compiled-program
# dispatch on the training hot path increments this counter — executor
# fwd / fused fwd+bwd launches, kvstore bucket programs, and the fused
# fit-step program. benchmark/layer_metrics/dispatches_per_step.train.py
# reads its delta over the window's steps, independent of wall clock.
DEVICE_DISPATCHES = Domain("device").new_counter("device_dispatches",
                                                 vital=True)


class Marker:
    def __init__(self, domain, name):
        self.domain, self.name = domain, name

    def mark(self, scope="process"):
        add_event(self.name, self.domain.name if self.domain else "marker",
                  _now_us(), 0, ph="i",
                  args={"scope": scope})


if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1":
    set_state("run")
    atexit.register(dump)
