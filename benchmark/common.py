"""What the drivers share: host spans on the profiler's clock, the
compile counter, counter snapshots of the program, the traced window,
percentiles and the check rows that decide ``correct``."""
import contextlib
import os
import shutil
import time

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Spans:
    """Named host spans of the benchmark's own loop: wall seconds summed
    by name, and a ``jax.profiler.TraceAnnotation`` of the same name so
    that a traced run finds them on the device's clock."""

    def __init__(self):
        self.seconds = {}
        self.count = {}

    @contextlib.contextmanager
    def span(self, name):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) \
            + time.perf_counter() - t0
        self.count[name] = self.count.get(name, 0) + 1


class CompileCounter:
    """Counts XLA backend compilations (jax's own monitoring event), so
    that a run can show that nothing compiled inside its window."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


def reference_model(config):
    """``benchmark/reference/<config["reference"]>.py``."""
    import importlib
    return importlib.import_module("reference." + config["reference"])


def program_counters():
    """The program's own counters the per-layer readers use."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.pallas.dispatch import PALLAS_FALLBACKS, PALLAS_LAUNCHES
    st = mx.aot.stats()
    return {
        "device_dispatches": int(profiler.DEVICE_DISPATCHES.value),
        "pallas_fallbacks": int(sum(c.value
                                    for c in PALLAS_FALLBACKS.children())),
        "pallas_kernels": {c.label_values[0]: int(c.value)
                           for c in PALLAS_LAUNCHES.children()},
        "aot_cache_hits": int(st["cache_hits"]),
        "aot_cache_misses": int(st["cache_misses"]),
    }


@contextlib.contextmanager
def traced(cell, on):
    """A ``jax.profiler`` trace around the block when ``on``; yields a
    dict that holds ``dir`` afterwards.  The python tracer is off: the
    benchmark's own spans are TraceAnnotations."""
    out = {}
    if not on:
        yield out
        return
    import jax
    d = os.path.join(cell.scratch, "trace")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            yield out
    finally:
        jax.profiler.stop_trace()
    out["dir"] = d


def reduce_trace(tdir, host_names, rehearse=False):
    """The trace's summary (benchmark/trace_reduce.py).  A trace with
    no device plane gives None in a rehearsal (the CPU has none); in a
    measured run it is an error, never a run without device metrics."""
    import trace_reduce
    try:
        return trace_reduce.summarize(trace_reduce.find_xplane(tdir),
                                      host_names, window_name="bench_window")
    except RuntimeError:
        if rehearse:
            return None
        raise


def percentile(values, q):
    """The q-quantile (0..1) by linear interpolation; None if empty."""
    vs = sorted(values)
    if not vs:
        return None
    pos = q * (len(vs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (pos - lo)


def check(name, value, limit, ok=None):
    """One number compared beside its limit (``value <= limit`` unless
    ``ok`` says otherwise)."""
    value = float(value)
    if ok is None:
        ok = value == value and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}
