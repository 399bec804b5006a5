#!/usr/bin/env python3
"""Where one run's ``setup_s`` went, by hand (no cell reads this).

    python3 benchmark/setup_breakdown.py --workload <cell> --seed <n> \
        [--seconds <s>] [--trace <0|1>] [--rehearse]

Runs ``benchmark/run.py``'s ``main`` in this process with the same
arguments and edits none of its files: it lays timers round what the
harness imports and calls (``import jax``, ``jax.devices()``, the
driver's ``make_pool``, the reference's ``first_steps`` and
``init_params``, ``models.get_symbol``, the host pool's ``nd.array``,
the checked steps' norms and comparison, the driver's own ``input`` /
``fit_step`` / ``readback`` spans) and listens to jax's build events
beside the program's listener.  After the run's own result line it
prints one more line, ``{"setup_breakdown": {...}}``: the run's
``setup_s``, the program's set-up counters (``setup_seconds{phase}``,
``program_build_seconds{site, phase}``, ``program_builds{site}``), and
under ``named`` every second of the set-up under ONE name: the
program's phases, the builds under ``site="outside"`` that fell outside
the reference (which ``setup_s`` leaves out), and each timer's seconds
less what an earlier name already holds; ``unnamed_s`` is the rest
(PERF.md section 5 has the table this fills).
"""
import builtins
import contextlib
import json
import sys
import time

import run as harness                   # benchmark/run.py: T_PROCESS is now

BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "load",
}
# time.time() - perf_counter(): jax stamps its events with the former
WALL_OFFSET = time.time() - time.perf_counter()

timed = []              # (name, t0, t1) on perf_counter's clock
builds = []             # (site, phase, t0, t1), the same clock
captured = {}           # the driver's own result


def _build_site():
    reg = sys.modules.get("mxnet_tpu.telemetry.registry")
    site = getattr(reg, "BUILD_SITE", None)
    return site.name if site is not None else "outside"


def _on_time_span(event, start, end, **kw):
    phase = BUILD_EVENTS.get(event)
    if phase is not None:
        builds.append((_build_site(), phase, start - WALL_OFFSET,
                       end - WALL_OFFSET))


def _timing(name, fn):
    """``fn`` with each call's interval noted under ``name``."""
    def run(*args, **kwargs):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            timed.append((name, t0, time.perf_counter()))
    return run


def _wrap(owner, attr, name):
    setattr(owner, attr, _timing(name, getattr(owner, attr)))


def _after_jax(jax):
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    _wrap(jax.profiler, "start_trace", "start_trace")


def _after_mxnet_tpu(mx):
    _wrap(mx.models, "get_symbol", "get_symbol")
    _wrap(mx.nd, "array", "host_pool")


def _time_first_imports(after):
    """The first ``import <name>`` statement of each name of ``after``
    timed (its dependencies with it), then ``after[name](module)``."""
    plain = builtins.__import__
    waiting = dict(after)

    def timed_import(name, *args, **kwargs):
        top = name.partition(".")[0]
        if top not in waiting or top in sys.modules:
            return plain(name, *args, **kwargs)
        hook = waiting.pop(top)
        t0 = time.perf_counter()
        mod = plain(name, *args, **kwargs)
        timed.append(("import " + top, t0, time.perf_counter()))
        hook(sys.modules[top])
        return mod

    builtins.__import__ = timed_import


def _wrap_driver(load_module):
    """``run.load_module`` that hands back the driver with timers on
    what its ``run`` calls."""
    def load(kind, name):
        mod = load_module(kind, name)
        if kind != "drivers" or not hasattr(mod, "ref_train"):
            return mod
        _wrap(mod, "make_pool", "make_pool")
        _wrap(mod, "first_gradient_norms", "checked_norms")
        _wrap(mod, "checked_loss", "comparison")
        _wrap(mod, "compare", "comparison")
        _wrap(mod.ref_train, "first_steps", "reference")
        _wrap(mod.ref_train, "init_params", "seeded_weights")
        _wrap(mod.ref_train, "delta_norm", "checked_norms")
        spans = mod.common.Spans
        plain_span = spans.span

        @contextlib.contextmanager
        def span(self, name):
            t0 = time.perf_counter()
            with plain_span(self, name):
                yield
            timed.append(("step." + name, t0, time.perf_counter()))

        spans.span = span
        plain_run = mod.run

        def run(cell):
            res = plain_run(cell)
            captured.update(res["end_to_end"], **{
                "reference_seconds": res["notes"]["reference_seconds"]})
            return res

        mod.run = run
        return mod
    return load


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(xs, ys):
    """Seconds the unions of two interval lists share."""
    return sum(max(0.0, min(b, d) - max(a, c))
               for a, b in _union(xs) for c, d in _union(ys))


def _seconds(intervals):
    return sum(b - a for a, b in _union(intervals))


def breakdown():
    """The dict of the extra line, from what the timers noted."""
    from mxnet_tpu import telemetry
    snap = {k: v for k, v in telemetry.REGISTRY.snapshot().items()
            if k.startswith(("setup_seconds{", "program_build_seconds{",
                             "program_builds{"))}
    import setup_time
    named = {
        "import_s": setup_time.phase_seconds("import"),
        "bind_s": setup_time.phase_seconds("bind"),
        "init_params_s": setup_time.phase_seconds("init_params"),
        "init_optimizer_s": setup_time.phase_seconds("init_optimizer"),
        "fit_build_s": setup_time.phase_seconds("fit_build"),
        "program_trace_s":
            setup_time.dispatch_build_seconds(("trace", "lower")),
        "program_load_s": setup_time.dispatch_build_seconds(("load",)),
    }
    window_t0 = harness.T_PROCESS + captured["setup_s"] \
        + captured["reference_seconds"]

    def noted(name):
        return [(a, b) for n, a, b in timed
                if n.startswith(name) and b <= window_t0]

    def built(pick, phases=("trace", "lower", "load")):
        return [(a, b) for site, phase, a, b in builds
                if pick(site) and phase in phases and b <= window_t0]

    def outside(site):
        return site == "outside"

    # every second under the first name of this order that holds it;
    # the reference is no part of setup_s, the package's import times
    # itself (import_s), the dispatch sites' builds are the two metrics
    held = noted("reference") + noted("import mxnet_tpu") + built(
        lambda site: site != "outside" and "." not in site)
    outside_by_phase = {}
    for name, spans in [
            ("outside_trace", built(outside, ("trace",))),
            ("outside_lower", built(outside, ("lower",))),
            ("outside_load", built(outside, ("load",))),
            ("import_jax_s", noted("import jax")),
            ("backend_init_s", noted("backend_init")),
            ("make_pool_s", noted("make_pool")),
            ("get_symbol_s", noted("get_symbol")),
            ("seeded_weights_s", noted("seeded_weights")),
            ("host_pool_s", noted("host_pool")),
            ("first_steps_s", noted("step.")),
            ("checked_norms_s", noted("checked_norms")),
            ("comparison_s", noted("comparison")),
            ("start_trace_s", noted("start_trace"))]:
        mine = _seconds(spans) - _overlap(spans, held)
        held = held + spans
        if name.startswith("outside_"):
            outside_by_phase[name[len("outside_"):]] = mine
        else:
            named[name] = mine
    named["outside_builds_s"] = sum(outside_by_phase.values())
    unnamed = captured["setup_s"] - sum(named.values())
    return {"setup_s": captured["setup_s"],
            "reference_seconds": captured["reference_seconds"],
            "named": named, "outside_builds_by_phase": outside_by_phase,
            "first_steps": len(noted("step.fit_step")),
            "first_steps_wall_s": _seconds(noted("step.")),
            "unnamed_s": unnamed,
            "unnamed_share": unnamed / captured["setup_s"],
            "counters": snap}


def main(argv=None):
    _time_first_imports({"jax": _after_jax, "mxnet_tpu": _after_mxnet_tpu})
    harness.load_module = _wrap_driver(harness.load_module)
    _wrap(harness, "device_info", "backend_init")
    rc = harness.main(argv)
    print(json.dumps({"setup_breakdown": breakdown()}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
