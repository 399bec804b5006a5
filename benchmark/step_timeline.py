"""What the readers of the program's step timeline share
(``mxnet_tpu.telemetry.tracing.steps``: one record a fit step, stamped
on the host's monotonic clock at the boundaries the step passed;
docs/OBSERVABILITY.md, "The step timeline").

The window's steps are the last ``facts["steps"]`` records: the harness
reads its metrics right after the window, so nothing has stepped since.
The window's last step is still OPEN then (no next entry has ended it,
and the program closes it at the time of the call): what depends on a
step's END leaves it out wherever the window holds a closed one.

The device's gap between two fit-step programs is cut in four on clocks
that never cross::

    device idle [n -> n+1]      (device clock)
      = launch lead             the rest: wake-up of the wait, and the
                                jit call up to the program's start
      + transfer1 - wait1       the two scalar copies      (host clock)
      + next_entry - transfer1  reset, the caller's loop   (host clock)
      + dispatch0 - entry       fit.prepare of step n+1    (host clock)

Each term is a difference taken inside ONE clock, so the lead of the
device's clock over the host's (which ``readback_idle_ms.train``
depends on) cancels.

Every reader gives None for a program without the timeline (the parent
of the PR that brought it), for a serving cell's facts and for an empty
window: no number, not 0.

    python3 benchmark/step_timeline.py [trace.xplane.pb]

prints, from a traced run's own host plane, the two bounds on that
lead, the launch lead by the trace's annotations alone, and for the
window's latest step whether its program started late, ran long or
was waited for long (ms over the window's medians).
"""
import json
import os
import sys

import program_trace
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

FIT_PROGRAM = "jit_step"        # the fused fit step on ``XLA Modules``


def records(facts):
    """The window's step records, oldest first; None where the program
    keeps no timeline or the facts are not a training window's."""
    if facts.get("kind") != "train" or not facts.get("steps"):
        return None
    try:
        from mxnet_tpu.telemetry import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "steps"):
        return None
    return tracing.steps(last=int(facts["steps"])) or None


def _mean_ms(ns):
    return sum(ns) / len(ns) / 1e6 if ns else None


def _ended(recs):
    """The records an entry has ended; the open one only where it is
    all the window has."""
    closed = [r for r in recs if not r.get("open")]
    return closed or list(recs)


def transfer_ms(recs):
    """Mean of ``transfer1 - wait1`` over the steps that were read
    back, in ms."""
    return _mean_ms([r["transfer1"] - r["wait1"] for r in recs
                     if r["wait1"] is not None])


def outside_ms(recs):
    """Mean of ``next_entry - transfer1`` (``next_entry - rebind1`` in a
    step that was not read back; the whole interval of a step that left
    the fused path), in ms."""
    out = []
    for r in _ended(recs):
        last = r["transfer1"] if r["transfer1"] is not None else r["rebind1"]
        out.append(r["next_entry"] - (r["entry"] if last is None else last))
    return _mean_ms(out)


def interval_means_ms(recs):
    """``{"prepare": ..., "dispatch": ..., "rebind": ...}``: the means of
    ``dispatch0 - entry``, ``dispatch1 - dispatch0`` and
    ``rebind1 - dispatch1`` over the fused steps, in ms: the host
    clock's account of the spans ``fit_prepare_ms.train`` and
    ``fit_dispatch_ms.train`` read on the profiler's."""
    fused = [r for r in recs if r["rebind1"] is not None]
    return {"prepare": _mean_ms([r["dispatch0"] - r["entry"] for r in fused]),
            "dispatch": _mean_ms([r["dispatch1"] - r["dispatch0"]
                                  for r in fused]),
            "rebind": _mean_ms([r["rebind1"] - r["dispatch1"]
                                for r in fused])}


def longest_over_median(recs):
    """The longest entry-to-entry interval over the median one."""
    xs = sorted(r["next_entry"] - r["entry"] for r in _ended(recs))
    n = len(xs)
    median = xs[n // 2] if n % 2 else (xs[n // 2 - 1] + xs[n // 2]) / 2
    return xs[-1] / median if median > 0 else None


def idle_between(modules):
    """For each pair of consecutive fit-step programs of ``modules``
    (events of the device's ``XLA Modules`` line, by start): the ns
    between the first's end and the second's start in which the device
    ran no other program."""
    steps = [m for m in modules if m["name"].startswith(FIT_PROGRAM)]
    others = [(m["name"], m["start_ns"], m["dur_ns"]) for m in modules
              if not m["name"].startswith(FIT_PROGRAM)]
    out = []
    for a, b in zip(steps, steps[1:]):
        t0, t1 = a["start_ns"] + a["dur_ns"], b["start_ns"]
        out.append((t1 - t0) - trace_reduce.busy_ns(
            trace_reduce.clip(others, t0, t1)))
    return out


def launch_lead_ms(recs, modules):
    """Mean over the pairs of consecutive steps whose first was read
    back of: the device's idle time between the two fit-step programs
    (device clock) less ``dispatch0[n+1] - wait1[n]`` (host clock), in
    ms.  None without a pair, or where the window's fit-step programs
    are not one a record (then nothing says which is which)."""
    steps = [m for m in modules if m["name"].startswith(FIT_PROGRAM)]
    if len(steps) != len(recs) or len(recs) < 2:
        return None
    out = []
    for idle, a, b in zip(idle_between(modules), recs, recs[1:]):
        if a["wait1"] is not None and b["dispatch0"] is not None:
            out.append(idle - (b["dispatch0"] - a["wait1"]))
    return _mean_ms(out)


def read(facts, of):
    """``of(records)`` for a reader under ``layer_metrics/``."""
    recs = records(facts)
    return None if recs is None else of(recs)


def read_launch_lead(facts):
    recs = records(facts)
    tr = program_trace.train_trace(facts) if recs else None
    if tr is None or not tr.modules:
        return None
    return launch_lead_ms(recs, tr.modules)


# ----------------------------------------------------------------------
# by hand: the clock lead's bounds from a trace's own host plane
# ----------------------------------------------------------------------
def lead_bounds(modules, waits, dispatches):
    """``(lower, upper)`` in ns on how far the device's clock leads the
    host's in one trace.  ``modules`` are the fit-step programs on the
    device's line, ``waits`` the ``metric.wait`` spans and ``dispatches``
    the ``fit.fused_dispatch`` spans on the host plane (``(name, start,
    dur)``), step for step.  A program ends before the wait for it
    returns (``dev_end - lead <= wait_end``: the lead is at least the
    largest ``dev_end - wait_end``) and starts after its jit call began
    (at most the smallest ``dev_start - dispatch_start``)."""
    lower = max(m["start_ns"] + m["dur_ns"] - (w[1] + w[2])
                for m, w in zip(modules, waits))
    upper = min(m["start_ns"] - d[1] for m, d in zip(modules, dispatches))
    return lower, upper


def late_step(modules, waits, dispatches):
    """Where the window's latest step was late, in ns over the window's
    medians: ``{"step": n, "head": ..., "ran": ..., "wake": ...}`` for
    the step whose three stretches exceed their medians by most.
    ``head`` is ``dev_start - dispatch_start`` (the program started late
    after its jit call began), ``ran`` the program's own duration,
    ``wake`` is ``wait_end - dev_end`` (the wait returned late after the
    program ended).  Head and wake cross the two clocks, but a stretch
    LESS ITS MEDIAN does not: the lead cancels."""
    def median(xs):
        return sorted(xs)[len(xs) // 2]
    head = [m["start_ns"] - d[1] for m, d in zip(modules, dispatches)]
    ran = [m["dur_ns"] for m in modules]
    wake = [w[1] + w[2] - (m["start_ns"] + m["dur_ns"])
            for m, w in zip(modules, waits)]
    over = [[x - median(xs) for x in xs] for xs in (head, ran, wake)]
    n = max(range(len(modules)), key=lambda i: sum(o[i] for o in over))
    return {"step": n, "head": over[0][n], "ran": over[1][n],
            "wake": over[2][n]}


def main(argv):
    path = argv[1] if len(argv) > 1 \
        else program_trace.find_trace(not_before=0.0)
    if path is None:
        raise SystemExit("step_timeline: no trace under %s"
                         % os.path.join(ROOT, ".bench_scratch"))
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    tr = program_trace.Trace(path)
    host = trace_reduce.clip(trace_reduce.read_events(
        path, ("metric.wait", "fit.fused_dispatch"))["host"], tr.t0, tr.t1)
    waits = [h for h in host if h[0] == "metric.wait"]
    dispatches = [h for h in host if h[0] == "fit.fused_dispatch"]
    steps = [m for m in tr.modules if m["name"].startswith(FIT_PROGRAM)]
    out = {"trace": path, "steps": len(steps),
           "metric_wait_spans": len(waits),
           "fused_dispatch_spans": len(dispatches)}
    if steps and len(steps) == len(waits) == len(dispatches):
        lower, upper = lead_bounds(steps, waits, dispatches)
        # the launch lead by the trace's annotations alone: the same
        # two differences, each inside one of the trace's clocks
        lead = [idle - (d[1] - (w[1] + w[2])) for idle, w, d in zip(
            idle_between(tr.modules), waits, dispatches[1:])]
        late = late_step(steps, waits, dispatches)
        out.update(latest_step={k: v if k == "step" else v / 1e6
                                for k, v in late.items()},
                   clock_lead_lower_ms=lower / 1e6,
                   clock_lead_upper_ms=upper / 1e6,
                   bounds_cross=bool(lower > upper),
                   device_gap_ms=_mean_ms(idle_between(tr.modules)),
                   launch_lead_ms_by_annotations=_mean_ms(lead))
    else:
        out["note"] = ("no bounds: the window needs one metric.wait and "
                       "one fit.fused_dispatch span a fit-step program "
                       "(a program without metric.wait has none)")
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
