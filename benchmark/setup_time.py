"""What the set-up readers under ``layer_metrics/`` share: the
program's own seconds of the path a process walks once, from two of its
counters (docs/OBSERVABILITY.md): ``setup_seconds{phase}``, fed by the
set-up spans (``module.bind``, ``module.init_params``,
``module.init_optimizer``) and by the package's import, and
``program_build_seconds{site, phase}``, jax's own trace, lowering and
load seconds laid to the site that caused them.  Counters of the whole
process, read when the metric is read: set-up is over by then.  Both
give None for a program from before the counters, never an error, and
0.0 where the program has them and the phase did not happen.
"""
OUTSIDE = "outside"


def _children(name):
    """The labelled children of registry counter ``name`` as
    ``{label name: value}`` dicts beside their values; None where the
    program has no such counter."""
    from mxnet_tpu import telemetry
    counter = telemetry.REGISTRY.get(name)
    if counter is None:
        return None
    return [(dict(zip(c.label_names, c.label_values)), float(c.value))
            for c in counter.children()]


def phase_seconds(phase):
    """``setup_seconds{phase}``: wall seconds of one set-up phase."""
    rows = _children("setup_seconds")
    if rows is None:
        return None
    return sum(v for labels, v in rows if labels.get("phase") == phase)


def dispatch_build_seconds(phases):
    """``program_build_seconds`` of ``phases`` summed over the DISPATCH
    sites (``fit_step``, ``executor``, ``kvstore_bucket``, ...).  A
    build under a set-up span's name (it has a dot: ``module.bind``)
    lies inside that span's seconds already; one under ``outside`` is
    the caller's own jit (here the harness's and the reference's)."""
    rows = _children("program_build_seconds")
    if rows is None:
        return None
    return sum(v for labels, v in rows
               if labels.get("phase") in phases
               and labels.get("site", OUTSIDE) != OUTSIDE
               and "." not in labels.get("site", ""))
