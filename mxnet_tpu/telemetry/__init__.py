"""mx.telemetry — unified metrics registry + export layer.

One place to read every operational witness the framework emits
(docs/OBSERVABILITY.md is the glossary):

* :mod:`registry` — thread-safe Counter / Gauge / Histogram registry
  (``telemetry.REGISTRY``); the old scattered witnesses
  (``kvstore_fused.TRACE_COUNT``, ``module.fused_fit.TRACE_COUNT``,
  ``profiler.DEVICE_DISPATCHES``, ``metric.HOST_SYNCS``, serving's
  ``ServerStats``) are live views over it.
* :mod:`export` — Prometheus text exposition: ``GET /metrics`` on a
  running ``ModelServer`` and :func:`start_http_exporter` for training
  jobs.
* :mod:`flight` — ring-buffer flight recorder; JSON-lines dump on
  crash/atexit (``MXNET_TELEMETRY_FLIGHT=<path>``).
* :mod:`memory` — HBM accounting: :func:`memory_snapshot` over
  ``jax.live_arrays``/allocator stats with a params/opt-states/
  residuals/auxs breakdown keyed by the fused-fit donation sets.
* :mod:`chrome` — injects per-step markers + counter tracks into the
  ``mx.profiler`` chrome-trace dump.
* :mod:`tracing` — mx.trace: Dapper-style request/step spans with W3C
  ``traceparent`` propagation, exported into the flight recorder and
  chrome-trace surfaces (near-zero cost when disabled, the default).
* :mod:`programs` — compiled-program registry: per-program FLOPs /
  bytes / peak HBM / compile time from XLA ``cost_analysis()`` /
  ``memory_analysis()`` for every RetraceSite jit site
  (``telemetry.programs()``).
* :mod:`health` — pod-scale straggler detection over the coordination-
  service collectives and a hang watchdog (flight note + faulthandler
  stack dump).
* :mod:`aggregate` — pod-wide metrics aggregation: every rank's
  registry merged into one fleet view over the coordination-service
  collectives (``GET /pod_metrics``; rank-labeled scalars,
  bucket-merged histograms).
* :mod:`sentinel` — declarative SLO rules evaluated on the aggregated
  view (``sentinel.rule("decode_ttft_steps_p99 < 700")``), firing
  once-per-incident alerts, plus the in-launch numerics witness series
  (``grad_norm``/``nonfinite_grads``/``residual_drift``/
  ``loss_zscore``).
* :mod:`moe` — per-expert load of the dropless expert layer
  (``moe_expert_tokens{layer,expert}``, ``moe_tokens_away{layer}``,
  ``moe_expert_load_max_over_mean``), read from the last step's count
  outputs at sync boundaries, never in the step.
* :mod:`dsa` — live score tiles of the sparse indexed attention
  (``dsa_live_block_share``), read from the last step's count output
  when asked, never in the step.
* :mod:`diffusion` — the masked rows of a block-diffusion step
  (``diffusion_masked_row_share``), read the same way.

This package is stdlib-only at import (jax is touched lazily inside
:mod:`memory`/:mod:`programs`), so the registry is safe to import from
anywhere in the framework without cycles.
"""
from . import registry
from .registry import (Counter, Gauge, Histogram, Registry, REGISTRY,
                       TraceTally, RetraceSite, counter, gauge, histogram,
                       enable, disable, enabled, exponential_buckets,
                       hist_quantile, sanitize_name)
from . import export
from .export import generate_text, parse_text, start_http_exporter
from . import flight
from .flight import FlightRecorder, RECORDER
from . import memory
from .memory import memory_snapshot, StepMemoryTracker
from . import chrome
from .chrome import mark_step
from . import tracing
from . import health
from . import programs as _programs_mod
from .health import PodHealthMonitor, Watchdog
from . import aggregate
from .aggregate import PodMetricsAggregator
from . import sentinel
from . import moe
from . import dsa
from . import diffusion


class _ProgramsFacade:
    """``telemetry.programs`` is both the module (attribute access —
    ``telemetry.programs.record``) and the query (``telemetry.
    programs()`` returns the per-program cost table)."""

    def __call__(self, analyze=True, site=None):
        return _programs_mod.programs(analyze=analyze, site=site)

    def __getattr__(self, name):
        return getattr(_programs_mod, name)


programs = _ProgramsFacade()

__all__ = [
    "registry", "export", "flight", "memory", "chrome", "tracing",
    "health", "programs", "aggregate", "sentinel", "moe", "dsa",
    "diffusion",
    "PodMetricsAggregator",
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "counter", "gauge", "histogram", "enable", "disable", "enabled",
    "exponential_buckets", "hist_quantile", "sanitize_name",
    "generate_text", "parse_text", "start_http_exporter",
    "FlightRecorder", "RECORDER", "PodHealthMonitor", "Watchdog",
    "memory_snapshot", "StepMemoryTracker", "mark_step",
    "JIT_COMPILE_MS",
]

# shared compile-time histogram: every dispatch site that detects a
# retrace (executor, fused fit step, bucketed kvstore) observes the
# wall time of the dispatching call here — "first-trace wall time",
# i.e. trace + XLA compile (or cache load) + the first execution of the
# new program in ONE sample; ``program_build_seconds{site, phase}``
# (aot/store.py) has the same builds' trace, lowering and load apart
JIT_COMPILE_MS = REGISTRY.histogram(
    "jit_compile_ms",
    "wall time of dispatches that (re)traced a program "
    "(trace + compile + first run; split by phase in "
    "program_build_seconds)", unit="ms",
    bounds=exponential_buckets(1.0, 2.0, 22))
