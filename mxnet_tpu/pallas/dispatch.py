"""Kernel-impl selection — one ``auto|<kernel>|xla`` contract.

``MXNET_PAGED_ATTN_IMPL`` (paged decode/prefill), ``MXNET_LN_IMPL``
(LayerNorm) and ``MXNET_Q2BIT_IMPL`` (kvstore 2-bit quantize) all
route through :func:`choose_impl`, so the knobs cannot drift (the
training kernels have none: they choose from :func:`_compiles_here` and
their shapes, and count a refusal the same way):

* ``auto`` (default) — the kernel when the backend/geometry supports
  it profitably, the XLA reference path otherwise (the fallback bumps
  ``pallas_fallbacks{reason}``);
* ``xla`` — force the reference path (A/B runs);
* ``<kernel>`` — require the kernel; raise instead of silently
  measuring the wrong path when it cannot run.  The paged/quant
  kernels are *forceable anywhere*: forced onto a backend that cannot
  compile them they run ``interpret=True`` — the tier-1/CI testing
  convention (docs/KERNELS.md).  That is the ONLY way a kernel ever
  runs interpreted: :func:`choose_impl` answers ``"interpret"`` for a
  forced knob and nothing else, so ``auto`` is either the compiled
  kernel or the XLA path, never a silent emulation.

The decisions here run at TRACE time (inside the enclosing jitted
program's Python), so they are per-program-construction, not
per-launch.
"""
import os

from .. import telemetry as _telemetry
from ..telemetry.registry import RETRACE_SUPPRESS

# trace-time witnesses (docs/OBSERVABILITY.md glossary).  "Launches"
# counts kernel instantiations built into traced programs: steady-state
# dispatches ride the enclosing compiled program (decode_dispatches /
# dispatches_per_step witness those), so a warm serving loop adds zero.
PALLAS_LAUNCHES = _telemetry.REGISTRY.counter(
    "pallas_kernel_launches",
    "pallas kernel instantiations built into traced programs, "
    "labeled by `kernel`", vital=True)
PALLAS_FALLBACKS = _telemetry.REGISTRY.counter(
    "pallas_fallbacks",
    "auto-mode kernel selections that fell back to the XLA reference "
    "path, labeled by `reason`")
FLASH_BLOCKS_WALKED = _telemetry.REGISTRY.counter(
    "flash_blocks_walked",
    "512 x 512 score blocks the banded flash kernels built into traced "
    "programs execute a step, labeled by `kernel`")
FLASH_BLOCKS_CAUSAL = _telemetry.REGISTRY.counter(
    "flash_blocks_causal",
    "512 x 512 score blocks the causal kernels would execute in the "
    "banded ones' place, labeled by `kernel`")
PALLAS_RETRACES = _telemetry.REGISTRY.counter(
    "pallas_kernel_retraces",
    "pallas kernel (re)builds — nonzero growth after warmup means a "
    "kernel is being reconstructed per call", vital=True)


def choose_impl(env_var, impl, kernel, supported, why, *,
                force_supported=None, fallback_reason="unsupported",
                count=True):
    """Shared ``auto|<kernel>|xla`` selection for a kernel knob.

    ``impl`` is the knob's raw value — the CALLER reads it with a
    literal env-var name (``os.environ.get("MXNET_X_IMPL", "auto")``)
    so the envknobs analyze pass can see the read site; ``env_var`` is
    only for error messages.  Returns False for the XLA path, else how
    the custom kernel runs: ``"compiled"``, or ``"interpret"`` when the
    knob forced it where ``supported`` is false.  Raises ``ValueError``
    for an unknown value, and when the kernel is forced
    (``<env_var>=<kernel>``) but cannot run — never silently measure
    the wrong path.  ``supported`` gates the
    ``auto`` choice; ``force_supported`` (default: same as
    ``supported``) gates the forced one — kernels that can run
    interpreted pass ``force_supported=True`` since they then run on
    any backend when explicitly requested.  ``count=False`` suppresses
    the fallback counter for observer-only calls (stats polling
    must not inflate the per-trace witness).
    """
    if impl == "xla":
        return False
    if impl not in ("auto", kernel):
        raise ValueError("%s=%s; use auto|%s|xla" % (env_var, impl, kernel))
    if impl == kernel:
        ok = supported if force_supported is None else force_supported
        if not ok:
            raise ValueError("%s=%s but the kernel cannot run here (%s)"
                             % (env_var, impl, why))
        return "compiled" if supported else "interpret"
    if not supported:
        if count and not RETRACE_SUPPRESS.on:   # not a registry re-lower
            PALLAS_FALLBACKS.labels(reason=fallback_reason).inc()
        return False
    return "compiled"


def _compiles_here():
    """Where ``auto`` may take a compiled Pallas kernel: on a TPU, in a
    program that runs on ONE device.  Mosaic kernels cannot be
    partitioned automatically (jax refuses them under GSPMD without a
    ``shard_map``), so under a selected mesh (``mx.sharding.set_mesh``,
    ``fleet.tp_mesh``) or the implicit 'dp' mesh of a multi-context
    bind the XLA path, which GSPMD does partition, is the one that
    runs.  Returns ``(ok, why, fallback_reason)``."""
    import jax
    from .. import sharding
    from ..parallel.mesh import current_mesh
    backend = jax.default_backend()
    if backend != "tpu":
        return False, "backend=%s" % backend, "backend"
    mesh = sharding.get_mesh() or current_mesh()
    if mesh is not None and mesh.devices.size > 1:
        return (False, "the program is partitioned over mesh %s and "
                "Mosaic kernels need a shard_map for that"
                % dict(mesh.shape), "mesh")
    return True, "", None


def use_paged_pallas(count=True):
    """Trace-time paged-attention impl decision shared by the decode
    and prefill ops (ops/nn.py) and the engine's stats reporting.
    ``auto`` prefers the Pallas kernels where they compile
    (:func:`_compiles_here`: decode there is bandwidth-bound on exactly
    the gather traffic they remove) and the XLA gather path elsewhere;
    ``MXNET_PAGED_ATTN_IMPL=pallas`` forces the kernels — off a TPU in
    interpret mode.  ``count=False`` suppresses the fallback counter
    for observer-only calls (stats)."""
    ok, why, reason = _compiles_here()
    return choose_impl(
        "MXNET_PAGED_ATTN_IMPL",
        os.environ.get("MXNET_PAGED_ATTN_IMPL", "auto"), "pallas", ok,
        why=why + "; auto uses the compiled kernels only there — force "
            "'pallas' to run them in interpret mode off a TPU",
        force_supported=reason != "mesh", fallback_reason=reason,
        count=count)


def paged_attn_impl():
    """The active paged-attention implementation name ('pallas' or
    'xla') for stats() — no counter side effects."""
    return "pallas" if use_paged_pallas(count=False) else "xla"


def use_layernorm_pallas(axis_last=True):
    """Impl decision for the fused LayerNorm (+residual) kernel
    (``MXNET_LN_IMPL``): auto = kernel where it compiles
    (:func:`_compiles_here`) when normalizing the LAST axis (the
    transformer symbol path), forceable off a TPU via interpret mode —
    forcing with a non-last axis still raises, since the kernel's
    row-tile layout only covers ``axis=-1``."""
    ok, why, reason = _compiles_here()
    return choose_impl(
        "MXNET_LN_IMPL",
        os.environ.get("MXNET_LN_IMPL", "auto"), "pallas",
        axis_last and ok,
        why="%s, axis_last=%s; auto uses the compiled kernel only "
            "there with axis=-1 — force 'pallas' to run it in "
            "interpret mode off a TPU (axis=-1 still required)"
            % (why, axis_last),
        force_supported=axis_last and reason != "mesh",
        fallback_reason=reason or "axis")


def use_q2bit_pallas():
    """Impl decision for the fused 2-bit quantize kernel on the
    kvstore bucket path (``MXNET_Q2BIT_IMPL``): same semantics as the
    paged knob."""
    ok, why, reason = _compiles_here()
    return choose_impl(
        "MXNET_Q2BIT_IMPL",
        os.environ.get("MXNET_Q2BIT_IMPL", "auto"), "pallas", ok,
        why=why + "; auto uses the compiled kernel only there — force "
            "'pallas' to run it in interpret mode off a TPU",
        force_supported=reason != "mesh", fallback_reason=reason)
