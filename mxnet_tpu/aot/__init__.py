"""mx.aot — zero-cold-start deploys (docs/AOT.md).

Two layers, composable:

* **Persistent program cache** — every compiled executable (executor
  fwd/fwd_bwd, fused fit step, kvstore programs, Pallas kernels)
  survives process restarts on disk; a restarted process disk-loads
  instead of recompiling (``aot_cache_hits`` counts the loads).  On
  from import, in ``JAX_COMPILATION_CACHE_DIR`` where that is set and
  in ``<checkout>/.jax_cache`` where it is not.

* **Warmup manifests** — ``capture()`` in a warmed process dumps every
  program signature; ``warm(manifest, server=..., engine=...)`` in a
  fresh process dispatches all of them (through the cache when
  enabled) BEFORE traffic arrives, so the first request/step sees
  ``coldstart_compiles == 0``.  Programs compiled under ``warm()`` are
  flagged ``warmed`` in ``telemetry.programs()`` to separate deploy
  cost from live compile storms.

Typical deploy::

    # warmed pod, once:
    mx.aot.save(mx.aot.capture(), "model.aot.json")
    # every restart (JAX_COMPILATION_CACHE_DIR shared):
    server = serving.ModelServer(sym, params, ...,
                                 warmup_manifest="model.aot.json")
"""
import logging

from ..telemetry.programs import warming
from . import manifest as _manifest
from . import store
from .manifest import capture, compatible, default_path, load, save
from .store import cache_dir, disable as disable_persistent_cache
from .store import enable as enable_persistent_cache

log = logging.getLogger(__name__)

__all__ = [
    "capture", "save", "load", "warm", "warming", "compatible",
    "default_path", "enable_persistent_cache",
    "disable_persistent_cache", "cache_dir", "stats", "store",
]


def warm(manifest, *, server=None, engine=None, module=None):
    """Pre-compile every program a previous process dispatched.

    ``manifest`` is a path or a ``capture()`` dict.  Targets are the
    objects that own the dispatch sites: a ``serving.ModelServer``
    (warms each replica's bucketed predictors), a
    ``decode.DecodeEngine`` (decode step + caches), a bound
    ``module.Module`` with a fused fit step.  An incompatible manifest
    (version/backend/mesh drift) is skipped with a warning — the
    process simply compiles on first use; deploys never fail here.

    Returns ``{"entries": n, "warmed": k, "skipped": reason|None}``.
    """
    m = _manifest.load(manifest) if isinstance(manifest, str) else manifest
    ok, reason = _manifest.compatible(m)
    if not ok:
        log.warning("aot: manifest incompatible (%s); falling back to "
                    "cold compiles", reason)
        return {"entries": len(_manifest.entries(m)), "warmed": 0,
                "skipped": reason}
    n = 0
    with warming():
        for target in (server, engine, module):
            if target is None:
                continue
            n += int(target.aot_warm(m) or 0)
    from .. import sharding
    mesh = sharding.get_mesh()
    fp = sharding.mesh_fingerprint(mesh) if mesh is not None else None
    store.index_update(_manifest.entries(m), mesh_fingerprint=fp)
    return {"entries": len(_manifest.entries(m)), "warmed": n,
            "skipped": None}


def stats():
    """Cache/warmup counters for quick inspection and the tests."""
    from ..telemetry.programs import PROGRAMS_WARMED
    return {
        "cache_dir": store.cache_dir(),
        "cache_hits": store.AOT_CACHE_HITS.value,
        "cache_misses": store.AOT_CACHE_MISSES.value,
        "index_errors": store.AOT_INDEX_ERRORS.value,
        "programs_warmed": PROGRAMS_WARMED.value,
    }


# on from import; JAX_COMPILATION_CACHE_DIR alone places it
enable_persistent_cache()
