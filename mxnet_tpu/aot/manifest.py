"""AOT warmup manifests (docs/AOT.md).

A manifest is a JSON snapshot of every compiled program a running
process dispatched — site, fn_name, full argument signature (treedef +
per-leaf dtype/shape), donation mask — plus a compatibility header
(jax version, backend, device kind, mesh fingerprint, cache dir).
``mx.aot.capture()`` dumps it from a warmed process;
``mx.aot.warm(manifest)`` in a FRESH process AOT-compiles (or, from
the persistent compile cache, disk-loads) every entry before the
process accepts traffic, so the first request/step launches with
``coldstart_compiles == 0``.

Manifests are advisory: an incompatible or stale manifest is skipped
with a warning and the process falls back to compile-on-first-use —
never a hard failure at deploy time.  ``load()`` of a syntactically
broken file does raise (that is an operator error, not drift).
"""
import json
import os

from ..base import MXNetError
from ..telemetry import programs as _programs

FORMAT_VERSION = 1


def _platform():
    import jax
    try:
        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", str(dev))
    except Exception:
        kind = None
    return jax.default_backend(), kind


def capture(site=None):
    """Snapshot the process's compiled programs into a manifest dict.

    ``site`` filters to one RetraceSite (e.g. ``"executor"``); default
    is every registered program with a recorded signature."""
    from .. import sharding
    from . import store
    backend, kind = _platform()
    import jax
    mesh = sharding.get_mesh()
    fp = sharding.mesh_fingerprint(mesh) if mesh is not None else None
    return {
        "format": FORMAT_VERSION,
        "jax": str(jax.__version__),
        "backend": backend,
        "device_kind": kind,
        "mesh": repr(fp) if fp is not None else None,
        "cache_dir": store.cache_dir(),
        "entries": _programs.export_signatures(site=site),
    }


def save(manifest, path):
    """Write a manifest atomically (tmp + rename)."""
    path = os.fspath(path)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def load(path):
    """Read and validate a manifest; raises MXNetError on a file that
    is not a manifest (operator error — unlike version drift, which
    ``compatible()`` reports softly)."""
    try:
        with open(os.fspath(path)) as f:
            m = json.load(f)
    except (OSError, ValueError) as e:
        raise MXNetError("aot: cannot read manifest %s: %s" % (path, e))
    if (not isinstance(m, dict) or "entries" not in m
            or not isinstance(m["entries"], list)):
        raise MXNetError("aot: %s is not an AOT manifest" % (path,))
    return m


def default_path():
    """The ``MXNET_AOT_MANIFEST`` knob: manifest consumed by server /
    engine startup when no explicit path is passed (None = unset)."""
    return os.environ.get("MXNET_AOT_MANIFEST") or None


def compatible(manifest):
    """(ok, reason) — whether warming from this manifest can reuse
    programs in this process.  Soft check: callers log the reason and
    fall back to cold compiles rather than raising."""
    import jax
    from .. import sharding
    if manifest.get("format") != FORMAT_VERSION:
        return False, "manifest format %r != %d" % (
            manifest.get("format"), FORMAT_VERSION)
    if manifest.get("jax") != str(jax.__version__):
        return False, "jax %s != manifest %s" % (
            jax.__version__, manifest.get("jax"))
    backend, _ = _platform()
    if manifest.get("backend") != backend:
        return False, "backend %s != manifest %s" % (
            backend, manifest.get("backend"))
    mesh = sharding.get_mesh()
    fp = sharding.mesh_fingerprint(mesh) if mesh is not None else None
    here = repr(fp) if fp is not None else None
    if manifest.get("mesh") != here:
        return False, "mesh %s != manifest %s" % (
            here, manifest.get("mesh"))
    return True, "ok"


def entries(manifest, site=None):
    """Manifest entries, optionally filtered by RetraceSite."""
    es = manifest.get("entries", [])
    if site is not None:
        es = [e for e in es if e.get("site") == site]
    return es
