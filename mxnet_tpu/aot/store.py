"""Persistent compiled-program store (docs/AOT.md).

Wraps jax's persistent compilation cache
(``jax.experimental.compilation_cache``) so every RetraceSite dispatch
— executor fwd/fwd_bwd, the fused fit step, the bucketed kvstore
programs, and the Pallas kernels they embed — serializes its compiled
executable to disk.  The directory is placed from OUTSIDE: where
``JAX_COMPILATION_CACHE_DIR`` is set the cache lives there (jax reads
that variable itself, and this package sets no other directory);
where it is not, the cache goes to one fixed path inside the checkout
(``.jax_cache/`` beside the package — the path is part of jax's cache
key, so a directory that moves never hits).  A restarted process pays
trace + disk-load instead of trace + XLA compile for every program it
has compiled before (``jit_compile_ms`` collapses to trace time; the
``aot_cache_hits`` counter is the witness).

The same listeners that count hits and misses keep jax's own build
seconds, laid to the dispatch site or set-up span that caused them:
``program_build_seconds{site, phase}`` (trace, lower, load and, inside
load, cache_read) and ``program_builds{site}`` (:func:`_on_time_span`).

On top of jax's content-addressed files this module keeps its OWN
index (``mx_cache_index.json``): the framework's (site, signature,
mesh-fingerprint) program keys with fn_name / compile_ms / versions,
written by ``mx.aot.capture()``/``warm()``.  The index is pure
bookkeeping — `jax` owns the executables — so corruption or a
version mismatch NEVER breaks a deploy: the index is discarded and
rebuilt, and a corrupt/stale cache entry simply misses (jax validates
its own entries) and falls back to a fresh compile.

Key stability: jax's cache key covers the computation, compile
options, XLA flags and versions.  Processes that should share a cache
must therefore run the same configuration — this module applies the
SAME three cache settings every time, so the framework itself never
forks the key.
"""
import json
import logging
import os
import threading

from .. import telemetry as _telemetry
from ..telemetry.registry import BUILD_SITE

log = logging.getLogger(__name__)

# bump when the index schema changes: mismatched indexes are discarded
# (never trusted), matching the corruption fallback
FORMAT_VERSION = 1
INDEX_NAME = "mx_cache_index.json"

AOT_CACHE_HITS = _telemetry.REGISTRY.counter(
    "aot_cache_hits", "compiled executables served from the "
    "persistent compilation cache instead of XLA-compiled "
    "(docs/AOT.md)")
AOT_CACHE_MISSES = _telemetry.REGISTRY.counter(
    "aot_cache_misses", "persistent-cache lookups that fell back to a "
    "fresh XLA compile (first compile of a key, or a stale/corrupt "
    "entry)")
AOT_INDEX_ERRORS = _telemetry.REGISTRY.counter(
    "aot_index_errors", "persistent-cache index files discarded as "
    "corrupt or version-mismatched (rebuilt; never fatal)")

PROGRAM_BUILD_SECONDS = _telemetry.REGISTRY.counter(
    "program_build_seconds", "seconds jax spent building programs, "
    "labeled by `site` (the RetraceSite dispatching, else the open "
    "set-up span, else outside) and `phase` (trace, lower, load, "
    "cache_read)", unit="s")
PROGRAM_BUILDS = _telemetry.REGISTRY.counter(
    "program_builds", "executables compiled or loaded from the "
    "persistent cache, labeled by `site`", unit="programs")

# jax's time-span events -> the phase of a program's build
_BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "load",
}
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_STATE = {"dir": None, "listener": False}


class _HostIntervals(threading.local):
    """One thread's trace and lower intervals already counted: disjoint,
    in the order they ended.  One pair of floats a top-level build,
    beside the executable jax keeps for it."""

    def __init__(self):
        self.spans = []


_COUNTED = _HostIntervals()

# the one fixed default: <checkout>/.jax_cache (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _jax_version():
    import jax
    return str(jax.__version__)


def cache_dir():
    """The active persistent-cache directory (None = disabled)."""
    return _STATE["dir"]


def _on_event(event, **kw):
    # jax monitoring events are the exact hit/miss witnesses: one
    # cache_hits/cache_misses event per persistent-cache lookup
    if event == "/jax/compilation_cache/cache_hits":
        AOT_CACHE_HITS.inc()
    elif event == "/jax/compilation_cache/cache_misses":
        AOT_CACHE_MISSES.inc()


def _uncounted_seconds(spans, start, end):
    """The seconds of [start, end] that no interval of ``spans`` holds,
    and the interval merged in.  jax's trace events nest (the trace of
    ``step`` reports ``multiply``, ``add``, ... first, then itself) and
    a lowering rule may trace: summed, the same seconds count many
    times; counted so, a thread's trace and lower seconds are a time."""
    fresh = end - start
    lo, hi = start, end
    while spans and spans[-1][1] > start:
        a, b = spans.pop()
        fresh -= max(0.0, min(b, end) - max(a, start))
        lo, hi = min(lo, a), max(hi, b)
    spans.append((lo, hi))
    return max(fresh, 0.0)


def _on_time_span(event, start, end, **kw):
    # jax reports a build's phases on the thread that dispatched it, so
    # the thread's BUILD_SITE is what caused this one
    phase = _BUILD_PHASES.get(event)
    if phase is None:
        return
    site = BUILD_SITE.name
    if phase == "load":
        # XLA's compile, or on a cache hit the read and deserialisation
        PROGRAM_BUILDS.labels(site=site).inc()
        seconds = end - start
    else:
        seconds = _uncounted_seconds(_COUNTED.spans, start, end)
    PROGRAM_BUILD_SECONDS.labels(site=site, phase=phase).inc(seconds)


def _on_duration(event, duration, **kw):
    # a plain duration, no span: the cache's part of a load
    if event == _CACHE_READ_EVENT:
        PROGRAM_BUILD_SECONDS.labels(
            site=BUILD_SITE.name, phase="cache_read").inc(duration)


def _install_listener():
    if _STATE["listener"]:
        return
    import jax
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _STATE["listener"] = True


def _index_path(d):
    return os.path.join(d, INDEX_NAME)


def _fresh_index():
    return {"format": FORMAT_VERSION, "jax": _jax_version(),
            "programs": {}}


def load_index(d=None):
    """The store's program index; a corrupt or version-mismatched file
    is counted, discarded, and replaced by a fresh index (the
    fall-back-to-fresh-compile contract — never raises)."""
    d = d or _STATE["dir"]
    if not d:
        return _fresh_index()
    path = _index_path(d)
    if not os.path.exists(path):
        return _fresh_index()
    try:
        with open(path) as f:
            idx = json.load(f)
        if (not isinstance(idx, dict)
                or idx.get("format") != FORMAT_VERSION
                or idx.get("jax") != _jax_version()
                or not isinstance(idx.get("programs"), dict)):
            raise ValueError("index version/schema mismatch")
        return idx
    except Exception as e:
        AOT_INDEX_ERRORS.inc()
        log.warning("aot: discarding cache index %s (%s); programs "
                    "recompile fresh", path, e)
        return _fresh_index()


def _write_index(d, idx):
    tmp = _index_path(d) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(idx, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, _index_path(d))


def index_update(entries, mesh_fingerprint=None, d=None):
    """Merge program entries (export_signatures rows) into the on-disk
    index under their (site, fn_name, signature, mesh) keys.  Best
    effort — an unwritable cache dir degrades to jax-only caching."""
    d = d or _STATE["dir"]
    if not d:
        return None
    with _lock:
        idx = load_index(d)
        for e in entries:
            key = "|".join([
                e["site"], e["fn_name"],
                str(mesh_fingerprint),
                e.get("treedef", ""),
                ";".join("%s%s" % (s[0], s[1]) if s else "None"
                         for s in e.get("arg_specs", ())),
            ])
            idx["programs"][key] = {
                "site": e["site"], "fn_name": e["fn_name"],
                "compile_ms": e.get("compile_ms"),
                "donated": e.get("donated"),
            }
        try:
            _write_index(d, idx)
        except OSError as e:
            log.warning("aot: cache index not written (%s)", e)
        return idx


def enable(path=None):
    """Turn on the persistent compilation cache — the package import
    does, so it is on unless :func:`disable` was called.  The directory
    is ``path``, else ``JAX_COMPILATION_CACHE_DIR``, else
    :data:`DEFAULT_DIR`.  Safe to call repeatedly; every process that
    should share the cache applies these exact settings so the cache
    keys agree.  A directory that cannot be created leaves the cache
    off with a warning (a read-only checkout must still import)."""
    import jax
    # first: a process whose cache stays off still builds programs
    _install_listener()
    d = path or os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    try:
        os.makedirs(d, exist_ok=True)
    except OSError as e:
        log.warning("aot: compile cache off, cannot create %s (%s)", d, e)
        return None
    if jax.config.jax_compilation_cache_dir != d:
        # never reached with only JAX_COMPILATION_CACHE_DIR set: jax
        # took the directory from the environment itself
        jax.config.update("jax_compilation_cache_dir", d)
    # cache every program: the default min-compile-time/entry-size
    # gates would skip exactly the small steady-state programs whose
    # compile storms make cold starts slow
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    d = os.path.abspath(d)
    with _lock:
        _STATE["dir"] = d
    # validate (and heal) the index up front so a corrupt file is
    # reported at enable time, not mid-deploy
    idx = load_index(d)
    try:
        _write_index(d, idx)
    except OSError as e:
        log.warning("aot: cache index not written (%s)", e)
    return d


def disable():
    """Tests/teardown: detach the persistent cache."""
    import jax
    jax.config.update("jax_compilation_cache_dir", None)
    with _lock:
        _STATE["dir"] = None
