"""Compiled-program registry: per-program cost attribution from XLA.

Every jit site that already reports retraces through
:class:`registry.RetraceSite` — the executor fwd/fwd_bwd programs, the
fused fit step, the bucketed kvstore programs (single-host and tpu),
and therefore the decode engine's prefill/step executors — registers
the program it just compiled here, keyed by ``(site, fn, abstract
argument signature)``.  The registry answers the question hand FLOP
math cannot: what does the COMPILER say each live program costs?

* **Recording is compile-path-only.**  ``RetraceSite.timed`` calls
  :func:`record` only on calls during which its thread (re)traced, so
  steady-state dispatches never touch this module.  ``record`` captures
  the jitted callable plus a ``ShapeDtypeStruct`` skeleton of the
  arguments (metadata only — safe even for donated buffers, whose
  shapes/dtypes survive donation) and the first-trace wall time.
* **Analysis is lazy and memoized.**  ``cost_analysis()`` /
  ``memory_analysis()`` need a compiled executable; re-lowering the
  jitted callable over the recorded abstract arguments costs one extra
  XLA compile the FIRST time a program is inspected (the same
  ``lower().compile()`` idiom) and nothing after.  :func:`programs` with ``analyze=False`` (the flight-recorder
  dump path) reports only already-computed analyses — a crash dump
  must never compile.

Exported surfaces: ``telemetry.programs()`` (list of dicts) and
``top_programs(k)`` (by FLOPs — the flight-dump table).  The package
keeps no table of chip peaks: a utilisation is the benchmark's to
compute, from ``benchmark/peaks.json``.
"""
from __future__ import annotations

import contextlib
import threading

from .registry import REGISTRY

__all__ = ["record", "register_compiled", "programs", "top_programs",
           "analyze", "clear", "export_signatures", "warming",
           "is_warming", "note_donation"]

PROGRAMS_REGISTERED = REGISTRY.gauge(
    "trace_programs", "distinct compiled programs currently in the "
    "program registry", unit="programs")
PROGRAMS_WARMED = REGISTRY.gauge(
    "trace_programs_warmed", "registered programs compiled (or loaded "
    "from the persistent cache) during an explicit AOT warmup phase "
    "(mx.aot) rather than by live traffic", unit="programs")

_lock = threading.Lock()
_programs = {}          # key -> entry dict
_order = []             # insertion order of keys
# (site, fn_name, fingerprint) -> key: the double-registration guard —
# an AOT-warmed program and its later live-traffic dispatch (a fresh
# fn id, or register_compiled followed by record) merge into ONE entry
# instead of inflating programs() counts (ISSUE 17)
_by_sig = {}
# id(jitted fn) -> donate_argnums, noted by program builders (executor
# donated step, fused fit step) so manifests can carry donation.  Keyed
# by id on purpose: the fns live in per-symbol compile caches for the
# process lifetime, so the table is bounded by the program count.
_donated = {}

# thread-local AOT-warmup flag (mx.aot re-exports `warming`): programs
# recorded while set carry warmed=True and count in PROGRAMS_WARMED
_warm_tls = threading.local()


@contextlib.contextmanager
def warming():
    """Mark programs recorded on this thread as AOT-warmed."""
    prev = getattr(_warm_tls, "on", False)
    _warm_tls.on = True
    try:
        yield
    finally:
        _warm_tls.on = prev


def _warming_now():
    return bool(getattr(_warm_tls, "on", False))


def is_warming():
    """Whether this thread is inside a ``warming()`` phase — warmup
    thread pools capture it in the submitting thread and re-enter
    ``warming()`` in each worker (the flag is thread-local)."""
    return _warming_now()


def note_donation(fn, argnums):
    """Builders of donated programs record their donate_argnums here
    (jit objects accept attributes, but a side table survives wrapper
    layers); manifests export it per program entry."""
    try:
        with _lock:
            _donated[id(fn)] = tuple(int(a) for a in argnums)
    except Exception:
        pass


def _abstractify(args):
    """ShapeDtypeStruct skeleton of a call's argument pytree (hashable
    fingerprint + relowerable spec).  Shape/dtype metadata is readable
    even off donated (already-deleted) arrays."""
    import jax
    import numpy as _np

    def one(a):
        if a is None:
            return None
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None or dtype is None:
            a = _np.asarray(a)
            shape, dtype = a.shape, a.dtype
        return jax.ShapeDtypeStruct(tuple(shape), _np.dtype(dtype))

    return jax.tree.map(one, args, is_leaf=lambda x: x is None)


def _fingerprint(abstract):
    import jax
    leaves, treedef = jax.tree.flatten(
        abstract, is_leaf=lambda x: x is None)
    return (str(treedef),
            tuple((l.shape, str(l.dtype)) if l is not None else None
                  for l in leaves))


def record(site, fn, args, compile_ms=None):
    """Register one just-compiled program (called by RetraceSite.timed
    on the compile path only).  Never raises — attribution must not be
    able to fail a training step."""
    try:
        abstract = _abstractify(args)
        fp = _fingerprint(abstract)
        key = (site, id(fn)) + fp
        fn_name = getattr(fn, "__name__",
                          None) or str(type(fn).__name__)
        sig_key = (site, fn_name, fp)
    except Exception:
        return None
    with _lock:
        entry = _programs.get(key)
        if entry is None and sig_key in _by_sig:
            # same (site, signature) already registered under another
            # id — an AOT-warmed program now dispatched by traffic, or
            # a rebind of the same symbol: merge, don't inflate counts
            key = _by_sig[sig_key]
            entry = _programs.get(key)
            if entry is not None and entry["fn"] is None:
                entry["fn"] = fn          # give AOT stubs a live fn
                entry["abstract"] = abstract
                entry["arg_shapes"] = _shape_summary(abstract)
        if entry is None:
            entry = {
                "site": site,
                "fn_name": fn_name,
                "fn": fn,
                "abstract": abstract,
                "arg_shapes": _shape_summary(abstract),
                "retraces": 0,
                "compile_ms": None,
                "warmed": _warming_now(),
                "donated": _donated.get(id(fn)),
                "analysis": None,       # filled lazily by analyze()
                "analysis_error": None,
            }
            _programs[key] = entry
            _by_sig[sig_key] = key
            _order.append(key)
            PROGRAMS_REGISTERED.set(len(_order))
            if entry["warmed"]:
                PROGRAMS_WARMED.set(sum(
                    1 for e in _programs.values() if e.get("warmed")))
        entry["retraces"] += 1
        if compile_ms is not None:
            # keep the FIRST trace's wall time (trace+compile+first run);
            # later shape-variant retraces are tracked by the count
            if entry["compile_ms"] is None:
                entry["compile_ms"] = round(float(compile_ms), 3)
    return key


def register_compiled(site, compiled, fn_name=None, compile_ms=None,
                      signature=None, warmed=None):
    """Register an ALREADY-compiled executable (``jitted.lower(...)
    .compile()``) for a caller that compiles ahead of time itself, so
    its programs appear in ``telemetry.programs()`` and their analyses
    never recompile (today's callers: tests/test_aot.py).

    ``signature`` (an argument pytree or ShapeDtypeStruct skeleton)
    enables the (site, signature) double-registration guard: if the
    same program was already recorded — or is later recorded by live
    traffic — both registrations share ONE entry.  ``warmed`` defaults
    to the thread's AOT-warming state.  Returns the entry dict."""
    key = (site, id(compiled), "aot")
    abstract = fp = sig_key = None
    if signature is not None:
        try:
            abstract = _abstractify(signature)
            fp = _fingerprint(abstract)
            sig_key = (site, fn_name or "compiled", fp)
        except Exception:
            abstract = sig_key = None
    if warmed is None:
        warmed = _warming_now()
    with _lock:
        entry = _programs.get(key)
        if entry is None and sig_key is not None and sig_key in _by_sig:
            entry = _programs.get(_by_sig[sig_key])
        if entry is not None:
            if warmed and not entry.get("warmed"):
                entry["warmed"] = True
                PROGRAMS_WARMED.set(sum(
                    1 for e in _programs.values() if e.get("warmed")))
            if compile_ms is not None and entry["compile_ms"] is None:
                entry["compile_ms"] = round(float(compile_ms), 3)
        if entry is None:
            entry = {
                "site": site,
                "fn_name": fn_name or "compiled",
                "fn": None,
                "abstract": abstract,
                "arg_shapes": (_shape_summary(abstract)
                               if abstract is not None else None),
                "retraces": 1,
                "compile_ms": (round(float(compile_ms), 3)
                               if compile_ms is not None else None),
                "warmed": bool(warmed),
                "donated": None,
                "analysis": None,
                "analysis_error": None,
            }
            _programs[key] = entry
            if sig_key is not None:
                _by_sig[sig_key] = key
            _order.append(key)
            PROGRAMS_REGISTERED.set(len(_order))
            if entry["warmed"]:
                PROGRAMS_WARMED.set(sum(
                    1 for e in _programs.values() if e.get("warmed")))
    _analyze_entry(entry, compiled=compiled)
    return _public(entry)


def _shape_summary(abstract, limit=8):
    import jax
    leaves = [l for l in jax.tree.leaves(
        abstract, is_leaf=lambda x: x is None) if l is not None]
    shapes = ["%s%s" % (str(l.dtype), list(l.shape)) for l in leaves]
    if len(shapes) > limit:
        shapes = shapes[:limit] + ["... +%d" % (len(shapes) - limit)]
    return shapes


def _analyze_entry(entry, compiled=None):
    """Compute + cache cost/memory analysis for one entry. One extra
    compile for RetraceSite-recorded entries the first time (AOT
    lowering is a separate cache from the dispatch path); zero for
    register_compiled entries."""
    if entry["analysis"] is not None or entry["analysis_error"] is not None:
        return entry["analysis"]
    try:
        if compiled is None:
            from .registry import RETRACE_SUPPRESS
            args = entry["abstract"]
            # re-materialize the recorded pytree call: sites call their
            # jitted fn positionally, so the skeleton is an args tuple.
            # Lowering usually hits the cached jaxpr; on a miss the
            # traced body re-runs — mute its retrace note() so analysis
            # can never move the zero-retrace witnesses it reports on
            RETRACE_SUPPRESS.on = True
            try:
                compiled = entry["fn"].lower(*args).compile()
            finally:
                RETRACE_SUPPRESS.on = False
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        cost = dict(cost or {})
        mem = None
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        analysis = {
            "flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)
                                    or 0.0),
            "transcendentals": float(cost.get("transcendentals", 0.0)
                                     or 0.0),
        }
        if mem is not None:
            arg_b = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
            out_b = int(getattr(mem, "output_size_in_bytes", 0) or 0)
            tmp_b = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
            analysis.update({
                "argument_bytes": arg_b,
                "output_bytes": out_b,
                "temp_bytes": tmp_b,
                # the executable's device high-water mark: resident
                # args + outputs + scratch (alias'd bytes counted once
                # on the argument side)
                "peak_hbm_bytes": arg_b + out_b + tmp_b
                - int(getattr(mem, "alias_size_in_bytes", 0) or 0),
                "generated_code_bytes": int(getattr(
                    mem, "generated_code_size_in_bytes", 0) or 0),
            })
        entry["analysis"] = analysis
        return analysis
    except Exception as e:                          # noqa: BLE001
        entry["analysis_error"] = "%s: %s" % (type(e).__name__, e)
        return None


def analyze(entry_or_index):
    """Force analysis of one entry (``programs(analyze=False)`` rows
    carry ``index``)."""
    with _lock:
        keys = list(_order)
    if isinstance(entry_or_index, int):
        entry = _programs[keys[entry_or_index]]
    else:
        entry = entry_or_index
    return _analyze_entry(entry)


def export_signatures(site=None):
    """FULL (untruncated) program signatures for AOT manifests
    (mx.aot.capture): per entry the site, fn_name, every argument
    leaf's dtype/shape with the pytree structure string, donation, the
    first-trace compile_ms and the warmed flag.  Entries registered
    without a signature (bare register_compiled) are skipped — they
    cannot be re-warmed from shapes alone."""
    import jax
    with _lock:
        entries = [_programs[k] for k in _order]
    out = []
    for entry in entries:
        if site is not None and entry["site"] != site:
            continue
        abstract = entry.get("abstract")
        if abstract is None:
            continue
        leaves, treedef = jax.tree.flatten(
            abstract, is_leaf=lambda x: x is None)
        out.append({
            "site": entry["site"],
            "fn_name": entry["fn_name"],
            "treedef": str(treedef),
            "arg_specs": [[str(l.dtype), list(l.shape)]
                          if l is not None else None for l in leaves],
            "donated": (list(entry["donated"])
                        if entry.get("donated") else None),
            "compile_ms": entry["compile_ms"],
            "warmed": bool(entry.get("warmed")),
        })
    return out


def _public(entry, index=None):
    out = {k: entry[k] for k in ("site", "fn_name", "arg_shapes",
                                 "retraces", "compile_ms")}
    out["warmed"] = bool(entry.get("warmed"))
    if index is not None:
        out["index"] = index
    a = entry["analysis"]
    if a is not None:
        out.update(a)
    elif entry["analysis_error"] is not None:
        out["analysis_error"] = entry["analysis_error"]
    return out


def programs(analyze=True, site=None):
    """Every registered program as a list of dicts (registration
    order).  ``analyze=True`` (default) runs the lazy cost/memory
    analysis for rows that don't have one yet; ``analyze=False`` (the
    crash-dump path) reports only cached analyses."""
    with _lock:
        entries = [(_programs[k], i) for i, k in enumerate(_order)]
    out = []
    for entry, i in entries:
        if site is not None and entry["site"] != site:
            continue
        if analyze:
            _analyze_entry(entry)
        out.append(_public(entry, index=i))
    return out


def top_programs(k=5, analyze=False, by="flops"):
    """Top-``k`` programs by ``by`` (default FLOPs) — the flight-dump
    table.  With ``analyze=False`` only already-analyzed rows rank."""
    rows = [r for r in programs(analyze=analyze) if r.get(by)]
    rows.sort(key=lambda r: -r[by])
    return rows[:k]


def clear():
    """Tests/teardown only."""
    with _lock:
        _programs.clear()
        _by_sig.clear()
        _donated.clear()
        del _order[:]
        PROGRAMS_REGISTERED.set(0)
        PROGRAMS_WARMED.set(0)
