"""Pass 3 — donation safety: donated buffers are dead after dispatch.

The fused hot paths donate params/states/residuals into their jitted
programs (``donate_argnums``) so HBM holds one copy of the training
state.  A donated jax array is DELETED by the dispatch; any later host
read raises (best case) or — via a stale alias — silently reads
garbage (the pull-alias-corruption class of bug).  Statically: a name
passed in a donated position must not be *read* again in the same
function after the dispatch call, unless rebound first.

Linking call sites to donation signatures is intra-module: builder
functions that ``return jax.jit(step, donate_argnums=...)`` are
collected (with the wrapped function's parameter list, so positions
map to names), and a call through a name that was bound from a
builder (directly, or through a ``cache[key] = _build_x(...)`` /
``fn = self._steps[sig] = _build_x(...)`` chain) is checked.  When a
builder has several jit returns, the one whose arity matches the call
is used.  Dispatch through ``<site>.timed(fn, *args)`` shifts the
argument positions by one.
"""
from __future__ import annotations

import ast

from .core import Pass


def _wrapped_params(func_def):
    a = func_def.args
    return [arg.arg for arg in a.posonlyargs + a.args]


def _literal_argnums(node, assigns, depth=0):
    """Int positions out of a donate_argnums expression, following one
    local ``donate = ...`` assignment hop."""
    if depth > 3:
        return []
    if isinstance(node, ast.Name) and assigns and node.id in assigns:
        return _literal_argnums(assigns[node.id], assigns, depth + 1)
    elts = node.elts if isinstance(node, (ast.Tuple, ast.List)) else [node]
    return [e.value for e in elts
            if isinstance(e, ast.Constant) and isinstance(e.value, int)]


def _jit_donations(mod, call, assigns=None):
    """(wrapped_name, donated_positions) for a jax.jit call with
    donate_argnums, else None."""
    if not (isinstance(call, ast.Call)
            and mod.resolve(call.func) == "jax.jit"):
        return None
    donate = None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            donate = kw.value
    if donate is None:
        return None
    positions = _literal_argnums(donate, assigns)
    target = call.args[0] if call.args else None
    name = target.id if isinstance(target, ast.Name) else None
    return name, tuple(positions)


class _Builder:
    """One builder function: its jit returns as (params, positions)."""

    def __init__(self, func):
        self.func = func
        self.signatures = []      # [(param_names, donated_positions)]

    def for_arity(self, n):
        for params, pos in self.signatures:
            if len(params) == n:
                return params, pos
        return None


def _collect_builders(mod):
    builders = {}
    for func in (n for n in ast.walk(mod.tree)
                 if isinstance(n, ast.FunctionDef)):
        local_defs = {n.name: n for n in ast.walk(func)
                      if isinstance(n, ast.FunctionDef) and n is not func}
        assigns = {n.targets[0].id: n.value for n in ast.walk(func)
                   if isinstance(n, ast.Assign) and len(n.targets) == 1
                   and isinstance(n.targets[0], ast.Name)}
        sigs = []
        for node in ast.walk(func):
            if isinstance(node, ast.Return) and node.value is not None:
                val = node.value
                # `fn = jax.jit(...); return fn` builders count too
                if isinstance(val, ast.Name) and val.id in assigns:
                    val = assigns[val.id]
                d = _jit_donations(mod, val, assigns)
                if d and d[0] and d[0] in local_defs:
                    sigs.append((_wrapped_params(local_defs[d[0]]),
                                 d[1]))
        if sigs:
            b = _Builder(func)
            b.signatures = sigs
            builders[func.name] = b
    return builders


def _builder_call_name(mod, value, builder_names):
    """Name of the builder a value expression calls, following
    chained assigns like ``cache[key] = _build_x(...)``."""
    if isinstance(value, ast.Call):
        f = value.func
        base = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None)
        if base in builder_names:
            return base
    return None


def _stmts_after(func, stmt):
    """Statements that can execute after ``stmt`` completes, control-
    flow aware: following siblings in every enclosing suite (so an
    exclusive ``else`` branch is NOT included), plus the whole body of
    any enclosing loop (the next iteration re-runs it)."""
    out = []
    child = stmt
    cur = getattr(stmt, "_parent", None)
    while cur is not None:
        suites = [getattr(cur, f, None)
                  for f in ("body", "orelse", "finalbody")]
        for h in getattr(cur, "handlers", []) or []:
            suites.append(h.body)
        for suite in suites:
            if isinstance(suite, list) and child in suite:
                out.extend(suite[suite.index(child) + 1:])
        if isinstance(cur, (ast.For, ast.While)):
            out.extend(s for s in cur.body if s is not stmt)
        if cur is func:
            break
        child = cur
        cur = getattr(cur, "_parent", None)
    return out


def _reads_after(func, stmt, name):
    """First possible Load of ``name`` after ``stmt`` (control-flow
    aware), unless ``stmt`` itself rebinds it (assign target) or a
    rebind is reached first.  Returns the offending node or None."""
    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            for n in ast.walk(t):
                if isinstance(n, ast.Name) and n.id == name:
                    return None          # result rebinds the donated name
    nodes = []
    for s in _stmts_after(func, stmt):
        nodes.extend(n for n in ast.walk(s) if hasattr(n, "lineno"))
    nodes.sort(key=lambda n: (n.lineno, getattr(n, "col_offset", 0)))
    for n in nodes:
        if isinstance(n, ast.Name) and n.id == name:
            if isinstance(n.ctx, ast.Load):
                return n
            if isinstance(n.ctx, (ast.Store, ast.Del)):
                return None
    return None


class DonationPass(Pass):
    name = "donation"
    doc = "names passed in donated positions are not read after dispatch"

    def run(self, ctx):
        findings = []
        for mod in ctx.modules:
            findings.extend(self._scan_module(mod))
        return findings

    def _scan_module(self, mod):
        out = []
        builders = _collect_builders(mod)
        if not builders:
            return out
        for func in (n for n in ast.walk(mod.tree)
                     if isinstance(n, ast.FunctionDef)):
            if func.name in builders:
                continue
            out.extend(self._scan_caller(mod, func, builders))
        return out

    def _scan_caller(self, mod, func, builders):
        # names in this function bound (anywhere) from a builder call
        bound = {}
        for node in ast.walk(func):
            if isinstance(node, ast.Assign):
                bname = _builder_call_name(mod, node.value, builders)
                if bname:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            bound[t.id] = builders[bname]
        if not bound:
            return []
        out = []
        for node in ast.walk(func):
            if not isinstance(node, ast.Call):
                continue
            callee, args = None, None
            f = node.func
            if isinstance(f, ast.Name) and f.id in bound:
                callee, args = bound[f.id], list(node.args)
            elif (isinstance(f, ast.Attribute) and f.attr == "timed"
                  and node.args
                  and isinstance(node.args[0], ast.Name)
                  and node.args[0].id in bound):
                callee = bound[node.args[0].id]
                args = list(node.args[1:])
            if callee is None:
                continue
            sig = callee.for_arity(len(args))
            if sig is None:
                continue
            params, positions = sig
            stmt = node
            while not isinstance(stmt, ast.stmt) \
                    and getattr(stmt, "_parent", None) is not None:
                stmt = stmt._parent
            for pos in positions:
                if pos >= len(args):
                    continue
                a = args[pos]
                if not isinstance(a, ast.Name):
                    continue
                read = _reads_after(func, stmt, a.id)
                if read is not None:
                    out.append(self.finding(
                        mod, read, "donated-read",
                        "%r was donated to the compiled program "
                        "(arg %d of %s, donate_argnums) at line %d "
                        "and is read again here — the buffer is "
                        "deleted by the dispatch" % (
                            a.id, pos, callee.func.name, node.lineno),
                        fix_hint="use the program's returned value, "
                                 "or rebind/copy before dispatch",
                        detail="%s:%s" % (func.name, a.id)))
        return out
