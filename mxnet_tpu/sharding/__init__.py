"""mx.sharding — GSPMD model parallelism through Symbol/Gluon.

The fused fit step already psums gradients over a 1-D ``dp`` mesh
(module/executor_group.py).  This package generalizes the mesh to 2-D
(data x model) and lets users annotate *which* axis each parameter or
activation is partitioned over, using the same string-attr machinery
that carries ``lr_mult`` through Symbol/Gluon:

    mx.sharding.set_mesh({"dp": 4, "mp": 2})          # or MXTPU_MESH=dp=4,mp=2
    w = mx.sym.Variable("fc_weight", __sharding__=mx.sharding.spec("mp", None))
    y = mx.sharding.constrain(y, None, None, "mp")    # activation constraint

At bind time the executor resolves ``__sharding__`` attrs into
``jax.sharding.NamedSharding``s and places the parameters sharded (the
HBM census shows per-device param bytes shrink); inside the one jitted
program every annotated activation gets a
``jax.lax.with_sharding_constraint`` so GSPMD partitions the matmuls
over ``mp`` while the gradient psum spans ``dp`` only — still one
launch per step, zero steady-state retraces.

Specs are serialized as canonical tuple reprs (e.g. ``"('mp', None)"``)
because Symbol attrs are strings and must survive tojson/pickle
round-trips (see docs/SHARDING.md).
"""
from __future__ import annotations

import ast
import os
import time

import numpy as _np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..parallel import mesh as _mesh_mod
from .. import telemetry as _telemetry

__all__ = [
    "KNOWN_AXES", "SHARDING_ATTR",
    "spec", "parse_spec", "partition_spec",
    "set_mesh", "get_mesh", "clear_mesh", "mesh_fingerprint",
    "resolve", "check_divisible", "match_param",
    "annotate", "constrain", "collect_var_specs", "symbol_has_sharding",
    "active_fingerprint",
    "column_parallel_fc", "row_parallel_fc", "ring_attention_on_mesh",
    "per_device_param_bytes",
]

#: Mesh axis names the framework knows about (parallel/mesh.py docs):
#: dp=data, mp/tp=tensor (model), pp=pipeline, sp=sequence, ep=expert.
KNOWN_AXES = ("dp", "mp", "tp", "pp", "sp", "ep")

#: The Symbol/Parameter string attr carrying a serialized spec.
SHARDING_ATTR = "__sharding__"

# -- telemetry (names must stay literal for the analyze telemetry pass) -
CONSTRAINT_SITES = _telemetry.REGISTRY.gauge(
    "sharding_constraint_sites",
    help="with_sharding_constraint sites in the most recently built "
         "compiled program", unit="sites")
RESOLVE_MS = _telemetry.REGISTRY.histogram(
    "sharding_resolve_ms",
    help="bind-time latency resolving __sharding__ attrs to "
         "NamedShardings", unit="ms")

# The explicitly selected training mesh.  Kept separate from
# parallel.mesh._CURRENT because data_parallel_mesh() overwrites that
# slot on every Module bind; this one changes only via set_mesh()/env.
_STATE = {"mesh": None, "env_checked": False}


# ----------------------------------------------------------------------
# specs
# ----------------------------------------------------------------------
def spec(*axes):
    """Serialize a per-dim partition spec to its canonical attr string.

    ``spec('mp', None)`` -> ``"('mp', None)"`` — dim 0 split over the
    ``mp`` mesh axis, dim 1 replicated.  An entry may also be a tuple of
    axis names (multi-axis sharding of one dim).  Unnamed trailing dims
    are replicated, matching ``jax.sharding.PartitionSpec``.
    """
    canon = []
    for a in axes:
        if a is None:
            canon.append(None)
        elif isinstance(a, str):
            _check_axis_name(a)
            canon.append(a)
        elif isinstance(a, (tuple, list)):
            for x in a:
                _check_axis_name(x)
            canon.append(tuple(a))
        else:
            raise MXNetError("sharding.spec entries must be an axis "
                             "name, None, or a tuple of axis names; got "
                             "%r" % (a,))
    return repr(tuple(canon))


def _check_axis_name(a):
    if not isinstance(a, str) or a not in KNOWN_AXES:
        raise MXNetError(
            "unknown mesh axis %r (known axes: %s)" % (a, ", ".join(KNOWN_AXES)))


def parse_spec(s):
    """Inverse of :func:`spec`: attr string -> tuple of axis entries."""
    if isinstance(s, tuple):
        return s
    try:
        val = ast.literal_eval(s)
    except (ValueError, SyntaxError):
        raise MXNetError("malformed __sharding__ attr %r" % (s,))
    if not isinstance(val, tuple):
        raise MXNetError("__sharding__ attr must serialize a tuple, got %r"
                         % (s,))
    for a in val:
        if a is None:
            continue
        if isinstance(a, str):
            _check_axis_name(a)
        elif isinstance(a, tuple):
            for x in a:
                _check_axis_name(x)
        else:
            raise MXNetError("malformed __sharding__ entry %r in %r"
                             % (a, s))
    return val


def partition_spec(s):
    """Attr string -> ``jax.sharding.PartitionSpec``."""
    return P(*parse_spec(s))


# ----------------------------------------------------------------------
# mesh selection
# ----------------------------------------------------------------------
def set_mesh(axes=None, devices=None):
    """Select the training mesh.

    ``set_mesh({'dp': 4, 'mp': 2})`` builds a 2-D mesh over the first 8
    visible devices (row-major, so adjacent devices share an ``mp``
    group).  ``set_mesh(mesh)`` adopts an existing ``jax.sharding.Mesh``;
    ``set_mesh(None)`` clears the selection (modules fall back to the
    implicit 1-D dp mesh).  Returns the mesh (or None).
    """
    if axes is None:
        _STATE["mesh"] = None
        _STATE["env_checked"] = True       # explicit clear beats the env
        _mesh_mod._CURRENT["mesh"] = None
        return None
    if isinstance(axes, Mesh):
        mesh = axes
    else:
        for name in axes:
            _check_axis_name(name)
        mesh = _mesh_mod.make_mesh(dict(axes), devices=devices)
    _STATE["mesh"] = mesh
    _STATE["env_checked"] = True
    _mesh_mod._CURRENT["mesh"] = mesh
    return mesh


def _mesh_from_env():
    raw = os.environ.get("MXTPU_MESH", "").strip()
    if not raw:
        return None
    axes = {}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise MXNetError("MXTPU_MESH entries must look like dp=4; "
                             "got %r" % part)
        name, _, size = part.partition("=")
        name = name.strip()
        _check_axis_name(name)
        axes[name] = int(size)
    if not axes:
        return None
    return set_mesh(axes)


def get_mesh():
    """The explicitly selected mesh, lazily parsing ``MXTPU_MESH`` the
    first time (format ``dp=4,mp=2``). None when no mesh is selected."""
    if _STATE["mesh"] is None and not _STATE["env_checked"]:
        _STATE["env_checked"] = True
        _mesh_from_env()
    return _STATE["mesh"]


def clear_mesh():
    """Drop the selected mesh (and suppress MXTPU_MESH re-parsing)."""
    return set_mesh(None)


def mesh_fingerprint(mesh):
    """Stable hashable identity of a mesh: axis names/sizes + devices.
    Used to key compiled-program caches so a mesh change retraces
    instead of reusing programs built against stale shardings."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


# ----------------------------------------------------------------------
# resolution (bind time)
# ----------------------------------------------------------------------
def check_divisible(entries, shape, mesh, what=""):
    """Raise unless every named axis divides its dim of ``shape``."""
    if len(entries) > len(shape):
        raise MXNetError(
            "sharding spec %r has %d entries but %s%r has rank %d"
            % (entries, len(entries), what and what + " ", tuple(shape),
               len(shape)))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for dim, entry in enumerate(entries):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            if a not in sizes:
                raise MXNetError(
                    "sharding spec %r names axis %r absent from mesh %s"
                    % (entries, a, tuple(mesh.axis_names)))
            n *= int(sizes[a])
        if shape[dim] % n != 0:
            raise MXNetError(
                "sharding spec %r: axis group %r (size %d) cannot divide "
                "dim %d of %s%r" % (entries, entry, n, dim,
                                    what and what + " ", tuple(shape)))


def resolve(spec_str, shape, mesh, what=""):
    """Attr string + shape + mesh -> validated ``NamedSharding``.

    Bind-time latency lands in the ``sharding_resolve_ms`` histogram.
    """
    t0 = time.perf_counter()
    try:
        entries = parse_spec(spec_str)
        check_divisible(entries, shape, mesh, what=what)
        return NamedSharding(mesh, P(*entries))
    finally:
        RESOLVE_MS.observe((time.perf_counter() - t0) * 1000.0)


def match_param(leaf, param_data, mesh=None):
    """Place an optimizer-state / residual leaf with its parameter's
    sharding (same-shape leaves inherit it; scalars and mismatched
    shapes are replicated over the same mesh so every input of the
    donated fit program lives on one device set)."""
    sh = getattr(param_data, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return leaf
    if tuple(getattr(leaf, "shape", ())) == tuple(param_data.shape):
        return jax.device_put(leaf, sh)
    return jax.device_put(leaf, NamedSharding(sh.mesh, P()))


# ----------------------------------------------------------------------
# symbol annotation
# ----------------------------------------------------------------------
def annotate(symbol, *axes):
    """Attach ``spec(*axes)`` to a symbol head node (a Variable for
    parameter placement, any op output for an activation constraint).
    Returns the same symbol for chaining."""
    symbol._set_attr(**{SHARDING_ATTR: spec(*axes)})
    return symbol


# activation alias — reads as jax.lax.with_sharding_constraint at the
# symbol level
constrain = annotate


def collect_var_specs(symbol):
    """{node name: spec string} for every annotated node in the graph,
    variables and op outputs alike."""
    out = {}
    for node in symbol._topo():
        s = node.str_attrs.get(SHARDING_ATTR)
        if s:
            out[node.name] = s
    return out


def symbol_has_sharding(symbol):
    for node in symbol._topo():
        if node.str_attrs.get(SHARDING_ATTR):
            return True
    return False


def active_fingerprint(symbol):
    """Cache key component for compiled programs: the selected mesh's
    fingerprint when this symbol carries sharding annotations (those
    programs close over the mesh), else None (mesh-independent)."""
    mesh = get_mesh()
    if mesh is None or not symbol_has_sharding(symbol):
        return None
    return mesh_fingerprint(mesh)


# ----------------------------------------------------------------------
# tensor-parallel building blocks (Megatron-style, (out, in) weights)
# ----------------------------------------------------------------------
def column_parallel_fc(data, num_hidden, name, axis="mp", no_bias=False,
                       flatten=False, act_spec=None, **kwargs):
    """FullyConnected whose OUTPUT features are split over ``axis``:
    weight (out, in) sharded ``(axis, None)``, bias ``(axis,)``.  The
    activation keeps the split (annotate with ``act_spec`` — e.g.
    ``(None, None, 'mp')`` for (B, S, F) inputs) and feeds a row-parallel
    layer with no communication in between."""
    from .. import symbol as sym
    weight = sym.Variable(name + "_weight",
                          **{SHARDING_ATTR: spec(axis, None)})
    bias = None if no_bias else sym.Variable(
        name + "_bias", **{SHARDING_ATTR: spec(axis)})
    out = sym.FullyConnected(data=data, weight=weight, bias=bias,
                             num_hidden=num_hidden, no_bias=no_bias,
                             flatten=flatten, name=name, **kwargs)
    if act_spec is not None:
        constrain(out, *act_spec)
    return out


def row_parallel_fc(data, num_hidden, name, axis="mp", no_bias=False,
                    flatten=False, **kwargs):
    """FullyConnected whose INPUT features arrive split over ``axis``:
    weight (out, in) sharded ``(None, axis)``; the output is constrained
    replicated, which is where GSPMD inserts the partial-sum
    all-reduce.  Bias stays replicated (added once, after the psum)."""
    from .. import symbol as sym
    weight = sym.Variable(name + "_weight",
                          **{SHARDING_ATTR: spec(None, axis)})
    bias = None if no_bias else sym.Variable(name + "_bias")
    out = sym.FullyConnected(data=data, weight=weight, bias=bias,
                             num_hidden=num_hidden, no_bias=no_bias,
                             flatten=flatten, name=name, **kwargs)
    return constrain(out)


def ring_attention_on_mesh(q, k, v, axis="sp", causal=False, scale=None,
                           batch_axis="dp"):
    """Run parallel.ring_attention over the selected mesh (jnp arrays,
    (B, T, H, D)).  The mesh must carry ``axis``; ``batch_axis`` is used
    when present so dp x sp meshes work unchanged."""
    from ..parallel.ring_attention import ring_attention as _ring
    mesh = get_mesh()
    if mesh is None:
        raise MXNetError("ring_attention_on_mesh: no mesh selected "
                         "(call mx.sharding.set_mesh or set MXTPU_MESH)")
    if axis not in mesh.axis_names:
        raise MXNetError("ring_attention_on_mesh: mesh %s has no %r axis"
                         % (tuple(mesh.axis_names), axis))
    b = batch_axis if batch_axis in mesh.axis_names else None
    return _ring(q, k, v, mesh, axis=axis, causal=causal, scale=scale,
                 batch_axis=b)


# ----------------------------------------------------------------------
# HBM accounting
# ----------------------------------------------------------------------
def per_device_param_bytes(arrays, device=None):
    """Bytes the given arrays occupy on ONE device (the first mesh /
    visible device by default).  Replicated arrays count full size;
    mp-sharded ones count their shard only — this is the number the
    ``param_bytes_per_device`` census gauge reports."""
    total = 0
    for a in arrays:
        data = getattr(a, "_data", a)
        shards = getattr(data, "addressable_shards", None)
        if not shards:
            total += int(getattr(data, "nbytes", 0))
            continue
        dev = device if device is not None else shards[0].device
        for s in shards:
            if s.device == dev:
                total += int(s.data.nbytes)
    return total
