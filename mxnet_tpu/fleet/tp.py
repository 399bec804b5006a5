"""Tensor-parallel decode: mesh selection + witnesses (docs/FLEET.md).

The heavy lifting lives elsewhere — ``models.transformer`` annotates
the decode-step weights/caches when ``tensor_parallel=<axis>`` is set,
and the executor resolves those annotations at bind time — so this
module is deliberately thin: it validates the geometry EARLY (a head
count the axis does not divide fails here with a message naming the
config key, not deep inside GSPMD), selects the mesh, and exposes the
per-device cache-bytes witness tests/test_fleet.py gates on.
"""
from __future__ import annotations

from ..base import MXNetError
from .. import sharding as _sharding

__all__ = ["tp_mesh", "make_tp_engine", "per_device_cache_bytes"]


def tp_mesh(size, axis="mp"):
    """Select (or adopt) a 1-D tensor-parallel mesh of ``size`` devices.

    Reuses the current mesh when it already carries ``axis`` at the
    requested size — calling this twice, or after an explicit
    ``sharding.set_mesh``, is idempotent.  Raises when a DIFFERENT
    ``axis`` extent is already selected: silently rebuilding the mesh
    under a live engine would retrace every program it compiled.
    """
    size = int(size)
    if size < 1:
        raise MXNetError("tp_mesh: size must be >= 1, got %d" % size)
    mesh = _sharding.get_mesh()
    if mesh is not None and axis in mesh.axis_names:
        have = int(mesh.shape[axis])
        if have != size:
            raise MXNetError(
                "tp_mesh: mesh already has %s=%d, asked for %d "
                "(clear_mesh() first — a live engine compiled against "
                "the old mesh would retrace)" % (axis, have, size))
        return mesh
    return _sharding.set_mesh({axis: size})


def _check_tp_geometry(model_config, size, axis):
    """Fail fast on axis-indivisible shapes, naming the config key."""
    heads = int(model_config.get("num_heads", 16))
    d_model = int(model_config.get("d_model", 2048))
    ffn = model_config.get("ffn_dim") or 4 * d_model
    for key, dim in (("num_heads", heads), ("ffn_dim", int(ffn))):
        if dim % size:
            raise MXNetError(
                "tensor-parallel decode needs %s %% %s == 0 "
                "(%s=%d, %s=%d)" % (key, axis, key, dim, axis, size))


def make_tp_engine(arg_params, model_config, tensor_parallel=None,
                   axis="mp", **engine_kwargs):
    """Build a :class:`~mxnet_tpu.decode.DecodeEngine` whose step
    program is sharded over a tensor-parallel mesh.

    ``tensor_parallel=N`` selects (or validates) an ``{axis: N}`` mesh
    and threads ``tensor_parallel=axis`` into the model config, which
    is ALL the engine needs — the decode-step symbols annotate
    QKV/proj/FFN weights column/row-wise and the paged KV caches
    head-wise, bind-time resolution places every buffer, and GSPMD
    propagation shards the step.  ``tensor_parallel=None`` (or 1)
    returns a plain single-device engine, so callers can keep one code
    path.  Remaining kwargs go to the engine untouched.
    """
    from ..decode import DecodeEngine

    if tensor_parallel is None or int(tensor_parallel) == 1:
        return DecodeEngine(arg_params, model_config, **engine_kwargs)
    size = int(tensor_parallel)
    _check_tp_geometry(model_config, size, axis)
    tp_mesh(size, axis=axis)
    cfg = dict(model_config, tensor_parallel=axis)
    return DecodeEngine(arg_params, cfg, **engine_kwargs)


def per_device_cache_bytes(engine, device=None):
    """Bytes of paged-KV-cache storage resident on one device — the
    TP witness of tests/test_fleet.py: head-sharded caches put ~1/mp of the
    replicated footprint on each device, and a regression here means
    the cache annotations stopped resolving (the engine would still be
    CORRECT, just silently paying replicated memory)."""
    return _sharding.per_device_param_bytes(engine._cache_arrs,
                                            device=device)
