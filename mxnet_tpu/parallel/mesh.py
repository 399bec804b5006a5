"""Device-mesh helpers.

The reference discovers GPU link topology to build reduction trees
(src/kvstore/gpu_topology.h, comm_tree.h). On TPU the interconnect is the
ICI torus and XLA schedules collectives over it, so "topology" reduces to
choosing mesh axes: ``dp`` (data), ``tp`` (tensor/model), ``pp``
(pipeline), ``sp`` (sequence/context), ``ep`` (expert).
"""
from __future__ import annotations

import numpy as _np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "data_parallel_mesh", "batch_sharding",
           "replicated_sharding", "shard_batch", "current_mesh"]

_CURRENT = {"mesh": None}


def make_mesh(axis_sizes: dict, devices=None) -> Mesh:
    """Create a Mesh with named axes, e.g. make_mesh({'dp': 4, 'tp': 2})."""
    devices = devices if devices is not None else jax.devices()
    names = tuple(axis_sizes)
    sizes = tuple(int(axis_sizes[n]) for n in names)
    total = int(_np.prod(sizes))
    if total > len(devices):
        raise ValueError("mesh needs %d devices, only %d visible"
                         % (total, len(devices)))
    arr = _np.array(devices[:total]).reshape(sizes)
    mesh = Mesh(arr, names)
    _CURRENT["mesh"] = mesh
    return mesh


def data_parallel_mesh(contexts):
    """Mesh with a single 'dp' axis over the given Contexts; None for
    one Context (a one-device program has no mesh — and the registry
    says so, since kernel selection asks it)."""
    devs = [c.jax_device for c in contexts]
    mesh = Mesh(_np.array(devs), ("dp",)) if len(devs) > 1 else None
    _CURRENT["mesh"] = mesh
    return mesh


def current_mesh():
    return _CURRENT["mesh"]


def batch_sharding(mesh, axis="dp"):
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh):
    return NamedSharding(mesh, P())


def shard_batch(x, mesh, axis="dp"):
    """Place a host batch sharded along its leading dim over the mesh."""
    return jax.device_put(x, batch_sharding(mesh, axis))
