"""Row-partition math and device placement for sharded embedding tables.

One embedding table of ``vocab`` rows is row-partitioned two ways at
once (docs/EMBEDDING.md):

* **across devices** (layer 5): when the process sees more than one XLA
  device, the table is laid out over a 1-D ``Mesh`` with axis ``"row"``
  (``NamedSharding((row, None))``) so a compiled lookup lowers to
  gather -> all-to-all/psum over ICI under GSPMD. With one device (the
  CPU test container) placement is the identity and the same programs
  run unsharded.
* **across processes** (layer 6, the ``kvstore='tpu'`` world from
  kvstore_tpu/dist.py): each rank OWNS the contiguous row range
  ``row_range(vocab, rank, world)`` for checkpoint-writing purposes
  (embedding/checkpoint.py: each rank persists its range; any host can
  die). On the CPU multi-process worlds the table itself stays
  replicated-deterministic — every rank applies the identical globally
  coalesced update (engine.py), the same invariant the dense host
  transport keeps for flat buckets.

The split matters: device sharding is a *placement* concern the
compiled programs see; process ownership is a *durability* concern only
the checkpoint path sees. Neither leaks into the other's cache keys.
"""
from __future__ import annotations

import os
import threading

import jax
import numpy as _np

from .. import telemetry as _telemetry

__all__ = ["row_range", "owned_slice", "local_mesh", "table_sharding",
           "place_table", "account_bytes", "EMBED_HBM",
           "partition_decision", "process_row_mesh",
           "account_table_bytes", "EMBED_TBL_PER_HOST", "ALLTOALL_BYTES"]

# table + optimizer state + error-feedback residual bytes currently
# resident for embedding tables, summed over registered keys
# (docs/OBSERVABILITY.md); recsys capacity planning reads this gauge
EMBED_HBM = _telemetry.REGISTRY.gauge(
    "embedding_hbm_bytes",
    "bytes resident for embedding tables (weights + optimizer state + "
    "residuals), summed over tables", unit="bytes")
# TABLE weight bytes this host actually holds: a replicated table
# contributes its full (vocab, dim) footprint, a pod-partitioned one
# only its owned row slab — the 1/W capacity-scaling witness
# tests/embedding_partition_worker.py checks (docs/EMBEDDING.md)
EMBED_TBL_PER_HOST = _telemetry.REGISTRY.gauge(
    "embedding_table_bytes_per_host",
    "embedding table weight bytes resident on this host (a partitioned "
    "table counts only its owned row slab)", unit="bytes")
# bytes this rank handed to the partitioned lookup/apply all-to-all
# transport (index routing + row return legs; 0 while tables replicate)
ALLTOALL_BYTES = _telemetry.REGISTRY.counter(
    "embedding_alltoall_bytes",
    "bytes this process contributed to partitioned-embedding all-to-all "
    "exchanges (indices out + rows back)", unit="bytes")

_LOCK = threading.Lock()
_MESH_CACHE = {}          # n_devices -> Mesh   (guarded by _LOCK)
_HBM_BY_KEY = {}          # key -> bytes        (guarded by _LOCK)
_TBL_BY_KEY = {}          # key -> table weight bytes (guarded by _LOCK)


def row_range(vocab, rank, world):
    """The contiguous row range rank ``rank`` owns: ceil-partitioned so
    every rank owns rows (the final rank's range may be short or empty
    when ``world`` does not divide ``vocab``)."""
    if world <= 0:
        raise ValueError("world must be positive")
    per = -(-int(vocab) // int(world))      # ceil division
    lo = min(int(vocab), int(rank) * per)
    hi = min(int(vocab), lo + per)
    return lo, hi


def owned_slice(host_array, rank, world):
    """``host_array[lo:hi]`` for this rank's owned row range."""
    lo, hi = row_range(host_array.shape[0], rank, world)
    return host_array[lo:hi], lo, hi


def local_mesh():
    """The process-local 1-D row mesh, or None when a single device (or
    a multi-process world, where cross-device layout is the kvstore
    transport's concern) makes sharding a no-op."""
    from ..kvstore_tpu import dist
    if dist.world_size() > 1:
        return None
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    with _LOCK:
        mesh = _MESH_CACHE.get(len(devs))
        if mesh is None:
            mesh = jax.sharding.Mesh(_np.asarray(devs), ("row",))
            _MESH_CACHE[len(devs)] = mesh
        return mesh


def table_sharding(mesh):
    """NamedSharding for a (vocab, dim) table: rows over the mesh."""
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("row", None))  # analyze: ok(sharding) embedding tables ride a dedicated single-axis 'row' mesh (local_mesh above), not the training mesh's named axes


def place_table(arr):
    """Lay a (vocab, dim) jax array out over the local row mesh (the
    identity when there is no mesh or the vocab does not divide)."""
    mesh = local_mesh()
    if mesh is None or arr.ndim < 2:
        return arr
    if arr.shape[0] % mesh.size != 0:
        return arr      # GSPMD wants even row tiles; lookup still works
    return jax.device_put(arr, table_sharding(mesh))


def account_bytes(key, nbytes):
    """Record ``key``'s resident embedding bytes (replaces any previous
    figure for the key) and refresh the ``embedding_hbm_bytes`` gauge."""
    with _LOCK:
        if nbytes:
            _HBM_BY_KEY[key] = int(nbytes)
        else:
            _HBM_BY_KEY.pop(key, None)
        EMBED_HBM.set(sum(_HBM_BY_KEY.values()))


def account_table_bytes(key, nbytes):
    """Record ``key``'s table WEIGHT bytes on this host (the slab for a
    partitioned table) and refresh ``embedding_table_bytes_per_host``."""
    with _LOCK:
        if nbytes:
            _TBL_BY_KEY[key] = int(nbytes)
        else:
            _TBL_BY_KEY.pop(key, None)
        EMBED_TBL_PER_HOST.set(sum(_TBL_BY_KEY.values()))


def process_row_mesh():
    """The cross-process 1-D 'dp' mesh partitioned tables ride: one
    device per process (dist.process_mesh), cached so equal meshes share
    program-cache entries."""
    from ..kvstore_tpu import dist
    key = ("proc", dist.world_size())
    with _LOCK:
        mesh = _MESH_CACHE.get(key)
        if mesh is None:
            mesh = _MESH_CACHE[key] = dist.process_mesh()
        return mesh


def partition_decision(vocab, dtype):
    """How a ShardedEmbedding table attaching to a kvstore should lay
    out across the process world (docs/EMBEDDING.md "Multi-host
    partitioning"):

    * ``("partition", world)`` — row-partition into ``world`` equal
      slabs (``row_range``; eligibility guarantees exact division, so
      the bounds equal the checkpoint shards' replicated-world bounds);
    * ``("replicate", slug)``  — stay replicated because the table is
      partition-INELIGIBLE; ``slug`` is the bounded
      ``kvstore_fallbacks`` reason (vocab not divisible by the world /
      non-f32 dtype);
    * ``("replicate", None)``  — partitioning is simply not in play
      (single process and not forced, or ``MXNET_EMBED_PARTITION=0``).

    ``MXNET_EMBED_PARTITION``: ``0`` never partitions, ``1`` forces the
    partitioned code path even in a single-process world (the slab is
    then the whole table — the tier-1 coverage mode), default (auto)
    partitions exactly when the world has more than one process."""
    mode = os.environ.get("MXNET_EMBED_PARTITION", "")
    if mode == "0":
        return "replicate", None
    from ..kvstore_tpu import dist
    world = dist.world_size()
    if world <= 1 and mode != "1":
        return "replicate", None
    if int(vocab) % world != 0:
        return "replicate", "embed_partition_vocab_indivisible"
    if _np.dtype(dtype) != _np.float32:
        return "replicate", "embed_partition_dtype"
    return "partition", world
