"""Compiled embedding lookup: ONE gather program per step.

The hot path (docs/EMBEDDING.md):

* index batches arrive with any shape/values; the flattened indices pad
  to the next power of two and ride as a RUNTIME argument, so ragged
  batches re-use the cached program — zero steady-state retraces, the
  same discipline as the serving bucket ladder (mx.decode);
* the program is one ``jnp.take``; under the local row mesh
  (sharding.py) the gather carries a sharding constraint and GSPMD
  lowers it to gather -> all-to-all/psum over ICI. Padding slots use the
  sentinel index ``vocab`` with ``mode='fill', fill_value=0`` — NOT
  clip: a clipped sentinel would fetch (and on the grad path corrupt)
  the last real row, the PR 6 paged-KV lesson;
* cache key: (vocab, dim, dtype, padded length, mesh size). Index
  VALUES never key anything.

``lookup()`` is the single entry for the gluon block and the symbol op,
so eager and compiled callers share one program cache.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as _np

from .. import telemetry as _telemetry
from . import sharding as _sharding

__all__ = ["lookup", "lookup_partitioned", "pad_length", "LOOKUPS",
           "LOOKUP_RETRACES"]

# one increment per compiled-lookup dispatch; with
# embedding_sparse_dispatches this is the dispatches-per-step witness
# tests/test_embedding.py reads (docs/OBSERVABILITY.md)
LOOKUPS = _telemetry.REGISTRY.counter(
    "embedding_lookups",
    "compiled embedding lookup dispatches", vital=True)
# trace-time-only witness: flat in the steady state across ragged
# index batches (pinned by tests/test_embedding.py)
LOOKUP_RETRACES = _telemetry.REGISTRY.counter(
    "embedding_lookup_retraces",
    "embedding lookup program (re)traces", vital=True)

_SITE = _telemetry.RetraceSite(LOOKUP_RETRACES, _telemetry.JIT_COMPILE_MS,
                               site="embedding_lookup")

_LOCK = threading.Lock()
_PROGRAMS = {}           # cache key -> jitted fn   (guarded by _LOCK)


def pad_length(n):
    """Next power of two >= n (>= 1): the ladder that keeps ragged
    batches on cached programs."""
    cap = 1
    while cap < n:
        cap <<= 1
    return cap


def _build(mesh):
    @jax.jit
    def _lookup(w, idx):
        _SITE.note()
        if mesh is not None:
            w = jax.lax.with_sharding_constraint(
                w, _sharding.table_sharding(mesh))
        # sentinel=vocab padding drops to zeros via fill, never row V-1
        return jnp.take(w, idx, axis=0, mode="fill", fill_value=0)

    return _lookup


def lookup(weight_jax, idx_host, out_shape=None):
    """Gather rows ``idx_host`` (any-shape int array-like) from the
    (vocab, dim) table ``weight_jax``. Returns a jax array shaped
    ``idx.shape + (dim,)`` (or ``out_shape`` when given).

    One compiled dispatch when the flat length is already a power of
    two; otherwise the unpad slice adds a second (cheap, shape-keyed)
    device op — size batches pow-2 to stay at one (docs/EMBEDDING.md).
    """
    vocab, _dim = weight_jax.shape
    idx = _np.asarray(idx_host)  # analyze: ok(hostsync) indices arrive on host by contract (data pipeline output)
    flat = idx.reshape(-1).astype(_np.int32)
    n = flat.shape[0]
    cap = pad_length(max(n, 1))
    if cap != n:
        flat = _np.concatenate(
            [flat, _np.full(cap - n, vocab, _np.int32)])
    mesh = _sharding.local_mesh()
    if mesh is not None and (mesh.size <= 1 or vocab % mesh.size):
        mesh = None
    # mesh is part of the cache key (jax.sharding.Mesh hashes by
    # devices+axis names), so a changed mesh never reuses a stale program
    key = (int(vocab), int(_dim), str(weight_jax.dtype), cap, mesh)
    with _LOCK:
        fn = _PROGRAMS.get(key)
        if fn is None:
            fn = _PROGRAMS[key] = _build(mesh)
    from ..executor import _count_dispatch
    _count_dispatch()
    LOOKUPS.inc()
    out = _SITE.timed(fn, weight_jax, jnp.asarray(flat))
    if mesh is not None:
        # the (n, dim) result is small next to the table: land it on the
        # default device so eager consumers (the dense tower, autograd)
        # never mix an 8-device output with single-device arrays — the
        # GSPMD win is the table-side gather, not the result placement
        out = jax.device_put(out, jax.devices()[0])
    if cap != n:
        out = out[:n]
    shape = tuple(idx.shape) + (weight_jax.shape[1],) \
        if out_shape is None else tuple(out_shape)
    return out.reshape(shape)


def _build_partitioned(mesh):
    """ONE GSPMD program for the pod-partitioned gather: the (vocab,
    dim) table is row-sharded over the process 'dp' mesh, the global
    index vector is 'dp'-sharded (each rank's slice is its own padded
    batch), and XLA lowers the cross-shard gather to the on-fabric
    all-to-all — all-to-all(indices) -> local gather -> all-to-all(rows)
    in one launch (docs/EMBEDDING.md)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    @jax.jit
    def _lookup(w, idx):
        _SITE.note()
        w = jax.lax.with_sharding_constraint(
            w, NamedSharding(mesh, P("dp", None)))
        out = jnp.take(w, idx, axis=0, mode="fill", fill_value=0)
        # each rank's addressable slice of the 'dp'-sharded result is
        # exactly its own batch's rows
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P("dp", None)))

    return _lookup


def lookup_partitioned(slab_jax, idx_host, lo, hi, vocab, out_shape=None):
    """Gather rows of a table row-partitioned ACROSS the process world:
    this rank owns rows ``[lo, hi)`` in ``slab_jax`` and every rank
    calls with its OWN batch (collective — all ranks must call once per
    step, SPMD order).

    GSPMD worlds (accelerator backends; also every single-process
    world, so tier-1 and ``MXNET_EMBED_PARTITION=1`` exercise this exact
    program): ONE jitted launch — the slab lifts metadata-only into the
    global row-sharded table and the gather's all-to-all happens inside
    the program. Host worlds (multi-process CPU backend): indices route
    to their owner ranks over ``dist.alltoall_bytes``, each owner runs
    the ONE compiled local gather on its slab, and rows route back —
    still one counted dispatch per rank per step.
    """
    from ..kvstore_tpu import dist
    idx = _np.asarray(idx_host)  # analyze: ok(hostsync) indices arrive on host by contract (data pipeline output)
    flat = idx.reshape(-1).astype(_np.int32)
    n = flat.shape[0]
    dim = slab_jax.shape[1]
    shape = tuple(idx.shape) + (dim,) if out_shape is None \
        else tuple(out_shape)
    world = dist.world_size()

    if dist.gspmd_supported():
        cap = pad_length(max(n, 1))
        if cap != n:
            flat = _np.concatenate(
                [flat, _np.full(cap - n, vocab, _np.int32)])
        mesh = _sharding.process_row_mesh()
        key = ("part", int(vocab), int(dim), str(slab_jax.dtype), cap,
               world, mesh)
        with _LOCK:
            fn = _PROGRAMS.get(key)
            if fn is None:
                fn = _PROGRAMS[key] = _build_partitioned(mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        w_g = jax.make_array_from_single_device_arrays(
            (vocab, dim), NamedSharding(mesh, P("dp", None)), [slab_jax])
        idx_g = jax.make_array_from_single_device_arrays(
            (world * cap,), NamedSharding(mesh, P("dp")),
            [jnp.asarray(flat)])
        from ..executor import _count_dispatch
        _count_dispatch()
        LOOKUPS.inc()
        if world > 1:
            # indices out + rows back, the fabric all-to-all payload
            _sharding.ALLTOALL_BYTES.inc(cap * 4 + cap * dim * 4)
        out = _SITE.timed(fn, w_g, idx_g)
        mine = out.addressable_data(0) if world > 1 else out
        if cap != n:
            mine = mine[:n]
        return mine.reshape(shape)

    # host transport: route each index to its owner rank, gather on the
    # owner's slab, route the rows back, undo the routing permutation
    per = hi - lo                    # equal slabs (partition eligibility)
    owner = _np.clip(flat // max(per, 1), 0, world - 1)
    order = _np.argsort(owner, kind="stable")
    counts = _np.bincount(owner, minlength=world)
    cuts = _np.cumsum(counts)[:-1]
    sends = _np.split(flat[order], cuts)
    payloads = [a.astype(_np.int32).tobytes() for a in sends]
    _sharding.ALLTOALL_BYTES.inc(sum(len(p) for p in payloads))
    got = dist.alltoall_bytes("emblookup", payloads)
    req = [_np.frombuffer(b, _np.int32) for b in got]
    sizes = [r.shape[0] for r in req]
    req_all = _np.concatenate(req) if req else _np.zeros(0, _np.int32)
    # slab-local ids; requests are owner-routed so they land in
    # [0, per) — anything else (corrupt id) hits the gather's fill
    rows = lookup(slab_jax, req_all - lo)
    rows_np = _np.asarray(rows, _np.float32)  # analyze: ok(hostsync) host transport return leg — the rows must cross the wire
    backs = _np.split(rows_np, _np.cumsum(sizes)[:-1])
    back_payloads = [b.tobytes() for b in backs]
    _sharding.ALLTOALL_BYTES.inc(sum(len(p) for p in back_payloads))
    mine = dist.alltoall_bytes("emblookup_rows", back_payloads)
    got_rows = _np.concatenate(
        [_np.frombuffer(b, _np.float32).reshape(-1, dim) for b in mine]) \
        if mine else _np.zeros((0, dim), _np.float32)
    out = _np.empty((n, dim), _np.float32)
    out[order] = got_rows
    return jnp.asarray(out).reshape(shape)
