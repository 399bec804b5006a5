"""SparseApplyEngine: the compiled row_sparse gradient pipeline.

One push of row_sparse gradients for one table runs as ONE jitted
program (docs/EMBEDDING.md):

    dedup/coalesce -> 2-bit-compress unique rows (error feedback)
        -> [cross-host reduce] -> lazy sparse-apply

extending the dense bucket engines (kvstore_fused.py, PR 2;
kvstore_tpu/engine.py, PR 7) to the row_sparse storage type the
reference kvstore treats as its native gradient format. Design points:

* **Runtime-vs-static split.** Index VALUES and row payloads are
  runtime arguments; only (table shape, per-stream padded capacities,
  optimizer signature, compression threshold) key the program cache.
  Capacities pad to the next power of two, so ragged non-zero counts
  re-use cached programs — zero steady-state retraces (the
  ``embedding_sparse_retraces`` witness).
* **In-program coalesce.** Duplicate indices merge by a stable
  sort + segment-sum whose per-group addition order equals the eager
  ``_coalesce_rsp`` (host ``np.unique``) order, so the eager path stays
  a bit-for-bit parity oracle. Padding uses the sentinel index
  ``vocab`` with gather ``mode='fill'(0)`` / scatter ``mode='drop'`` —
  never clip (the PR 6 paged-KV out-of-bounds lesson).
* **Lazy updates.** The apply touches ONLY the gradient's rows, with
  the exact op sequence of the eager lazy updates in
  ndarray/sparse.py (``sparse_sgd_update`` / ``sparse_adagrad_update``
  / ``sparse_group_adagrad_update``), selected by
  ``Optimizer._fused_sparse_sig()``.
* **Residual ownership.** Per-table error-feedback residuals are
  donated (vocab, dim) arrays owned by the engine exactly like the
  dense engine's flat buffers: seeded from
  ``kv._compression_residuals[(key, "rsp")]``, spilled back there by
  ``spill_residuals()`` (checkpoint capture and routing changes call
  ``kv._sync_engine()`` first, same contract as the dense engine).
* **Cross-host.** In a multi-process world (``kvstore='tpu'``) the
  engine mirrors the PR 7 host transport: a local program coalesces +
  quantizes, the (indices, rows) payload rides one
  ``dist.allgather_bytes``, and a second program coalesces the union
  in deterministic rank order and applies. Compression runs BEFORE the
  wire — that is what it is for. A single GSPMD program spanning the
  process mesh (like the dense engine's accelerator path) is future
  work; the host transport keeps every rank's replicated table
  bit-identical, which is the invariant checkpointing relies on.
"""
from __future__ import annotations

import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from ..ndarray import NDArray
from .. import telemetry as _telemetry
from ..kvstore_fused import two_bit_quantize
from . import sharding as _sharding
from .lookup import pad_length

__all__ = ["SparseApplyEngine", "SPARSE_DISPATCHES", "SPARSE_RETRACES"]

# compiled sparse-apply program launches (1 per push single-process,
# 2 on the multi-process host transport); with embedding_lookups this
# is the dispatches-per-step witness tests/test_embedding.py reads
SPARSE_DISPATCHES = _telemetry.REGISTRY.counter(
    "embedding_sparse_dispatches",
    "compiled sparse-apply program dispatches", vital=True)
# trace-time-only: flat in the steady state across ragged nnz counts
SPARSE_RETRACES = _telemetry.REGISTRY.counter(
    "embedding_sparse_retraces",
    "compiled sparse-apply program (re)traces", vital=True)

_SITE = _telemetry.RetraceSite(SPARSE_RETRACES, _telemetry.JIT_COMPILE_MS,
                               site="embedding_sparse")

_RSP_RES = "rsp"      # device slot in kv._compression_residuals keys


def _coalesce(idx, rows, vocab):
    """In-program dedup: stable-sorted unique indices compacted to the
    low slots (sentinel ``vocab`` elsewhere) + per-index row sums.
    Stable sort keeps duplicate groups in original order, so the
    segment sums add in the same order as the eager host coalesce."""
    order = jnp.argsort(idx)                       # jax argsort: stable
    si = idx[order]
    sr = rows[order]
    head = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), si[1:] != si[:-1]])
    seg = jnp.cumsum(head) - 1
    uidx = jnp.full(si.shape, vocab, si.dtype).at[seg].set(si)
    urows = jax.ops.segment_sum(sr, seg, num_segments=si.shape[0])
    return uidx, urows


def _sparse_apply(sig, w, state, uidx, g, lr, wd, rescale):
    """The lazy optimizer apply on coalesced (uidx, g): same op
    sequence as the eager updates in ndarray/sparse.py restricted to
    the touched rows. Sentinel slots compute garbage-free zeros and
    drop at the scatter."""
    kind, hyper, clip = sig
    g = g * rescale
    if clip is not None and clip >= 0:
        g = jnp.clip(g, -clip, clip)
    wr = jnp.take(w, uidx, axis=0, mode="fill", fill_value=0)
    if kind == "sgd":
        g = g + wd * wr
        if state is not None:            # hyper == momentum != 0
            mr = hyper * jnp.take(state, uidx, axis=0, mode="fill",
                                  fill_value=0) - lr * g
            state = state.at[uidx].set(mr, mode="drop")
            new_wr = wr + mr
        else:
            new_wr = wr - lr * g
    elif kind == "adagrad":              # hyper == epsilon
        hr = jnp.take(state, uidx, axis=0, mode="fill",
                      fill_value=0) + jnp.square(g)
        state = state.at[uidx].set(hr, mode="drop")
        new_wr = wr - lr * (g / jnp.sqrt(hr + hyper) + wd * wr)
    elif kind == "group_adagrad":        # hyper == epsilon, no wd
        hr = jnp.take(state, uidx, axis=0, mode="fill", fill_value=0) \
            + jnp.mean(jnp.square(g), axis=1, keepdims=True)
        state = state.at[uidx].set(hr, mode="drop")
        new_wr = wr - lr * g / jnp.sqrt(hr + hyper)
    else:
        raise MXNetError("unknown sparse-apply signature %r" % (kind,))
    w = w.at[uidx].set(new_wr, mode="drop")
    return w, state


class SparseApplyEngine:
    """Per-kvstore compiled row_sparse push engine (one instance per
    store, one program per table signature). ``cross_host=True`` (the
    ``kvstore='tpu'`` store) routes through the host transport when the
    dist world has more than one process."""

    def __init__(self, kv, cross_host=False):
        self._kv = kv
        self._cross_host = cross_host
        self._programs = {}
        self._residuals = {}           # key -> donated (vocab, dim) array
        self._lock = threading.Lock()

    # -- eligibility ----------------------------------------------------
    def ineligible_reason(self, key, vlist):
        """None when this push may take the compiled sparse path, else a
        BOUNDED reason slug (a ``kvstore_fallbacks`` label — keep key
        names and shapes out). Narrower than the dense engine's single
        ``sparse_value``: unsupported OPTIMIZER and ineligible DTYPE
        fall back for different reasons and warn separately."""
        from ..ndarray.sparse import RowSparseNDArray
        from ..optimizer import Updater
        if not all(isinstance(v, RowSparseNDArray) for v in vlist):
            return "sparse_mixed_stype"
        updater = self._kv._updater
        if updater is None:
            return "sparse_assign_push"
        if not isinstance(updater, Updater):
            return "sparse_custom_updater"
        opt = updater.optimizer
        sig = getattr(opt, "_fused_sparse_sig", lambda: None)()
        if sig is None:
            return ("sparse_unsupported_optimizer:%s"
                    % type(opt).__name__)
        stored = self._kv._store.get(key)
        if stored is None:
            return "sparse_key_not_initialized"
        if stored.dtype != _np.float32 \
                or any(v.dtype != _np.float32 for v in vlist):
            return "sparse_ineligible_dtype"
        part = self._kv._partitioned.get(key)
        expect = (part[2], stored.shape[1]) if part is not None \
            else tuple(stored.shape)   # gradients carry the FULL vocab
        if len(stored.shape) != 2 \
                or any(tuple(v.shape) != expect for v in vlist):
            return "sparse_shape_mismatch"
        return None

    # -- residual ownership (mirrors FusedBucketEngine flat buffers) ----
    def _residual(self, key, vocab, dim):
        res = self._residuals.get(key)
        if res is None:
            seed = self._kv._compression_residuals.get((key, _RSP_RES))
            if seed is not None and tuple(seed.shape) == (vocab, dim):
                res = jnp.array(seed._data)      # copy: we will donate
            else:
                res = jnp.zeros((vocab, dim), jnp.float32)
            self._residuals[key] = res
        return res

    def spill_residuals(self):
        """Hand residual ownership back to the per-key dict (checkpoint
        capture, routing changes — kv._sync_engine's contract)."""
        with self._lock:
            for key, arr in self._residuals.items():
                self._kv._compression_residuals[(key, _RSP_RES)] = \
                    NDArray(arr)
            self._residuals.clear()

    # -- dispatch -------------------------------------------------------
    def push(self, key, vlist, priority=0):
        """Dispatch one table's row_sparse push through the compiled
        pipeline (the caller has already checked eligibility)."""
        del priority                       # per-table: nothing to order
        from ..kvstore import _updater_key
        kv = self._kv
        updater = kv._updater
        opt = updater.optimizer
        uk = _updater_key(key)
        stored = kv._store[key]
        vocab, dim = stored.shape
        part = kv._partitioned.get(key)
        if part is not None:
            # stored is this rank's row slab; sentinels and coalesce
            # bounds use the GLOBAL vocab from the partition registry
            vocab = part[2]
        if uk not in updater.states:
            updater.states[uk] = opt.create_state_multi_precision(
                uk, stored)
            updater.states_synced[uk] = True
        state_nd = updater.states[uk]
        opt._update_count(uk)
        lr = _np.float32(opt._get_lr(uk))
        wd = _np.float32(opt._get_wd(uk))
        rescale = _np.float32(opt.rescale_grad)
        sig = opt._fused_sparse_sig()
        comp = kv._compression
        threshold = float(comp.threshold) if comp is not None else None

        if part is not None:
            with self._lock:
                new = self._dispatch_partition(
                    key, sig, stored, state_nd, threshold, part, dim,
                    vlist, lr, wd, rescale)
            new_w, new_state = new
            stored._set_data(new_w)
            if state_nd is not None:
                state_nd._set_data(new_state)
            nbytes = stored._data.nbytes \
                + (state_nd._data.nbytes if state_nd is not None else 0) \
                + (self._residuals[key].nbytes
                   if key in self._residuals else 0)
            _sharding.account_bytes(key, nbytes)
            _sharding.account_table_bytes(key, stored._data.nbytes)
            return

        idxs, rowss, caps = [], [], []
        for v in vlist:
            n = int(v._sp_indices.shape[0])
            cap = pad_length(max(n, 1))
            idx = v._sp_indices.astype(jnp.int32)
            rows = v._sp_data.astype(jnp.float32)
            if cap != n:
                idx = jnp.concatenate(
                    [idx, jnp.full((cap - n,), vocab, jnp.int32)])
                rows = jnp.concatenate(
                    [rows, jnp.zeros((cap - n, dim), jnp.float32)])
            idxs.append(idx)
            rowss.append(rows)
            caps.append(cap)

        if len(stored._data.sharding.device_set) > 1:
            # the table is row-sharded over the local mesh while the
            # gradient streams arrive committed to the default device
            # (lookup lands its output there); replicate the small
            # streams onto the table's mesh or jit rejects the mix of
            # device sets
            mesh = _sharding.local_mesh()
            if mesh is not None:
                rep = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())
                idxs = [jax.device_put(i, rep) for i in idxs]
                rowss = [jax.device_put(r, rep) for r in rowss]

        from ..kvstore_tpu import dist
        world = dist.world_size() if self._cross_host else 1
        with self._lock:
            if world > 1:
                new = self._dispatch_host(key, sig, stored, state_nd,
                                          threshold, vocab, dim,
                                          tuple(caps), idxs, rowss,
                                          lr, wd, rescale)
            else:
                new = self._dispatch_local(key, sig, stored, state_nd,
                                           threshold, vocab, dim,
                                           tuple(caps), idxs, rowss,
                                           lr, wd, rescale)
        new_w, new_state = new
        stored._set_data(new_w)
        if state_nd is not None:
            state_nd._set_data(new_state)
        nbytes = stored._data.nbytes \
            + (state_nd._data.nbytes if state_nd is not None else 0) \
            + (self._residuals[key].nbytes
               if key in self._residuals else 0)
        _sharding.account_bytes(key, nbytes)

    def _program(self, cache_key, builder):
        fn = self._programs.get(cache_key)
        if fn is None:
            fn = self._programs[cache_key] = builder()
        return fn

    def _dispatch_local(self, key, sig, stored, state_nd, threshold,
                        vocab, dim, caps, idxs, rowss, lr, wd, rescale):
        """Single-process: the whole pipeline is ONE donated program."""
        has_state = state_nd is not None
        fn = self._program(
            ("local", sig, caps, vocab, dim, threshold, has_state),
            lambda: _build_local(sig, vocab, threshold, has_state))
        res_in = self._residual(key, vocab, dim) \
            if threshold is not None else ()
        from ..executor import _count_dispatch
        _count_dispatch()
        SPARSE_DISPATCHES.inc()
        out = _SITE.timed(
            fn, stored._data, state_nd._data if has_state else (),
            res_in, tuple(idxs), tuple(rowss), lr, wd,
            jnp.float32(rescale))
        new_w, new_state, new_res = out
        if threshold is not None:
            self._residuals[key] = new_res
        return new_w, (new_state if has_state else None)

    def _dispatch_partition(self, key, sig, stored, state_nd, threshold,
                            part, dim, vlist, lr, wd, rescale):
        """Pod-partitioned apply: ``stored`` is this rank's row slab of
        the full (vocab, dim) table and the incoming gradients carry
        GLOBAL indices. ONE cross-host sparse launch per push
        (docs/EMBEDDING.md) instead of the replicated host transport's
        two.

        GSPMD worlds (and every single-process world — tier-1 coverage
        via ``MXNET_EMBED_PARTITION=1``): one jitted program over the
        process 'dp' mesh coalesces the global union and applies to the
        row-sharded table; XLA lowers the index/row exchange to the
        fabric all-to-all. Host worlds (multi-process CPU backend): raw
        (index, row) pairs route to their owner ranks over
        ``dist.alltoall_bytes`` and each owner runs the ONE local
        coalesce->quantize->apply program on its slab.

        Note the error-feedback difference from the replicated host
        transport: compression quantizes ONCE on the owner-side
        coalesced union against the slab residual (exact error
        feedback), not per-rank before the wire — the wire carries raw
        gradients routed by ownership, already 1/W of the replicated
        all-to-all-gather traffic."""
        from ..kvstore_tpu import dist
        lo, hi, vocab = part
        slab_rows = hi - lo
        world = dist.world_size()
        if dist.gspmd_supported():
            return self._dispatch_partition_gspmd(
                key, sig, stored, state_nd, threshold, part, dim, vlist,
                lr, wd, rescale, world)
        idx_np = _np.concatenate(
            [_np.asarray(v._sp_indices) for v in vlist]).astype(_np.int32)  # analyze: ok(hostsync) host transport: owner routing reads the indices on host by design
        rows_np = _np.concatenate(
            [_np.asarray(v._sp_data).reshape(-1, dim)  # analyze: ok(hostsync) host transport payload — rows must cross the wire anyway
             for v in vlist]).astype(_np.float32)
        owner = _np.clip(idx_np // max(slab_rows, 1), 0, world - 1)
        order = _np.argsort(owner, kind="stable")
        counts = _np.bincount(owner, minlength=world)
        cuts = _np.cumsum(counts)[:-1]
        si, sr = idx_np[order], rows_np[order]
        payloads = [i.tobytes() + r.tobytes()
                    for i, r in zip(_np.split(si, cuts),
                                    _np.split(sr, cuts))]
        _sharding.ALLTOALL_BYTES.inc(sum(len(p) for p in payloads))
        got = dist.alltoall_bytes("embgrad", payloads)
        all_i, all_r = [], []
        for buf in got:
            nn = len(buf) // (4 + 4 * dim)
            all_i.append(_np.frombuffer(buf[:4 * nn], _np.int32))
            all_r.append(_np.frombuffer(buf[4 * nn:], _np.float32)
                         .reshape(nn, dim))
        idx_g = _np.concatenate(all_i) - lo          # slab-local ids
        rows_g = _np.concatenate(all_r)
        nn = idx_g.shape[0]
        cap = pad_length(max(nn, 1))
        if cap != nn:
            idx_g = _np.concatenate(
                [idx_g, _np.full(cap - nn, slab_rows, _np.int32)])
            rows_g = _np.concatenate(
                [rows_g, _np.zeros((cap - nn, dim), _np.float32)])
        # the owned union runs the SAME single-launch local program as a
        # single-process table, on the slab (sentinel = slab_rows)
        return self._dispatch_local(
            key, sig, stored, state_nd, threshold, slab_rows, dim,
            (cap,), [jnp.asarray(idx_g)], [jnp.asarray(rows_g)], lr, wd,
            rescale)

    def _dispatch_partition_gspmd(self, key, sig, stored, state_nd,
                                  threshold, part, dim, vlist, lr, wd,
                                  rescale, world):
        """ONE GSPMD launch: every rank's padded (global-index, row)
        stream lifts into 'dp'-sharded global arrays, the slab/state/
        residual lift into row-sharded (vocab, dim) tables, and the
        program coalesces the global union, quantizes against the
        row-sharded residual, and lazily applies — XLA inserts the
        all-to-alls."""
        from ..kvstore_tpu import dist
        from ..executor import _count_dispatch
        from jax.sharding import NamedSharding, PartitionSpec as P
        lo, hi, vocab = part
        has_state = state_nd is not None
        mesh = _sharding.process_row_mesh()
        idx = jnp.concatenate([v._sp_indices.astype(jnp.int32)
                               for v in vlist]) if len(vlist) > 1 \
            else vlist[0]._sp_indices.astype(jnp.int32)
        rows = jnp.concatenate([v._sp_data.astype(jnp.float32)
                                for v in vlist]) if len(vlist) > 1 \
            else vlist[0]._sp_data.astype(jnp.float32)
        n = int(idx.shape[0])
        cap = pad_length(max(n, 1))
        if world > 1:
            # ragged nnz: agree on the pow2 pad rung so every rank lifts
            # the same global shape (one tiny host exchange; the ladder
            # keeps it steady-state stable and the LAUNCH count at one)
            caps = dist.allgather_bytes(
                "embcap", _np.int32(cap).tobytes())
            cap = max(int(_np.frombuffer(b, _np.int32)[0]) for b in caps)
        if cap != n:
            idx = jnp.concatenate(
                [idx, jnp.full((cap - n,), vocab, jnp.int32)])
            rows = jnp.concatenate(
                [rows, jnp.zeros((cap - n, dim), jnp.float32)])
        res_in = self._residual(key, hi - lo, dim) \
            if threshold is not None else ()
        fn = self._program(
            ("part-gspmd", sig, cap, vocab, dim, world, threshold,
             has_state, mesh),
            lambda: _build_partition_gspmd(sig, vocab, threshold,
                                           has_state, mesh))

        def lift_rows(x):
            return jax.make_array_from_single_device_arrays(
                (vocab,) + tuple(x.shape[1:]),
                NamedSharding(mesh, P("dp") if x.ndim == 1
                              else P("dp", *([None] * (x.ndim - 1)))),
                [x])

        def lift_stream(x):
            return jax.make_array_from_single_device_arrays(
                (world * cap,) + tuple(x.shape[1:]),
                NamedSharding(mesh, P("dp") if x.ndim == 1
                              else P("dp", *([None] * (x.ndim - 1)))),
                [x])

        w_g = lift_rows(stored._data)
        st_g = lift_rows(state_nd._data) if has_state else ()
        res_g = lift_rows(res_in) if threshold is not None else ()
        idx_g = lift_stream(idx)
        rows_g = lift_stream(rows)
        if world > 1:
            _sharding.ALLTOALL_BYTES.inc(cap * 4 + cap * dim * 4)
        _count_dispatch()
        SPARSE_DISPATCHES.inc()
        new_w, new_state, new_res = _SITE.timed(
            fn, w_g, st_g, res_g, idx_g, rows_g, lr, wd,
            jnp.float32(rescale))

        def unlift(x):
            return x.addressable_data(0) if world > 1 else x

        if threshold is not None:
            self._residuals[key] = unlift(new_res)
        return unlift(new_w), (unlift(new_state) if has_state else None)

    def _dispatch_host(self, key, sig, stored, state_nd, threshold,
                       vocab, dim, caps, idxs, rowss, lr, wd, rescale):
        """Multi-process host transport (PR 7 pattern): local
        coalesce+quantize program -> one allgather of the (indices,
        rows) payload -> global coalesce+apply program, deterministic in
        rank order so every rank's replicated table stays
        bit-identical."""
        from ..kvstore_tpu import dist
        from ..executor import _count_dispatch
        has_state = state_nd is not None
        fn_local = self._program(
            ("pre", caps, vocab, dim, threshold),
            lambda: _build_pre(vocab, threshold))
        res_in = self._residual(key, vocab, dim) \
            if threshold is not None else ()
        _count_dispatch()
        SPARSE_DISPATCHES.inc()
        uidx, g, new_res = _SITE.timed(
            fn_local, res_in, tuple(idxs), tuple(rowss))
        if threshold is not None:
            self._residuals[key] = new_res
        # the payload fetch + allgather are the transport's ONE
        # synchronization point per push, the documented host-transport
        # cost (docs/EMBEDDING.md) — the apply below is async again
        head = _np.asarray(uidx, _np.int32)  # analyze: ok(hostsync) host transport payload fetch — the one sync per push
        body = _np.asarray(g, _np.float32)
        payload = head.tobytes() + body.tobytes()
        gathered = dist.allgather_bytes("embpush", payload)
        all_idx, all_rows = [], []
        for buf in gathered:
            n = len(buf) // (4 + 4 * dim)
            all_idx.append(_np.frombuffer(buf[:4 * n], _np.int32))
            all_rows.append(_np.frombuffer(buf[4 * n:], _np.float32)
                            .reshape(n, dim))
        idx_g = _np.concatenate(all_idx)
        rows_g = _np.concatenate(all_rows)
        n = idx_g.shape[0]
        cap_g = pad_length(max(n, 1))
        if cap_g != n:
            idx_g = _np.concatenate(
                [idx_g, _np.full(cap_g - n, vocab, _np.int32)])
            rows_g = _np.concatenate(
                [rows_g, _np.zeros((cap_g - n, dim), _np.float32)])
        fn_apply = self._program(
            ("apply", sig, cap_g, vocab, dim, has_state),
            lambda: _build_apply_only(sig, vocab, has_state))
        _count_dispatch()
        SPARSE_DISPATCHES.inc()
        new_w, new_state = _SITE.timed(
            fn_apply, stored._data,
            state_nd._data if has_state else (),
            jnp.asarray(idx_g), jnp.asarray(rows_g), lr, wd,
            jnp.float32(rescale))
        return new_w, (new_state if has_state else None)


def _build_local(sig, vocab, threshold, has_state):
    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, state, residual, idxs, rowss, lr, wd, rescale):
        _SITE.note()
        idx = jnp.concatenate(idxs) if len(idxs) > 1 else idxs[0]
        rows = jnp.concatenate(rowss) if len(rowss) > 1 else rowss[0]
        uidx, g = _coalesce(idx, rows, vocab)
        new_res = ()
        if threshold is not None:
            res_rows = jnp.take(residual, uidx, axis=0, mode="fill",
                                fill_value=0)
            g, new_rows = two_bit_quantize(res_rows, g, threshold)
            new_res = residual.at[uidx].set(new_rows, mode="drop")
        new_w, new_state = _sparse_apply(
            sig, w, state if has_state else None, uidx, g, lr, wd,
            rescale)
        return new_w, (new_state if has_state else ()), new_res

    return step


def _build_partition_gspmd(sig, vocab, threshold, has_state, mesh):
    """ONE GSPMD program for the pod-partitioned sparse apply: table /
    state / residual arrive row-sharded over the process 'dp' mesh,
    gradient streams arrive 'dp'-sharded (each rank's slice is its own
    padded contribution), and the global coalesce -> quantize -> lazy
    apply runs as a single launch whose cross-shard gathers/scatters
    XLA lowers to the fabric all-to-all."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    def _rows_spec(x):
        return NamedSharding(mesh, P("dp") if x.ndim == 1
                             else P("dp", *([None] * (x.ndim - 1))))

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, state, residual, idx, rows, lr, wd, rescale):
        _SITE.note()
        w = jax.lax.with_sharding_constraint(w, _rows_spec(w))
        if has_state:
            state = jax.lax.with_sharding_constraint(
                state, _rows_spec(state))
        uidx, g = _coalesce(idx, rows, vocab)
        new_res = ()
        if threshold is not None:
            residual = jax.lax.with_sharding_constraint(
                residual, _rows_spec(residual))
            res_rows = jnp.take(residual, uidx, axis=0, mode="fill",
                                fill_value=0)
            g, new_rows = two_bit_quantize(res_rows, g, threshold)
            new_res = residual.at[uidx].set(new_rows, mode="drop")
            new_res = jax.lax.with_sharding_constraint(
                new_res, _rows_spec(new_res))
        new_w, new_state = _sparse_apply(
            sig, w, state if has_state else None, uidx, g, lr, wd,
            rescale)
        new_w = jax.lax.with_sharding_constraint(new_w, _rows_spec(new_w))
        if has_state:
            new_state = jax.lax.with_sharding_constraint(
                new_state, _rows_spec(new_state))
        return new_w, (new_state if has_state else ()), new_res

    return step


def _build_pre(vocab, threshold):
    """Local half of the host transport: coalesce (+ quantize against
    the host-local residual) before anything crosses the wire."""
    @partial(jax.jit, donate_argnums=(0,))
    def pre(residual, idxs, rowss):
        _SITE.note()
        idx = jnp.concatenate(idxs) if len(idxs) > 1 else idxs[0]
        rows = jnp.concatenate(rowss) if len(rowss) > 1 else rowss[0]
        uidx, g = _coalesce(idx, rows, vocab)
        new_res = ()
        if threshold is not None:
            res_rows = jnp.take(residual, uidx, axis=0, mode="fill",
                                fill_value=0)
            g, new_rows = two_bit_quantize(res_rows, g, threshold)
            new_res = residual.at[uidx].set(new_rows, mode="drop")
        return uidx, g, new_res

    return pre


def _build_apply_only(sig, vocab, has_state):
    """Global half of the host transport: coalesce the rank-ordered
    union (already quantized per host) and apply."""
    @partial(jax.jit, donate_argnums=(0, 1))
    def apply_(w, state, idx, rows, lr, wd, rescale):
        _SITE.note()
        uidx, g = _coalesce(idx, rows, vocab)
        new_w, new_state = _sparse_apply(
            sig, w, state if has_state else None, uidx, g, lr, wd,
            rescale)
        return new_w, (new_state if has_state else ())

    return apply_
