"""Asynchronous parameter-server kvstore (``dist_async``).

Reference parity: src/kvstore/kvstore_dist_server.h:262-300 — in async
mode the server applies every worker push to the stored value
IMMEDIATELY (Hogwild-style, no per-key barrier counting pushes from all
workers), and workers run free: a fast worker's pushes and pulls never
wait for a slow one. This is a genuinely different capability from the
collective ``dist_sync`` (kvstore_dist.py): collectives are barriers by
construction, so async semantics need real server state. The TPU-native
shape of that state is a host-side service — gradients are small relative
to activations, DCN-bound either way, and the server never touches an
accelerator — so the server here is a threaded TCP service over
length-prefixed pickles with one lock per key:

* ``push``  — decompress if needed, then apply under the key's lock:
  ``updater(key, grad, stored)`` when an optimizer/updater is installed
  (the reference's optimizer-on-server, ``set_optimizer``), else
  ``stored += grad`` (the reference's AssignOrPlus aggregation).
* ``pull``  — return the CURRENT value; no wait for other workers
  (polls briefly only until the key is first initialized).
* ``init``  — first writer wins (idempotent across workers; reference
  kvstore_dist.h:181-197 has worker 0 push init).
* ``barrier`` — explicit Postoffice-style barrier for the rare code that
  wants one (init fences, shutdown); never used by push/pull.

Topology (reference DMLC names): ``tools/launch.py -n W -s S`` spawns S
server processes (DMLC_ROLE=server, kvstore_server.py) on
DMLC_PS_ROOT_PORT..+S-1 and W free-running workers; keys shard across
servers by stable hash (the reference's EncodeDefaultKey ring). With no
launcher (single process, DMLC_NUM_SERVER unset) the store spawns one
in-process daemon server — ``mx.kv.create('dist_async')`` then works
standalone with the same immediate-apply semantics.
"""
from __future__ import annotations

import hmac
import os
import pickle
import secrets as _secrets
import socket
import socketserver
import struct
import threading
import time

import numpy as _np

from .base import MXNetError
from .kvstore import KVStore, _key_value, _updater_key

__all__ = ["KVStoreDistAsync", "ParamServer", "serve_forever"]

_HDR = struct.Struct(">Q")
_MAC_BYTES = 32  # HMAC-SHA256


def _job_secret():
    """Per-job wire secret. launch.py generates one and exports
    MXTPU_PS_SECRET to every worker/server; standalone mode generates a
    process-local one. The wire is pickle, so every frame carries an
    HMAC-SHA256 over the payload — a peer without the secret cannot get
    a frame deserialized (ADVICE r4: pickle over TCP is an arbitrary-
    code-execution surface without authentication)."""
    return os.environ.get("MXTPU_PS_SECRET", "").encode()


def _mac(secret, payload):
    return hmac.new(secret, payload, "sha256").digest()


def _send_msg(sock, obj, secret=b""):
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_HDR.pack(len(payload)) + _mac(secret, payload) + payload)


def _recv_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_msg(sock, secret=b""):
    (n,) = _HDR.unpack(_recv_exact(sock, _HDR.size))
    mac = _recv_exact(sock, _MAC_BYTES)
    payload = _recv_exact(sock, n)
    if not hmac.compare_digest(mac, _mac(secret, payload)):
        # authentication failure: never unpickle the payload
        raise ConnectionError("bad frame MAC (wrong or missing "
                              "MXTPU_PS_SECRET)")
    return pickle.loads(payload)


class _App:
    """Per-app (per KVStore instance) server state — the analog of a
    ps-lite customer id: each ``mx.kv.create('dist_async')`` gets its own
    key space, updater, compression config, and barrier."""

    def __init__(self):
        self.store = {}             # key -> np.ndarray (current value)
        self.locks = {}             # key -> threading.Lock
        self.updater = None
        self.compression = None
        self.barrier_gen = 0
        self.barrier_count = 0
        self.barrier_cv = threading.Condition()
        self.push_counts = {}       # key -> applied pushes (observability)
        # at-most-once RPC: (worker_rank) -> (last_seq, last_response).
        # A client only ever retransmits its LAST request (synchronous
        # protocol), so caching one response per worker makes every
        # non-idempotent op (push under an updater) safe across
        # connection resets.
        self.last_rpc = {}
        # barrier needs entry-time dedupe too: its response is only
        # cached AFTER release, so a retransmit of a still-blocked
        # barrier must not count twice. worker -> (seq, gen at entry).
        self.barrier_entered = {}


class ParamServer:
    """Server state + request handling (one instance per server process
    or per in-process daemon thread)."""

    def __init__(self, num_workers):
        self._num_workers = int(num_workers)
        self._apps = {}
        self._meta_lock = threading.Lock()
        # bind every dependency a request handler needs NOW (constructed
        # on a thread where importing is safe); handler threads must
        # never import — they can run while another thread is inside
        # ``import mxnet_tpu`` and would deadlock on the import lock
        from . import optimizer as _opt
        from .ndarray import NDArray as _NDArray
        from .parallel.compression import TwoBitCompressor as _TwoBit
        import jax.numpy as _jnp
        self._mod_opt = _opt
        self._NDArray = _NDArray
        self._TwoBit = _TwoBit
        self._jnp = _jnp

    # ------------------------------------------------------------------
    def _app(self, app_id):
        with self._meta_lock:
            app = self._apps.get(app_id)
            if app is None:
                app = self._apps[app_id] = _App()
            return app

    def _lock_for(self, app, key):
        with self._meta_lock:
            lk = app.locks.get(key)
            if lk is None:
                lk = app.locks[key] = threading.Lock()
            return lk

    def _decompress(self, app, wire):
        kind, packed, shape, dtype = wire
        if kind != "2bit":
            raise MXNetError("unknown wire compression %r" % kind)
        if app.compression is None:
            raise MXNetError("server has no compression configured")
        arr = app.compression.decompress(
            _np.frombuffer(packed, _np.uint8), tuple(shape), dtype)
        return _np.asarray(arr, dtype)

    def _apply(self, app, key, grad):
        """The async core: apply THIS push now, under only this key's
        lock (kvstore_dist_server.h async mode — no merge buffer, no
        push counting)."""
        lk = self._lock_for(app, key)
        with lk:
            stored = app.store.get(key)
            if stored is None:
                raise MXNetError("push to uninitialized key %r" % key)
            if app.updater is not None:
                NDArray, jnp = self._NDArray, self._jnp
                w = NDArray(jnp.asarray(stored))
                app.updater(_updater_key(key), NDArray(jnp.asarray(grad)),
                            w)
                app.store[key] = _np.asarray(w.asnumpy(), stored.dtype)
            else:
                app.store[key] = stored + grad.astype(stored.dtype)
            app.push_counts[key] = app.push_counts.get(key, 0) + 1

    # ------------------------------------------------------------------
    def handle(self, msg):
        op = msg["op"]
        app = self._app(msg.get("app", 0))
        wkr, seq = msg.get("wkr"), msg.get("seq")
        if wkr is not None and seq is not None:
            with self._meta_lock:
                last = app.last_rpc.get(wkr)
            if last is not None and last[0] == seq:
                return last[1]          # retransmit of the last request
            resp = self._handle_op(op, app, msg)
            if not resp.get("stop"):
                with self._meta_lock:
                    app.last_rpc[wkr] = (seq, resp)
            return resp
        return self._handle_op(op, app, msg)

    def _handle_op(self, op, app, msg):
        if op == "init":
            key, val = msg["key"], msg["value"]
            lk = self._lock_for(app, key)
            with lk:
                if key not in app.store:       # first writer wins
                    app.store[key] = _np.asarray(val)
            return {"ok": True}
        if op == "push":
            grad = msg["value"]
            if isinstance(grad, tuple):
                grad = self._decompress(app, grad)
            self._apply(app, msg["key"], grad)
            return {"ok": True}
        if op == "pull_rows":
            key = msg["key"]
            rows = _np.asarray(msg["rows"], _np.int64)
            lk = self._lock_for(app, key)
            with lk:
                val = app.store.get(key)
                if val is None:
                    return {"ok": False,
                            "error": "key %r not initialized" % (key,)}
                return {"ok": True, "value": val[rows], "rows": rows}
        if op == "pull":
            key = msg["key"]
            deadline = time.time() + msg.get("timeout", 60.0)
            while True:
                lk = self._lock_for(app, key)
                with lk:
                    val = app.store.get(key)
                    if val is not None:
                        return {"ok": True, "value": val,
                                "pushes": app.push_counts.get(key, 0)}
                if time.time() > deadline:
                    return {"ok": False,
                            "error": "key %r not initialized" % (key,)}
                time.sleep(0.01)
        if op == "set_optimizer":
            optimizer = pickle.loads(msg["optimizer"])
            app.updater = self._mod_opt.get_updater(optimizer)
            return {"ok": True}
        if op == "set_gradient_compression":
            app.compression = self._TwoBit(
                threshold=float(msg["params"].get("threshold", 0.5)))
            return {"ok": True}
        if op == "barrier":
            n = msg.get("count", self._num_workers)
            wkr, seq = msg.get("wkr"), msg.get("seq")
            with app.barrier_cv:
                entered = app.barrier_entered.get(wkr)
                if entered is not None and entered[0] == seq:
                    gen = entered[1]       # retransmit: already counted
                else:
                    gen = app.barrier_gen
                    app.barrier_entered[wkr] = (seq, gen)
                    app.barrier_count += 1
                if app.barrier_count >= n:
                    app.barrier_gen += 1
                    app.barrier_count = 0
                    app.barrier_cv.notify_all()
                elif app.barrier_gen == gen:
                    while app.barrier_gen == gen:
                        if not app.barrier_cv.wait(timeout=120):
                            # roll this worker back OUT of the barrier so a
                            # later retry re-enters cleanly instead of
                            # double-counting (ADVICE r4); without this the
                            # barrier could release with a worker absent.
                            if app.barrier_gen == gen:
                                app.barrier_count -= 1
                                app.barrier_entered.pop(wkr, None)
                            return {"ok": False, "error": "barrier timeout"}
            return {"ok": True}
        if op == "ping":
            return {"ok": True, "apps": len(self._apps)}
        if op == "stop":
            return {"ok": True, "stop": True}
        return {"ok": False, "error": "unknown op %r" % op}


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):
        secret = self.server.secret
        while True:
            try:
                msg = _recv_msg(self.request, secret)
            except (ConnectionError, OSError):
                return
            resp = self.server.param_server.handle(msg)
            try:
                _send_msg(self.request, resp, secret)
            except (ConnectionError, OSError):
                return
            if resp.get("stop"):
                threading.Thread(target=self.server.shutdown,
                                 daemon=True).start()
                return


class _TCPServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


def serve_forever(host, port, num_workers, secret=None):
    """Run one parameter server (blocking). kvstore_server.py calls this
    for DMLC_ROLE=server processes."""
    srv = _TCPServer((host, port), _Handler)
    srv.param_server = ParamServer(num_workers)
    srv.secret = _job_secret() if secret is None else secret
    srv.serve_forever()


def _spawn_inprocess_server(port, num_workers, secret):
    srv = _TCPServer(("127.0.0.1", port), _Handler)
    srv.param_server = ParamServer(num_workers)
    srv.secret = secret
    t = threading.Thread(target=srv.serve_forever, daemon=True,
                         name="mxtpu-param-server")
    t.start()
    return srv


class KVStoreDistAsync(KVStore):
    """Worker-side client of the async parameter servers. Free-running:
    no method here ever waits on another worker (except ``barrier``).

    Each instance gets an app id (a ps-lite-customer-id analog) from a
    per-process counter, namespacing its keys/updater/barrier on the
    servers — workers must therefore create their dist_async stores in
    the same order (the reference's customer ids have the same
    contract)."""

    _next_app = [0]
    _captures_local_state = False   # state lives on the servers

    def __init__(self, name="dist_async"):
        super().__init__(name)
        # push is overridden: the compiled bucketed engine never
        # engages and every push is an eager wire round-trip — signal
        # it once + count it (kvstore_fallbacks), like kvstore_dist
        from .kvstore import _note_fallback
        _note_fallback(
            "legacy_dist_kvstore:%s" % name,
            detail="async parameter-server store, every push is eager "
                   "per-key (Hogwild semantics need it)")
        self._app_id = KVStoreDistAsync._next_app[0]
        KVStoreDistAsync._next_app[0] += 1
        self._rank = int(os.environ.get("MXTPU_WORKER_RANK", "0"))
        self._nworkers = int(os.environ.get("DMLC_NUM_WORKER", "1"))
        nserv = int(os.environ.get("DMLC_NUM_SERVER", "0"))
        host = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
        port = int(os.environ.get("DMLC_PS_ROOT_PORT", "0")) or 9091
        self._own_server = None
        self._secret = _job_secret()
        if nserv <= 0:
            if self._nworkers > 1:
                raise MXNetError(
                    "dist_async with %d workers needs parameter-server "
                    "processes: launch with tools/launch.py -n %d -s <S> "
                    "(an in-process fallback server would give every "
                    "worker its own isolated store)"
                    % (self._nworkers, self._nworkers))
            # standalone/dev mode (single worker): in-process daemon server
            import socket as _socket
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            host = "127.0.0.1"
            if not self._secret:
                # standalone: nobody shares this server, so mint a
                # process-local secret rather than running unauthenticated
                self._secret = _secrets.token_bytes(16)
            self._own_server = _spawn_inprocess_server(port, self._nworkers,
                                                       self._secret)
            nserv = 1
        self._servers = [(host, port + i) for i in range(nserv)]
        self._socks = [None] * nserv
        self._sock_locks = [threading.Lock() for _ in range(nserv)]
        # Per-shard RPC sequence for at-most-once retransmit dedupe.
        # Server-side dedupe state is per server, so independent per-shard
        # counters (each guarded by that shard's socket lock) cannot race
        # across threads the way one shared counter could (ADVICE r4).
        self._rpc_seq = [0] * nserv

    # ------------------------------------------------------------------
    def _server_of(self, key):
        # stable shard ring (reference EncodeDefaultKey): same key ->
        # same server on every worker
        import zlib
        return zlib.crc32(str(key).encode()) % len(self._servers)

    def _request(self, sidx, msg, retries=240, timeout=120):
        # generous connect retries: the server process imports the full
        # package before listening (~seconds on a loaded host).
        # ``timeout``: seconds to wait for the connection and for the
        # reply of one attempt
        # fresh copy per (request, shard): callers (and _all_servers)
        # reuse msg dicts, and a seq stamped for one shard must never
        # leak to another — each server dedupes on its own counter line
        msg = dict(msg)
        msg.setdefault("app", self._app_id)
        msg.setdefault("wkr", self._rank)
        with self._sock_locks[sidx]:
            self._rpc_seq[sidx] += 1
            msg["seq"] = self._rpc_seq[sidx]
            for attempt in range(retries):
                sock = self._socks[sidx]
                if sock is None:
                    try:
                        sock = socket.create_connection(
                            self._servers[sidx], timeout=timeout)
                        sock.setsockopt(socket.IPPROTO_TCP,
                                        socket.TCP_NODELAY, 1)
                        self._socks[sidx] = sock
                    except OSError:
                        time.sleep(0.25)
                        continue
                try:
                    sock.settimeout(timeout)
                    _send_msg(sock, msg, self._secret)
                    resp = _recv_msg(sock, self._secret)
                except (ConnectionError, OSError):
                    self._socks[sidx] = None
                    time.sleep(0.25)
                    continue
                if not resp.get("ok"):
                    raise MXNetError("param server: %s"
                                     % resp.get("error", "unknown"))
                return resp
        raise MXNetError("cannot reach param server %s:%d"
                         % self._servers[sidx])

    def _all_servers(self, msg):
        return [self._request(i, msg) for i in range(len(self._servers))]

    # ------------------------------------------------------------------
    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._nworkers

    def init(self, key, value):
        keys, values = _key_value(key, value)
        for k, vlist in zip(keys, values):
            self._request(self._server_of(k),
                          {"op": "init", "key": k,
                           "value": _np.asarray(vlist[0].asnumpy())})

    def push(self, key, value, priority=0):
        """Local reduce, then ship to the key's server, which applies it
        IMMEDIATELY — returns as soon as this worker's push is applied;
        never waits for other workers."""
        keys, values = _key_value(key, value)
        for k, vlist in zip(keys, values):
            reduced = self._local_reduce(vlist)
            if self._compression is not None:
                packed, shape, dtype = self._compress_wire(k, reduced)
                wire = ("2bit", _np.asarray(packed, _np.uint8).tobytes(),
                        tuple(shape), _np.dtype(dtype).str)
                self._request(self._server_of(k),
                              {"op": "push", "key": k, "value": wire})
            else:
                self._request(self._server_of(k),
                              {"op": "push", "key": k,
                               "value": _np.asarray(reduced.asnumpy())})

    def _compress_wire(self, k, grad):
        residual = self._get_residual((k, "wire"), grad)
        packed, new_residual = self._compression.compress(
            grad._data, residual._data)
        residual._set_data(new_residual)
        return _np.asarray(packed), grad.shape, grad._data.dtype

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        """Fetch the CURRENT server value — whatever pushes have landed
        so far (async staleness is the semantics, not a bug)."""
        import jax.numpy as jnp
        keys, outs = _key_value(key, out)
        for k, olist in zip(keys, outs):
            resp = self._request(self._server_of(k),
                                 {"op": "pull", "key": k})
            val = jnp.asarray(resp["value"])
            for o in olist:
                o._set_data(val.astype(o.dtype))

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Reference PullRowSparse over the async wire: the server slices
        the requested rows (op ``pull_rows``) so only those rows cross
        the wire — no dense transfer, no shared-state mutation."""
        if row_ids is None:
            self.pull(key, out=out, priority=priority)
            return
        import jax.numpy as jnp
        from .kvstore import _key_value
        from .ndarray import NDArray
        from .ndarray.sparse import RowSparseNDArray

        keys, outs = _key_value(key, out)
        n_out = sum(len(olist) for olist in outs)
        if isinstance(row_ids, NDArray):
            rid_list = [row_ids] * n_out
        else:
            rid_list = list(row_ids)
            if len(rid_list) != n_out:
                raise MXNetError(
                    "row_sparse_pull: %d row_ids for %d out arrays"
                    % (len(rid_list), n_out))
        i = 0
        for k, olist in zip(keys, outs):
            for o in olist:
                rows = _np.unique(
                    _np.asarray(rid_list[i].asnumpy(), _np.int64))
                i += 1
                resp = self._request(self._server_of(k),
                                     {"op": "pull_rows", "key": k,
                                      "rows": rows})
                vals = jnp.asarray(resp["value"])
                if isinstance(o, RowSparseNDArray):
                    # _set_data re-derives the (data, indices) pair from
                    # the dense view; zero rows drop out
                    full = jnp.zeros(o.shape, vals.dtype) \
                        .at[jnp.asarray(rows)].set(vals)
                    o._set_data(full.astype(o.dtype))
                else:
                    dense = jnp.asarray(o._data) \
                        .at[jnp.asarray(rows)].set(vals)
                    o._set_data(dense.astype(o.dtype))

    def pull_with_meta(self, key):
        """(value, applied_push_count) — observability used by tests to
        demonstrate unsynchronized interleaving."""
        resp = self._request(self._server_of(key),
                             {"op": "pull", "key": key})
        return resp["value"], resp["pushes"]

    def set_optimizer(self, optimizer):
        """Pickle the optimizer to every server (reference
        kvstore.py set_optimizer → server-side Updater)."""
        payload = pickle.dumps(optimizer)
        self._all_servers({"op": "set_optimizer", "optimizer": payload})

    def set_updater(self, updater):
        # host-side updater objects can't cross the wire in general; the
        # reference has the same restriction (only optimizers pickle).
        raise MXNetError(
            "dist_async runs the update on the server: use set_optimizer() "
            "(reference kvstore_dist_server.h ApplyUpdates)")

    def set_gradient_compression(self, compression_params):
        super().set_gradient_compression(compression_params)
        params = dict(compression_params)
        self._all_servers({"op": "set_gradient_compression",
                           "params": params})

    def barrier(self):
        """Explicit Postoffice-style barrier (never implicit in any
        push/pull)."""
        self._request(0, {"op": "barrier", "count": self._nworkers})

    def get_num_dead_node(self, node_id=0, timeout=60):
        """Servers that do not answer a ping within ``timeout`` seconds
        (reference kvstore.h:341: a node silent for ``timeout`` seconds
        is presumed dead)."""
        dead = 0
        for i in range(len(self._servers)):
            try:
                self._request(i, {"op": "ping"}, retries=2, timeout=timeout)
            except MXNetError:
                dead += 1
        return dead

    @property
    def is_recovery(self):
        return os.environ.get("DMLC_IS_RECOVERY", "0") == "1"
