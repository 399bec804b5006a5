"""Executor: a bound symbol compiled to whole-graph XLA computations.

Reference parity: src/executor/graph_executor.cc + include/mxnet/executor.h.
The reference builds a full fwd+bwd nnvm graph, plans memory, and pushes one
engine op per node; here ``simple_bind`` traces the DAG once into

* ``_fwd``      — one XLA computation for forward (+ aux-state updates),
* ``_fwd_bwd``  — one XLA computation for forward+backward via ``jax.vjp``,

so the whole step is a single fused HLO (the BASELINE.json north-star:
"one XLA computation per forward/backward subgraph"). Memory planning,
op fusion, scheduling = XLA. grad_req add/write follows the reference's
OpReqType semantics (include/mxnet/op_attr_types.h:46).

Training forward is lazily fused: ``forward(is_train=True)`` defers
execution; ``backward()`` then runs the fused fwd+bwd program, so a
Module-style fit step costs exactly one compiled program launch.
"""
from __future__ import annotations


import numpy as _np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context, current_context
from .ndarray.ndarray import NDArray, zeros as nd_zeros
from .ops import registry as _reg
from . import profiler as _prof
from . import telemetry as _telemetry

__all__ = ["Executor"]

# retrace witness: incremented at TRACE time inside every executor
# program body (host code that only runs while jax traces), so a
# steady-state launch leaves it untouched — same contract as the
# kvstore/fused-fit TRACE_COUNTs (docs/OBSERVABILITY.md)
EXECUTOR_RETRACES = _telemetry.REGISTRY.counter(
    "executor_retraces",
    "executor fwd/fwd_bwd/monitor program (re)traces", vital=True)
EXECUTOR_DISPATCH_MS = _telemetry.REGISTRY.histogram(
    "executor_dispatch_ms",
    "host wall time to dispatch one executor program (async enqueue, "
    "not device completion)", unit="ms")
# dispatch + retrace instrumentation site (shared RetraceSite
# semantics with kvstore_fused / fused_fit): traced bodies call
# _note_retrace(); call sites dispatch through _timed_dispatch
_SITE = _telemetry.RetraceSite(EXECUTOR_RETRACES,
                               _telemetry.JIT_COMPILE_MS,
                               site="executor")
_note_retrace = _SITE.note


# per-thread launch tally next to the global one: lets a dispatcher
# (the decode engine) attribute launch counts to ITS OWN calls even
# while other threads (serving replicas, checkpoint) dispatch
# concurrently — same rationale as RetraceSite's TraceTally
_DISPATCH_TALLY = _telemetry.TraceTally()


def _count_dispatch():
    """Bump the global device-launch witness (profiler.DEVICE_DISPATCHES)
    — benchmark/layer_metrics/dispatches_per_step.train.py reads its
    delta over the window's steps."""
    _prof.DEVICE_DISPATCHES.increment()
    _DISPATCH_TALLY.count += 1


def _timed_dispatch(fn, *args):
    """Call one jitted executor program with telemetry: dispatch wall
    time -> executor_dispatch_ms; calls during which this thread
    (re)traced additionally observe into jit_compile_ms."""
    return _SITE.timed(fn, *args, dispatch_hist=EXECUTOR_DISPATCH_MS)


class _dispatch_span:
    """ONE context around a program dispatch.  It opens the
    ``telemetry.tracing`` span ``name`` (always an annotation in a
    running ``jax.profiler`` trace; a ring record when tracing is
    enabled) and, while ``mx.profiler`` profiles symbolic execution,
    records its host event under the reference's name ``prof_name``
    (``Executor::forward``, ...: MXNet's ``profile_symbolic``)."""

    __slots__ = ("_span", "_prof_name", "_scope")

    def __init__(self, name, prof_name):
        self._span = _telemetry.tracing.span(name)
        self._prof_name = prof_name

    def __enter__(self):
        self._scope = _prof.scope(self._prof_name, "symbolic") \
            if _prof.SYMBOLIC_ON else None
        if self._scope is not None:
            self._scope.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        if self._scope is not None:
            self._scope.__exit__(*exc)
        return False


def _build_graph_fn(symbol, collect_taps=False, monitor_all=False,
                    group_devices=None, tap_cb=None, tap_stat=None,
                    also=()):
    """Build a pure function (args, auxs, seed, is_train) ->
    (outputs, new_auxs) interpreting the DAG with registered op impls.
    With ``collect_taps`` the function also returns {tap_name: value} for
    every op output (and every variable when ``monitor_all``) — the debug
    program behind executor monitor callbacks (reference
    graph_executor.cc SetMonitorCallback).

    With ``tap_cb`` the taps instead STREAM out of the compiled program
    via ``jax.debug.callback`` — the TPU-native analog of the reference
    engine firing the monitor callback per executed op: ONE program, no
    second tapped launch. ``tap_stat`` (a jnp function) is applied to
    each tap inside the program, so only the small statistic crosses to
    the host, not the full intermediate tensor.

    With ``also``, a sequence of ``(node, output index)`` entries, the
    function returns those interior values as a third result: how the
    fused fit program reaches a loss head's stem (loss_head.py).

    ``group_devices`` maps a ctx_group name (``with AttrScope(
    ctx_group='dev1')``) to a ``jax.Device``: nodes carrying that attr
    have their outputs placed on the group's device via ``jax.device_put``
    **inside the traced program** — the TPU-native realization of the
    reference's PlaceDevice pass + _CrossDeviceCopy insertion
    (graph_executor.cc:408): one XLA program spanning the devices, with
    transfers exactly at group boundaries, and gradients transferring
    back through the transposed copies.

    Every operator node is traced under ``jax.named_scope(<op name>)``
    then ``jax.named_scope(<node name>)``: an instruction's ``op_name``
    reads ``.../FullyConnected/layer3_ffn1/dot_general`` forward and
    ``transpose(jvp(FullyConnected))/...`` backward in every program
    built from this function, whatever the compiler calls the
    instruction (trace-time metadata only; docs/OBSERVABILITY.md,
    "Scope names")."""
    topo = symbol._topo()
    entries = list(symbol._entries)
    aux_names = set(symbol.list_auxiliary_states())

    # activation sharding constraints: __sharding__ attrs on op outputs
    # become jax.lax.with_sharding_constraint inside the ONE program.
    # The mesh is captured at build time — safe because _compiled_cache
    # keys program caches on sharding.active_fingerprint(symbol).
    from . import sharding as _sharding
    _smesh = _sharding.get_mesh()
    _constraints = {}
    if _smesh is not None:
        for _node in topo:
            if _node.is_var:
                continue
            _s = _node.str_attrs.get(_sharding.SHARDING_ATTR)
            if _s:
                _constraints[id(_node)] = _sharding.parse_spec(_s)
        _sharding.CONSTRAINT_SITES.set(len(_constraints))

    def _constrain(node, v):
        entries_ = _constraints.get(id(node))
        if entries_ is None:
            return v
        # divisibility surfaces at trace time, when shapes are known
        _sharding.check_divisible(entries_, v.shape, _smesh,
                                  what="output of %r" % node.name)
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.lax.with_sharding_constraint(
            v, NamedSharding(_smesh, PartitionSpec(*entries_)))

    def _place(node, v):
        if not group_devices:
            return v
        grp = node.str_attrs.get("ctx_group")
        dev = group_devices.get(grp)
        return jax.device_put(v, dev) if dev is not None else v

    def _emit_tap(name, v):
        import functools
        val = tap_stat(v) if tap_stat is not None else v
        jax.debug.callback(functools.partial(tap_cb, name), val)

    def _tap_count(node):
        # taps follow the user-visible monitor contract: one entry per
        # visible output (invisible aux outputs like BatchNorm's
        # moving-stat updates would appear as duplicate same-named taps)
        return node.visible_out_count()

    def graph_fn(args, auxs, seed, is_train):
        rng = jax.random.key(seed)
        new_auxs = {}
        taps = {}
        with _reg._OpCtxScope(is_train, rng):
            env = {}
            for node in topo:
                if node.is_var:
                    if node.name in args:
                        env[(id(node), 0)] = _place(node, args[node.name])
                    elif node.name in auxs:
                        env[(id(node), 0)] = _place(
                            node, jax.lax.stop_gradient(auxs[node.name]))
                    else:
                        raise MXNetError("unbound variable '%s'" % node.name)
                    if collect_taps and monitor_all:
                        taps[node.name] = env[(id(node), 0)]
                    if tap_cb is not None and monitor_all:
                        _emit_tap(node.name, env[(id(node), 0)])
                    continue
                ins = [env[(id(inp), oi)] for inp, oi in node.inputs]
                with jax.named_scope(node.op.name), \
                        jax.named_scope(node.name):
                    raw = jax.checkpoint(lambda *a, n=node: n.apply(list(a)))(
                        *ins) if is_train and _mirrored(node) \
                        else node.apply(ins)
                if group_devices:
                    raw = (tuple(_place(node, r) for r in raw)
                           if isinstance(raw, (tuple, list))
                           else _place(node, raw))
                outs = list(raw) if isinstance(raw, (tuple, list)) else [raw]
                if _constraints:
                    # the annotation names the node's primary output
                    outs[0] = _constrain(node, outs[0])
                n_vis = _tap_count(node)
                for i, v in enumerate(outs):
                    env[(id(node), i)] = v
                    if collect_taps and i < n_vis:
                        taps[node.output_name(i)] = v
                    if tap_cb is not None and i < n_vis:
                        _emit_tap(node.output_name(i), v)
                # aux-state updates (reference FMutateInputs)
                if node.op.mutate_inputs and is_train:
                    in_names = node.input_names()
                    for mut_name, out_idx in node.op.mutate_inputs:
                        for (inp, _), nm in zip(node.inputs, in_names):
                            if nm == mut_name and inp.is_var and inp.name in aux_names:
                                new_auxs[inp.name] = outs[out_idx]
            outputs = [env[(id(n), oi)] for n, oi in entries]
        for name in auxs:
            new_auxs.setdefault(name, auxs[name])
        if collect_taps:
            return outputs, new_auxs, taps
        if also:
            return outputs, new_auxs, [env[(id(n), oi)] for n, oi in also]
        return outputs, new_auxs

    return graph_fn


def _compiled_cache(symbol):
    """Per-symbol compiled-callable cache: executors bound to the same
    Symbol (rebinds, numeric-grad perturbations, BucketingModule buckets)
    share XLA executables — the analog of the reference's shared memory
    pool across executors (graph_executor.cc InitDataEntryMemory).

    The store is keyed by ``sharding.active_fingerprint(symbol)``: None
    for mesh-independent symbols (the common case — one entry, exactly
    the old behavior), or the selected mesh's fingerprint when the
    symbol carries ``__sharding__`` annotations, whose graph_fn closes
    over the mesh.  A mesh change then builds fresh programs instead of
    silently reusing executables with stale shardings."""
    from . import sharding as _sharding
    store = getattr(symbol, "_exec_cache", None)
    if store is None:
        store = symbol._exec_cache = {}
    fp = _sharding.active_fingerprint(symbol)
    cache = store.get(fp)
    if cache is None:
        graph_fn = _build_graph_fn(symbol)

        @jax.jit
        # analyze: ok(retrace) graph_fn is symbol-pure; the compiled cache lives on the Symbol itself (_exec_cache)
        def _fwd_train(args, auxs, seed):
            _note_retrace()
            return graph_fn(args, auxs, seed, True)

        @jax.jit
        # analyze: ok(retrace) graph_fn is symbol-pure; the compiled cache lives on the Symbol itself (_exec_cache)
        def _fwd_eval(args, auxs, seed):
            _note_retrace()
            outs, _ = graph_fn(args, auxs, seed, False)
            return outs

        cache = {"graph_fn": graph_fn, "fwd_train": _fwd_train,
                 "fwd_eval": _fwd_eval, "fwd_eval_donated": None,
                 "fwd_bwd": {}, "fwd_monitor": {}}
        store[fp] = cache
    return cache


def _make_fwd_eval_donated(graph_fn):
    """Inference-forward program whose FIRST argument pytree (a dict of
    donated inputs) hands its buffers to XLA for in-place reuse.  The
    decode engine routes the paged k/v caches here (donate_args), so
    each compiled step updates the caches where they live instead of
    copying the whole cache in and out every launch — the O(cache)
    per-token traffic docs/DECODE.md used to book as an accepted cost.
    ONE callable serves any donated/retained name split: jit keys on
    the pytree structure of both dicts, and the distinct ``fn_name``
    lets telemetry.programs() tell donated programs from copy-based
    ones."""
    def _fwd_eval_donated(donated, args, auxs, seed):
        _note_retrace()
        outs, _ = graph_fn(dict(args, **donated), auxs, seed, False)
        return outs
    fn = jax.jit(_fwd_eval_donated, donate_argnums=0)
    _telemetry.programs.note_donation(fn, (0,))
    return fn


class _StreamTarget:
    """Indirection for in-stream tap callbacks: the compiled stream
    program calls the module-level dispatcher, which forwards to
    whichever executor is currently running it — so the compiled program
    is executor-independent and can be cached per SYMBOL (like
    _compiled_cache), not per executor. A plain attribute, NOT
    thread-local: jax delivers debug callbacks on a runtime thread, so
    the running executor is published globally for the duration of the
    monitored launch (which ends with an effects barrier). Concurrent
    monitored launches from multiple host threads would interleave taps
    — a debug-path limitation the reference's engine callbacks share."""
    exe = None


_STREAM_TARGET = _StreamTarget()

# the stable default on-device statistic (mean |x|, the reference
# Monitor default); Monitor.install passes this same object so the
# stream-program cache key is stable across installs
def DEFAULT_STREAM_STAT(a):
    return jnp.mean(jnp.abs(a.astype(jnp.float32)))


def _stream_dispatch(name, value):
    exe = _STREAM_TARGET.exe
    if exe is not None:
        exe._stream_tap(name, value)


def _monitor_fn(symbol, is_train, monitor_all):
    """Jitted tapped-forward program, cached per (is_train, monitor_all)."""
    cache = _compiled_cache(symbol)
    key = (bool(is_train), bool(monitor_all))
    fn = cache["fwd_monitor"].get(key)
    if fn is None:
        tapped = _build_graph_fn(symbol, collect_taps=True,
                                 monitor_all=monitor_all)

        @jax.jit
        # analyze: ok(retrace) tapped graph is (symbol, is_train, monitor_all)-pure and cached under exactly that key
        def fn(args, auxs, seed):
            _note_retrace()
            return tapped(args, auxs, seed, is_train)

        cache["fwd_monitor"][key] = fn
    return fn


def _mirrored(node):
    """The reference's per-node mirroring hint (``force_mirroring=True``
    on a symbol call, stored as ``__force_mirroring__``): the node's
    forward is computed again in the backward pass and only its inputs
    are kept (``jax.checkpoint`` round the one operator), where
    MXNET_BACKWARD_DO_MIRROR does it to the whole graph.  Not for a
    node that updates an auxiliary state."""
    return not node.op.mutate_inputs and str(node.str_attrs.get(
        "__force_mirroring__", "")).lower() in ("true", "1")


def _make_fwd_bwd(graph_fn, diff_names, mirror):
    # `mirror` (MXNET_BACKWARD_DO_MIRROR) is an explicit builder param
    # and part of every fwd_bwd cache key: a capture read from the
    # environment here would be invisible to the cache, so flipping the
    # knob between binds would silently reuse the wrong program
    # (flagged by mx.analyze retrace/env-capture)

    @jax.jit
    def _fwd_bwd(args, auxs, seed, ograds):
        _note_retrace()
        diff = {n: args[n] for n in diff_names}
        rest = {n: v for n, v in args.items() if n not in diff}

        def f(d):
            outs, new_auxs = graph_fn({**rest, **d}, auxs, seed, True)
            return outs, new_auxs

        if mirror:
            # MXNET_BACKWARD_DO_MIRROR: recompute the forward during
            # backward instead of keeping activations (jax.checkpoint —
            # the reference's gradient-mirroring memory/compute trade,
            # graph_executor.cc:193)
            f = jax.checkpoint(f)

        outs, vjp_fn, new_auxs = jax.vjp(f, diff, has_aux=True)
        # head grads cast to each output's dtype (a bf16/fp16 graph fed
        # f32 out_grads — e.g. check_consistency's shared grads — must
        # not fail the VJP dtype check)
        cts = [jnp.asarray(g, o.dtype) if g is not None
               else jnp.ones_like(o)
               for g, o in zip(ograds, outs)]
        (grads,) = vjp_fn(cts)
        return outs, new_auxs, grads
    return _fwd_bwd


class Executor:
    def __init__(self, symbol, ctx, arg_dict, grad_dict, aux_dict,
                 grad_req_dict, group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx if ctx is not None else current_context()
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        self._grad_req = grad_req_dict
        # group2ctx is the reference's manual model-parallel placement
        # (graph_executor.cc PlaceDevice + _CrossDeviceCopy insertion).
        # TPU-native realization: each ctx_group's jax device is honored
        # by jax.device_put at group boundaries INSIDE the one traced
        # program (_build_graph_fn group_devices) — XLA compiles a single
        # multi-device program with transfers exactly where the reference
        # inserted copy nodes, and gradients ride the transposed copies.
        self._group2ctx = group2ctx
        self._group_devices = None
        if group2ctx:
            base = ctx if ctx is not None else current_context()
            gd = {g: c.jax_device for g, c in group2ctx.items()}
            if any(c != base for c in group2ctx.values()):
                self._group_devices = gd
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self._diff_names = [n for n in self._arg_names
                            if grad_req_dict.get(n, "null") != "null"]
        self._monitor_callback = None
        self._monitor_all = False
        self._monitor_mode = "stream"
        self._monitor_stat = None
        self._donated_names = ()
        self._jit_fwd_eval_donated = None
        self._outputs = None
        self._pending_train_fwd = False
        self._train_seed = None
        self._train_auxs = None
        self._step = 0
        from . import random as _rand
        self._base_seed = _rand.next_seed()

        from . import config as _config
        # snapshot MXNET_BACKWARD_DO_MIRROR at BIND time: every fwd_bwd
        # this executor selects (plain or stream-monitored) uses this
        # one setting, and it is part of each cache key — a mid-life
        # env flip affects only later binds, never an existing executor
        self._mirror = mirror = _config.backward_do_mirror()
        if self._group_devices is None:
            cache = _compiled_cache(symbol)
            self._graph_fn = cache["graph_fn"]
            self._jit_fwd_train = cache["fwd_train"]
            self._jit_fwd_eval = cache["fwd_eval"]
            key = (tuple(sorted(self._diff_names)), mirror)
            if key not in cache["fwd_bwd"]:
                cache["fwd_bwd"][key] = _make_fwd_bwd(
                    cache["graph_fn"], key[0], mirror)
            self._jit_fwd_bwd = cache["fwd_bwd"][key]
        else:
            # model-parallel bind: the placed program is specific to this
            # group->device map, so it gets its own jitted callables
            # (cached per symbol+placement)
            gkey = tuple(sorted((g, str(d))
                                for g, d in self._group_devices.items()))
            placed = getattr(symbol, "_exec_cache_placed", None)
            if placed is None:
                placed = symbol._exec_cache_placed = {}
            entry = placed.get(gkey)
            if entry is None:
                graph_fn = _build_graph_fn(
                    symbol, group_devices=self._group_devices)

                @jax.jit
                # analyze: ok(retrace) placed graph_fn is (symbol, group->device map)-pure; cache keyed by that placement
                def _fwd_train(args, auxs, seed):
                    _note_retrace()
                    return graph_fn(args, auxs, seed, True)

                @jax.jit
                # analyze: ok(retrace) placed graph_fn is (symbol, group->device map)-pure; cache keyed by that placement
                def _fwd_eval(args, auxs, seed):
                    _note_retrace()
                    outs, _ = graph_fn(args, auxs, seed, False)
                    return outs

                entry = {"graph_fn": graph_fn, "fwd_train": _fwd_train,
                         "fwd_eval": _fwd_eval, "fwd_bwd": {}}
                placed[gkey] = entry
            self._graph_fn = entry["graph_fn"]
            self._jit_fwd_train = entry["fwd_train"]
            self._jit_fwd_eval = entry["fwd_eval"]
            key = (tuple(sorted(self._diff_names)), mirror)
            if key not in entry["fwd_bwd"]:
                entry["fwd_bwd"][key] = _make_fwd_bwd(
                    entry["graph_fn"], key[0], mirror)
            self._jit_fwd_bwd = entry["fwd_bwd"][key]

    # ------------------------------------------------------------------
    @property
    def outputs(self):
        if self._pending_train_fwd:
            self._run_fwd(True)
        elif callable(self._outputs):
            # the last fused fit step deferred its loss heads
            # (loss_head.DeferredOutputs): build them now, once
            self._outputs = self._outputs()
        return self._outputs

    @property
    def output_dict(self):
        return dict(zip(self._output_names, self.outputs))

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    def set_monitor_callback(self, callback, monitor_all=False,
                             mode="stream", stat_fn=None):
        """Install a (name, NDArray) callback fired with every node output
        (and every variable when ``monitor_all``) after each forward
        (reference graph_executor.cc SetMonitorCallback).

        ``mode='stream'`` (default) fires the taps from INSIDE the one
        compiled step via ``jax.debug.callback`` — the analog of the
        reference engine streaming callbacks from in-flight execution.
        ``stat_fn`` (a jnp function) runs on-device per tap so only the
        statistic crosses to the host; without it the full tensors
        stream out. Monitored batches cost ~the plain step plus the
        stats (timed in tests/test_monitor_stream.py).

        ``mode='tapped'`` keeps the previous behavior — a SECOND jitted
        program returning every intermediate (full-tensor dumps without
        per-tap host callbacks) at ~2x step cost on monitored batches.
        Monitor's interval gate (``Monitor(interval=N)``) limits either
        cost to every N-th batch."""
        self._monitor_callback = callback
        self._monitor_all = bool(monitor_all)
        self._monitor_mode = mode
        self._monitor_stat = stat_fn

    def _stream_tap(self, name, value):
        cb = self._monitor_callback
        if cb is not None:
            cb(name, NDArray(jnp.asarray(value), self._ctx))

    def _stream_fns(self):
        """Jitted in-stream-tapped programs. Cached per SYMBOL (sharing
        XLA executables across executors and re-installs exactly like
        _compiled_cache) — the compiled program calls the module-level
        _stream_dispatch, which forwards to the currently-running
        executor. Keyed by (monitor_all, stat id, diff set); Monitor
        passes the stable DEFAULT_STREAM_STAT object, so repeat installs
        hit the cache. group2ctx (placed) binds keep a per-executor
        cache since their programs embed the device map."""
        key = (self._monitor_all, id(self._monitor_stat))
        if self._group_devices is None:
            store = _compiled_cache(self._symbol).setdefault("stream", {})
        else:
            store = self.__dict__.setdefault("_placed_stream_cache", {})
        fns = store.get(key)
        if fns is None:
            tapped = _build_graph_fn(
                self._symbol, group_devices=self._group_devices,
                monitor_all=self._monitor_all, tap_cb=_stream_dispatch,
                tap_stat=self._monitor_stat)

            @jax.jit
            # analyze: ok(retrace) stream-tap debug program: (symbol, monitor_all, stat)-pure, cached under that key; retraces intentionally uncounted on the monitored path
            def fwd_train(args, auxs, seed):
                return tapped(args, auxs, seed, True)

            @jax.jit
            # analyze: ok(retrace) stream-tap debug program: (symbol, monitor_all, stat)-pure, cached under that key; retraces intentionally uncounted on the monitored path
            def fwd_eval(args, auxs, seed):
                outs, _ = tapped(args, auxs, seed, False)
                return outs

            # "stat" pins the stat function alive so its id() (the cache
            # key) can never be recycled onto a different function
            fns = {"graph_fn": tapped, "fwd_train": fwd_train,
                   "fwd_eval": fwd_eval, "fwd_bwd": {},
                   "stat": self._monitor_stat}
            store[key] = fns
        # forward programs are diff-set independent; only the fused
        # fwd+bwd needs a per-(diff-set, mirror) variant — using the
        # BIND-time mirror snapshot so a monitored backward can never
        # run a different mirror setting than this executor's plain one
        mirror = self._mirror
        diff_key = (tuple(sorted(self._diff_names)), mirror)
        if diff_key not in fns["fwd_bwd"]:
            fns["fwd_bwd"][diff_key] = _make_fwd_bwd(
                fns["graph_fn"], diff_key[0], mirror)
        return {"fwd_train": fns["fwd_train"], "fwd_eval": fns["fwd_eval"],
                "fwd_bwd": fns["fwd_bwd"][diff_key]}

    def _monitor_active(self):
        if self._monitor_callback is None:
            return False
        # Monitor attaches itself to its stat_helper; skip the extra tapped
        # program launch entirely on batches its interval gate would drop
        mon = getattr(self._monitor_callback, "_monitor", None)
        return mon is None or getattr(mon, "activated", True)

    def _fire_monitor(self, is_train, seed, auxs):
        fn = _monitor_fn(self._symbol, is_train, self._monitor_all)
        _, _, taps = fn(self._args_values(), auxs, seed)
        # a stream-installed callback expects the on-device statistic,
        # not the raw tensor (Monitor.stream_helper skips stat_func) —
        # apply it here when the tapped program is used as a fallback
        # (e.g. MXNET_BACKWARD_DO_MIRROR)
        stat = self._monitor_stat if self._monitor_mode == "stream" else None
        for name, val in taps.items():
            if stat is not None:
                val = stat(val)
            self._monitor_callback(name, NDArray(val, self._ctx))

    # ------------------------------------------------------------------
    def _args_values(self):
        return {n: self.arg_dict[n]._data for n in self._arg_names}

    def _auxs_values(self):
        return {n: self.aux_dict[n]._data for n in self._aux_names}

    def _next_seed(self):
        self._step += 1
        return _np.uint32((int(self._base_seed) + self._step * 2654435761)
                          & 0x7FFFFFFF)

    def _to_ctx(self, data):
        """Colocate an input with the executor's device — data-iterator
        batches live on the cpu context (reference iterator contract) and
        must move to the bind device exactly once here."""
        dev = self._ctx.jax_device
        try:
            if data.devices() == {dev}:
                return data
        except AttributeError:
            pass
        import jax as _jax
        return _jax.device_put(data, dev)

    def _commit_args(self):
        """Commit every bound array to the placement it already has.
        Freshly allocated arrays are uncommitted; what a program returns
        is committed, and jax lowers (and XLA compiles) again when that
        changes.  Owners of state that is threaded through outputs (the
        decode engine's caches) call this once after binding, so their
        first real dispatch reuses the warmup's executable."""
        import jax as _jax
        for nd in list(self.arg_dict.values()) + list(
                self.aux_dict.values()):
            nd._set_data(_jax.device_put(nd._data, nd._data.sharding))

    def donate_args(self, names):
        """Route the named arguments through the donated inference
        forward: their device buffers are handed to XLA each eval
        dispatch (donate_argnums), so programs that thread state through
        outputs (the decode engine's k/v caches) update it in place
        instead of copying it in and out every launch.

        CONTRACT: after every dispatch the donated NDArrays hold
        DELETED buffers — the caller must re-point them at the
        corresponding outputs (engine._commit_caches) before anything
        reads them.  Stream-monitored debug forwards fall back to the
        copy-based program.  Pass an empty sequence to turn donation
        back off."""
        names = tuple(names)
        for n in names:
            if n not in self.arg_dict:
                raise MXNetError("donate_args: unknown argument '%s'" % n)
        if not names:
            self._donated_names = ()
            self._jit_fwd_eval_donated = None
            return True
        if self._group_devices is not None:
            raise MXNetError("donate_args: model-parallel (group2ctx) "
                             "binds are not supported")
        cache = _compiled_cache(self._symbol)
        if cache["fwd_eval_donated"] is None:
            cache["fwd_eval_donated"] = _make_fwd_eval_donated(
                cache["graph_fn"])
        self._donated_names = names
        self._jit_fwd_eval_donated = cache["fwd_eval_donated"]
        return True

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("forward: unknown argument '%s'" % k)
            dst = self.arg_dict[k]
            if isinstance(v, NDArray):
                data = v._data
                sh = dst._data.sharding
                if getattr(data, "sharding", None) != sh:
                    # move onto the bound buffer's placement (single
                    # device normally; the mesh under GSPMD binds)
                    data = jax.device_put(data, sh)
                dst._set_data(data)
            else:
                dst._sync_copyfrom(v)
        if is_train:
            # defer: backward() will run the fused fwd+bwd program. The seed
            # and pre-update aux snapshot are fixed NOW so that a forced
            # .outputs read and the later backward() see the exact same
            # computation (same dropout masks, single aux-momentum update).
            self._pending_train_fwd = True
            self._outputs = None
            self._train_seed = self._next_seed()
            self._train_auxs = self._auxs_values()
        else:
            self._train_seed = None
            self._train_auxs = None
            self._run_fwd(False)
        return self.outputs if not is_train else _LazyOutputs(self)

    def _run_fwd(self, is_train):
        monitored = self._monitor_active()
        stream = monitored and self._monitor_mode == "stream"
        if stream:
            # analyze: ok(threads) documented debug-path limitation: the running executor is published globally for the duration of a monitored launch (_StreamTarget docstring)
            _STREAM_TARGET.exe = self
        try:
            if is_train:
                seed = self._train_seed if self._train_seed is not None \
                    else self._next_seed()
                auxs = self._train_auxs if self._train_auxs is not None \
                    else self._auxs_values()
                if monitored and not stream:
                    self._fire_monitor(True, seed, auxs)
                fwd = (self._stream_fns()["fwd_train"] if stream
                       else self._jit_fwd_train)
                with _dispatch_span("executor.forward", "Executor::forward"):
                    _count_dispatch()
                    outs, new_auxs = _timed_dispatch(
                        fwd, self._args_values(), auxs, seed)
                self._write_auxs(new_auxs)
            else:
                seed = self._next_seed()
                if monitored and not stream:
                    self._fire_monitor(False, seed, self._auxs_values())
                donated_fn = (self._jit_fwd_eval_donated
                              if not stream else None)
                fwd = (self._stream_fns()["fwd_eval"] if stream
                       else self._jit_fwd_eval)
                with _dispatch_span("executor.forward", "Executor::forward"):
                    _count_dispatch()
                    if donated_fn is not None:
                        vals = self._args_values()
                        donated = {n: vals.pop(n)
                                   for n in self._donated_names}
                        outs = _timed_dispatch(
                            donated_fn, donated, vals,
                            self._auxs_values(), seed)
                    else:
                        outs = _timed_dispatch(
                            fwd, self._args_values(), self._auxs_values(),
                            seed)
            if stream:
                jax.effects_barrier()   # flush in-flight tap callbacks
        finally:
            if stream:
                # analyze: ok(threads) documented debug-path limitation (_StreamTarget docstring); cleared in the finally
                _STREAM_TARGET.exe = None
        self._outputs = [NDArray(o, self._ctx) for o in outs]
        self._pending_train_fwd = False
        return self._outputs

    def backward(self, out_grads=None, is_train=True):
        if not self._diff_names:
            self._pending_train_fwd = False
            return
        n_out = len(self._output_names)
        if out_grads is None:
            ograds = [None] * n_out
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            ograds = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                      for g in out_grads]
        # reuse the seed/aux snapshot fixed at forward(is_train=True) so the
        # recomputed forward inside the fused program matches what the user
        # observed (and aux momentum updates apply exactly once per step)
        seed = self._train_seed if self._train_seed is not None \
            else self._next_seed()
        auxs = self._train_auxs if self._train_auxs is not None \
            else self._auxs_values()
        self._train_seed = None
        self._train_auxs = None
        monitored = self._monitor_active() and self._pending_train_fwd
        # MXNET_BACKWARD_DO_MIRROR rematerializes the forward inside the
        # fused fwd+bwd (jax.checkpoint) — the re-run would fire every
        # stream tap twice, so monitored mirror steps use the tapped
        # program instead (bind-time snapshot, matching _stream_fns)
        stream = (monitored and self._monitor_mode == "stream"
                  and not self._mirror)
        if monitored and not stream:
            # tapped mode: fire taps with the same seed/aux snapshot the
            # fused program will consume, so the monitored values match
            # what executes
            self._fire_monitor(True, seed, auxs)
        if stream:
            # analyze: ok(threads) documented debug-path limitation: the running executor is published globally for the duration of a monitored launch (_StreamTarget docstring)
            _STREAM_TARGET.exe = self
        try:
            fwd_bwd = (self._stream_fns()["fwd_bwd"] if stream
                       else self._jit_fwd_bwd)
            with _dispatch_span("executor.forward_backward",
                                "Executor::forward_backward"):
                _count_dispatch()
                outs, new_auxs, grads = _timed_dispatch(
                    fwd_bwd, self._args_values(), auxs, seed, ograds)
            if stream:
                jax.effects_barrier()   # flush in-flight tap callbacks
        finally:
            if stream:
                # analyze: ok(threads) documented debug-path limitation (_StreamTarget docstring); cleared in the finally
                _STREAM_TARGET.exe = None
        self._outputs = [NDArray(o, self._ctx) for o in outs]
        self._pending_train_fwd = False
        self._write_auxs(new_auxs)
        for name, g in grads.items():
            req = self._grad_req.get(name, "null")
            dst = self.grad_dict.get(name)
            if dst is None or req == "null":
                continue
            g = g.astype(dst._data.dtype)
            if req == "add":
                dst._set_data(dst._data + g)
            else:
                dst._set_data(g)

    def _write_auxs(self, new_auxs):
        for name, v in new_auxs.items():
            self.aux_dict[name]._set_data(v)

    # ------------------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        # staging preserves each destination's placement: a param the
        # bind installed with a NamedSharding (mx.sharding annotations
        # resolved in _install_param_shardings) re-shards the incoming
        # host values instead of collapsing back to the single bind
        # device; unsharded params keep the exact old behavior (their
        # current sharding IS the ctx device).
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                dst = self.arg_dict[name]
                dst._set_data(
                    jax.device_put(arr._data, dst._data.sharding))
            elif not allow_extra_params:
                raise MXNetError("unknown arg '%s'" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    dst = self.aux_dict[name]
                    dst._set_data(
                        jax.device_put(arr._data, dst._data.sharding))
                elif not allow_extra_params:
                    raise MXNetError("unknown aux '%s'" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound with new data shapes; weights are
        shared (reference: GraphExecutor::Reshape, graph_executor.h:110).
        The jit cache keys on shape, so recompilation is automatic."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape_partial(**kwargs)
        new_args = {}
        for name, shp in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if shp is not None and tuple(shp) != cur.shape:
                new_args[name] = nd_zeros(shp, self._ctx, cur.dtype)
            else:
                new_args[name] = cur
        grad_dict = {}
        for name, arr in new_args.items():
            if self._grad_req.get(name, "null") != "null":
                prev = self.grad_dict.get(name)
                if prev is not None and prev.shape == arr.shape:
                    grad_dict[name] = prev
                else:
                    grad_dict[name] = nd_zeros(arr.shape, self._ctx, arr.dtype)
        return Executor(self._symbol, self._ctx, new_args, grad_dict,
                        dict(self.aux_dict), dict(self._grad_req),
                        self._group2ctx)

    def debug_str(self):
        lines = ["Symbol outputs: %s" % ", ".join(self._output_names)]
        for node in self._symbol._topo():
            kind = "var" if node.is_var else node.op.name
            lines.append("  %s %s <- %s" % (kind, node.name,
                                            [n.name for n, _ in node.inputs]))
        return "\n".join(lines)

    # ------------------------------------------------------------------
    # binding entry points (invoked from Symbol)
    # ------------------------------------------------------------------
    @staticmethod
    def _normalize_grad_req(grad_req, arg_names):
        if isinstance(grad_req, str):
            return {n: grad_req for n in arg_names}
        if isinstance(grad_req, (list, tuple)):
            return dict(zip(arg_names, grad_req))
        if isinstance(grad_req, dict):
            return {n: grad_req.get(n, "null") for n in arg_names}
        raise MXNetError("invalid grad_req %r" % (grad_req,))

    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, group2ctx,
                     shared_exec, shared_buffer, shape_kwargs):
        ctx = ctx if ctx is not None else current_context()
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        type_dict = type_dict or {}
        arg_shapes, arg_types, aux_shapes, aux_types = \
            symbol.infer_shape_type(shape_kwargs, type_dict)
        if any(s is None for s in arg_shapes):
            missing = [n for n, s in zip(arg_names, arg_shapes) if s is None]
            raise MXNetError("simple_bind: cannot infer shapes of %s" % missing)

        grad_req_dict = Executor._normalize_grad_req(grad_req, arg_names)
        # data/label inputs default to grad null under 'write' like the
        # reference Module behavior is handled by the caller; here we follow
        # the grad_req given.
        arg_dict = {}
        for name, shp, dt in zip(arg_names, arg_shapes, arg_types):
            shared = shared_exec.arg_dict.get(name) if shared_exec else None
            if shared is not None and shared.shape == tuple(shp):
                arg_dict[name] = shared
            else:
                arg_dict[name] = nd_zeros(shp, ctx, type_dict.get(name, dt))
        grad_dict = {}
        for name in arg_names:
            if grad_req_dict.get(name, "null") != "null":
                arr = arg_dict[name]
                grad_dict[name] = nd_zeros(arr.shape, ctx, arr.dtype)
        aux_dict = {}
        for name, shp, dt in zip(aux_names, aux_shapes, aux_types):
            shared = shared_exec.aux_dict.get(name) if shared_exec else None
            if shared is not None and shared.shape == tuple(shp):
                aux_dict[name] = shared
            else:
                aux_dict[name] = nd_zeros(shp, ctx, dt)
        Executor._install_param_shardings(symbol, arg_dict, grad_dict,
                                          aux_dict)
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict,
                        grad_req_dict, group2ctx)

    @staticmethod
    def _install_param_shardings(symbol, arg_dict, grad_dict, aux_dict):
        """Bind-time GSPMD placement: resolve ``__sharding__`` var attrs
        against the selected mesh (mx.sharding.set_mesh / MXTPU_MESH)
        and device_put each annotated parameter — and its grad buffer —
        with the resulting NamedSharding, so per-device param bytes
        shrink the moment the executor exists (the HBM census reads
        this).  No mesh selected, or no annotations: no-op."""
        from . import sharding as _sharding
        mesh = _sharding.get_mesh()
        if mesh is None:
            return
        specs = _sharding.collect_var_specs(symbol)
        if not specs:
            return
        placed = set()
        for name, s in specs.items():
            for store in (arg_dict, aux_dict):
                arr = store.get(name)
                if arr is None:
                    continue
                ns = _sharding.resolve(s, arr.shape, mesh, what=name)
                arr._set_data(jax.device_put(arr._data, ns))
                placed.add(name)
                g = grad_dict.get(name) if store is arg_dict else None
                if g is not None:
                    g._set_data(jax.device_put(g._data, ns))
        # every OTHER bound buffer goes replicated over the same mesh:
        # jit refuses argument sets committed to different device sets,
        # so once one param lives on the mesh, all of them (and the
        # inputs) must.  Module binds immediately re-place data/label
        # with P('dp') in executor_group._install_shardings; direct
        # simple_bind users (mx.decode under an mp mesh) keep the
        # replicated placement, which GSPMD treats as free.
        repl = _sharding.NamedSharding(mesh, _sharding.P())
        for store in (arg_dict, aux_dict):
            for name, arr in store.items():
                if name in placed:
                    continue
                arr._set_data(jax.device_put(arr._data, repl))
                g = grad_dict.get(name) if store is arg_dict else None
                if g is not None:
                    g._set_data(jax.device_put(g._data, repl))

    @staticmethod
    def _bind(symbol, ctx, args, args_grad, grad_req, aux_states, group2ctx,
              shared_exec):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args)
        missing = [n for n in arg_names if n not in arg_dict]
        if missing:
            raise MXNetError("bind: missing arguments %s" % missing)
        if isinstance(args_grad, (list, tuple)):
            grad_dict = dict(zip(arg_names, args_grad))
        elif args_grad is None:
            grad_dict = {}
        else:
            grad_dict = dict(args_grad)
        if isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        elif aux_states is None:
            aux_dict = {}
        else:
            aux_dict = dict(aux_states)
        for n in aux_names:
            if n not in aux_dict:
                raise MXNetError("bind: missing aux state %s" % n)
        grad_req_dict = Executor._normalize_grad_req(grad_req, arg_names)
        for n in arg_names:
            if n not in grad_dict:
                grad_req_dict[n] = "null"
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict,
                        grad_req_dict, group2ctx)


class _LazyOutputs(list):
    """Returned by forward(is_train=True); materializes on first access so
    Module's fwd+bwd fuses into one program when outputs aren't read early."""

    def __init__(self, executor):
        super().__init__()
        self._ex = executor

    def _force(self):
        outs = self._ex.outputs
        if not list.__len__(self):
            self.extend(outs)
        return outs

    def __getitem__(self, i):
        self._force()
        return super().__getitem__(i)

    def __iter__(self):
        self._force()
        return super().__iter__()

    def __len__(self):
        self._force()
        return super().__len__()
