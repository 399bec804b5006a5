"""Module: symbol + executor-group + optimizer, the mid-level training API.

API parity: python/mxnet/module/module.py (bind :364, init_params :270,
init_optimizer :465, forward :570, update :643) — same surface, re-derived
implementation.  The executor group compiles forward(+backward) into one
fused XLA program per shape signature; ``forward`` transparently re-binds
when a batch arrives with a new shape (the compiled-program cache makes
that cheap after the first time).
"""
from __future__ import annotations

import functools
import logging
import os
import warnings

from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..initializer import Uniform, InitDesc
from .. import optimizer as opt
from .. import telemetry as _telemetry
from ..model import (_create_kvstore, _initialize_kvstore,
                     _update_params_on_kvstore, _update_params,
                     load_checkpoint, save_checkpoint)
from ..io.io import DataDesc
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]

_tracing = _telemetry.tracing
_FIT_BUILD_S = _tracing.SETUP_SECONDS.labels(phase="fit_build")


def _setup_span(name, phase):
    """Run a method a process walks once under set-up span ``name``: an
    annotation in any running ``jax.profiler`` trace, a ring record
    when recording is on, and always its wall seconds in
    ``setup_seconds{phase}`` and the programs its body builds under
    ``program_build_seconds{site=name}`` (docs/OBSERVABILITY.md)."""
    seconds = _tracing.SETUP_SECONDS.labels(phase=phase)

    def wrap(method):
        @functools.wraps(method)
        def run(self, *args, **kwargs):
            with _tracing.span(name, seconds_to=seconds):
                return method(self, *args, **kwargs)
        return run
    return wrap


def _as_descs(shapes):
    """Normalise a list of (name, shape) / DataDesc into DataDesc records;
    None/empty passes through as None."""
    if not shapes:
        return None
    return DataDesc.get_list(
        [d if isinstance(d, DataDesc) else tuple(d) for d in shapes])


class Module(BaseModule):
    """Bind a Symbol over contexts and drive fused train/eval steps."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = current_context()
        self._context = [context] if isinstance(context, Context) else context
        self._work_load_list = work_load_list
        self._group2ctxs = group2ctxs
        self._symbol = symbol
        self._compression_params = compression_params

        names = {"data": list(data_names or []),
                 "label": list(label_names or []),
                 "state": list(state_names or []),
                 "fixed_param": list(fixed_param_names or [])}
        for kind, lst in names.items():
            _check_input_names(symbol, lst, kind, throw=kind != "label")
        self._data_names = names["data"]
        self._label_names = names["label"]
        self._state_names = names["state"]
        self._fixed_param_names = names["fixed_param"]

        non_params = set(self._data_names + self._label_names
                         + self._state_names)
        self._param_names = [a for a in symbol.list_arguments()
                             if a not in non_params]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        # single-launch fit step (module/fused_fit.py, docs/TRAINING.md):
        # built lazily on the first fit_step after init_optimizer;
        # MXNET_FIT_FUSED=0 keeps every step on the eager path
        self._fused_fit = None
        self._fused_fit_tried = False
        self._fused_fit_enabled = os.environ.get(
            "MXNET_FIT_FUSED", "1") != "0"
        self._monitor_installed = False

    # -- checkpointing --------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Rebuild a Module from ``prefix-symbol.json`` + params at epoch."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params, mod._aux_params = args, auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = f"{prefix}-{epoch:04d}.states"
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Write symbol/params (and optionally optimizer state) in the
        reference's file layout."""
        self._symbol.save(f"{prefix}-symbol.json")
        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, None, arg_params, aux_params)
        if save_optimizer_states:
            self.save_optimizer_states(f"{prefix}-{epoch:04d}.states")

    # -- introspection --------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        # Derived by shape inference from the bound inputs so it works
        # before any forward has run (SequentialModule wires at bind time).
        known = {d.name: d.shape for d in self._data_shapes}
        for l in self._label_shapes or []:
            known[l.name] = l.shape
        _, out_shapes, _ = self._symbol.infer_shape_partial(**known)
        return [(name, tuple(s) if s is not None else None)
                for name, s in zip(self._output_names, out_shapes)]

    @property
    def _param_names_bound(self):
        return self._exec_group.param_names

    # -- parameters -----------------------------------------------------
    def get_params(self):
        assert self.binded or self.params_initialized
        if self.binded and self._params_dirty:
            self._sync_params_from_devices()
        return self._arg_params, self._aux_params

    def _host_param_caches(self):
        """Materialise host-side copies of device params on first touch."""
        if self._arg_params is None:
            live = self._exec_group._exec.arg_dict
            bound_names = [n for n in self._param_names if n in live]
            self._arg_params = {
                name: arrs[0].copyto(cpu())
                for name, arrs in zip(bound_names,
                                      self._exec_group.param_arrays)}
        if self._aux_params is None:
            self._aux_params = {
                name: arrs[0].copyto(cpu())
                for name, arrs in zip(self._aux_names,
                                      self._exec_group.aux_arrays)}

    def _reject_extra(self, arg_params, aux_params):
        orphans = [n for n in (arg_params or {}) if n not in self._arg_params]
        orphans += [n for n in (aux_params or {}) if n not in self._aux_params]
        if orphans:
            raise MXNetError(
                f"set_params/init_params got extra parameter(s) "
                f"{sorted(orphans)} (pass allow_extra=True to ignore)")

    @_setup_span("module.init_params", "init_params")
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False")
            return
        assert self.binded, "call bind before initializing the parameters"
        self._host_param_caches()
        attrs = self._symbol.attr_dict()
        if not allow_extra:
            self._reject_extra(arg_params, aux_params)

        def fill(name, target, source):
            """Resolve one parameter: copy from `source` if present, else
            fall back to missing-policy / initializer."""
            if source is not None and name in source:
                given = source[name]
                if given is not target:
                    if given.shape != target.shape:
                        raise MXNetError(
                            f"shape mismatch for {name}: {given.shape} vs "
                            f"{target.shape}")
                    given.copyto(target)
                return
            if source is not None and not allow_missing:
                raise RuntimeError(f"{name} is not presented")
            if initializer is not None:
                initializer(InitDesc(name, attrs.get(name)), target)

        for name, target in sorted(self._arg_params.items()):
            fill(name, target, arg_params)
        for name, target in sorted(self._aux_params.items()):
            fill(name, target, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params,
                                    allow_extra=True)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False")
            return
        self._exec_group.set_params(arg_params, aux_params,
                                    allow_extra=allow_extra)
        self._params_dirty = True
        self.params_initialized = True

    # -- binding --------------------------------------------------------
    @_setup_span("module.bind", "bind")
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        if not for_training and inputs_need_grad:
            raise ValueError("inputs_need_grad requires for_training")

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._data_shapes = _as_descs(data_shapes)
        self._label_shapes = _as_descs(label_shapes)
        self.binded = True

        shared_group = None
        if shared_module is not None:
            assert shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names,
            group2ctxs=self._group2ctxs)
        self._total_exec_bytes = 0
        if shared_module is not None:
            # share host caches and (if live) the optimizer with the donor
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            if shared_module.optimizer_initialized:
                self.borrow_optimizer(shared_module)
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params,
                                        allow_extra=True)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        # a re-bind may change grad_req / inputs_need_grad — fused-fit
        # eligibility must be re-evaluated against the new executor
        self._fused_fit = None
        self._fused_fit_tried = False

    def reshape(self, data_shapes, label_shapes=None):
        """Re-bind to new input shapes, reusing weights (and the compiled
        program cache keyed by shape)."""
        assert self.binded
        self._data_shapes = _as_descs(data_shapes)
        self._label_shapes = _as_descs(label_shapes)
        self._exec_group = self._exec_group.reshape(self._data_shapes,
                                                    self._label_shapes)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params,
                                        allow_extra=True)

    # -- optimizer ------------------------------------------------------
    def _effective_batch_size(self, kvstore):
        first = self._exec_group.data_shapes[0]
        batch = first.shape[0] if isinstance(first, DataDesc) \
            else first[1][0]
        if kvstore and (("dist" in kvstore.type and "_sync" in kvstore.type)
                        or kvstore.type.startswith("tpu")
                        or kvstore.type == "nccl"):
            batch *= kvstore.num_workers
        return batch

    def _param_index_names(self, update_on_kvstore):
        """Index→name map handed to the optimizer (per-device interleaved
        when updates run on workers, matching the reference's updater
        keying)."""
        names = self._exec_group.param_names
        if update_on_kvstore:
            return dict(enumerate(names))
        n_dev = len(self._context)
        return {i * n_dev + k: n
                for i, n in enumerate(names) for k in range(n_dev)}

    @_setup_span("module.init_optimizer", "init_optimizer")
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        kvstore, update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        rescale_grad = 1.0 / self._effective_batch_size(kvstore)

        if isinstance(optimizer, str):
            config = dict(optimizer_params)
            config.setdefault("rescale_grad", rescale_grad)
            optimizer = opt.create(
                optimizer, sym=self._symbol,
                param_idx2name=self._param_index_names(update_on_kvstore),
                **config)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                warnings.warn(
                    f"Optimizer created manually outside Module but "
                    f"rescale_grad is not normalized to 1.0/batch_size/"
                    f"num_workers ({optimizer.rescale_grad} vs. "
                    f"{rescale_grad}). Is this intended?")

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._exec_group.param_names,
                                update_on_kvstore=update_on_kvstore)
            if self._exec_group._mesh is not None:
                # the kvstore init/pull round-trip re-wrote the param
                # arrays with single-device copies; restore the bind-time
                # GSPMD placement (mp-sharded params must START sharded,
                # not converge to it after the first donated step)
                self._exec_group._install_shardings()
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
        self.optimizer_initialized = True
        self._fused_fit = None          # re-evaluate fused-fit eligibility
        self._fused_fit_tried = False

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        """Adopt a live optimizer/kvstore/updater from another module (the
        bucketing path)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True
        self._fused_fit = None
        self._fused_fit_tried = False

    # -- execution ------------------------------------------------------
    def _batch_descs(self, data_batch, new_shapes):
        """Build (data_descs, label_descs) for a batch whose shapes differ
        from the bound ones."""
        if getattr(data_batch, "provide_data", None):
            d_descs = data_batch.provide_data
        else:
            d_descs = [DataDesc(d.name, shape, d.dtype, d.layout)
                       for d, shape in zip(self._data_shapes, new_shapes)]
        labels = getattr(data_batch, "label", None)
        if getattr(data_batch, "provide_label", None):
            l_descs = data_batch.provide_label
        elif labels:
            if self._label_shapes:
                l_descs = [DataDesc(l.name, arr.shape, l.dtype, l.layout)
                           for l, arr in zip(self._label_shapes, labels)]
            else:
                # a previous unlabeled batch dropped the label shapes;
                # rebuild them from the declared label names
                l_descs = [DataDesc(name, arr.shape)
                           for name, arr in zip(self._label_names, labels)]
        else:
            l_descs = None
        return d_descs, l_descs

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        bound = tuple(d.shape for d in self._data_shapes)
        arriving = tuple(b.data[0].shape for b in data_batch) \
            if isinstance(data_batch, list) \
            else tuple(a.shape for a in data_batch.data)
        if bound != arriving:
            self.reshape(*self._batch_descs(data_batch, arriving))
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def aot_warm(self, manifest=None):
        """mx.aot.warm hook (docs/AOT.md): dispatch the bound forward
        (+ backward when bound for training) once on zeros, so a
        restarted trainer pays trace + persistent-cache disk-load
        before its first real batch rather than during it.  Touches
        gradients only — parameters and optimizer state are untouched
        (no ``update``).  The fused fit step keys on live optimizer
        state and compiles lazily on the first ``fit_step``; on a
        restart that compile is also a disk-load (the persistent
        compile cache, docs/AOT.md).  Returns the number of programs
        dispatched."""
        assert self.binded and self.params_initialized
        from ..io import DataBatch
        from ..ndarray import zeros as _nd_zeros
        from ..telemetry import programs as _programs
        group = self._exec_group

        def dummy(descs):
            return [_nd_zeros(tuple(d.shape if hasattr(d, "shape")
                                    else d[1])) for d in descs]

        batch = DataBatch(data=dummy(group.data_shapes),
                          label=(dummy(group.label_shapes)
                                 if group.label_shapes else None))
        with _programs.warming():
            self.forward(batch, is_train=group.for_training)
            if group.for_training:
                self.backward()
        return 1

    def fit_step(self, data_batch, eval_metric=None):
        """One training step. Eligible configurations (docs/TRAINING.md)
        run forward+backward+compress+reduce+update — plus device-side
        metric accumulation when ``eval_metric.device_fn()`` exists — as
        ONE donated compiled program (module/fused_fit.py) and return
        True; everything else falls back to the eager fwd_bwd + kvstore
        pair."""
        fused = self._get_fused_fit()
        if fused is not None and fused.step(data_batch, eval_metric):
            return True
        return super().fit_step(data_batch, eval_metric)

    def _get_fused_fit(self):
        if not self._fused_fit_tried:
            self._fused_fit_tried = True
            if self.binded and self.params_initialized \
                    and self.optimizer_initialized:
                from .fused_fit import FusedFitStep
                with _tracing.span("fit.build", seconds_to=_FIT_BUILD_S):
                    self._fused_fit = FusedFitStep.build(self)
        return self._fused_fit

    def _fit_sync(self):
        """Bounded async depth (MXNET_FIT_SYNC_EVERY): block until the
        last dispatched step's parameters are materialized. Must wait on
        a TRAINABLE parameter — data/label buffers and frozen params are
        plain program inputs, always ready."""
        import jax
        exe = self._exec_group._exec
        for name in self._exec_group.param_names:
            arr = exe.arg_dict.get(name)
            if arr is not None and exe._grad_req.get(name, "null") != "null":
                jax.block_until_ready(arr._data)
                break
        scaler = getattr(self, "_loss_scaler", None)
        if scaler is not None:
            # already a sync boundary: refresh the loss_scale gauge and
            # overflow-skip counter from the device triple
            scaler.publish()
        if self._fused_fit is not None:
            # same boundary: fold the in-launch numerics sentinels
            # (grad norm, non-finite count, z-score, residual drift)
            # into the registry
            self._fused_fit.publish_sentinels()
        # same boundary: per-expert load from the counts the last fused
        # step noted (returns at once when none did)
        _telemetry.moe.publish()
        kv = self._kvstore
        if kv is not None and getattr(kv, "_engine", None) is not None:
            # the bucketed kvstore engine carries its own non-finite
            # witness scalar; same boundary, same dedup semantics
            kv._engine.publish_sentinels()

    def update(self):
        """Apply one optimizer step (kvstore push/pull or local updater)."""
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        if self._fused_fit is not None:
            # an eager update between fused steps must see the exact
            # accumulated error-feedback residuals — spill them back
            self._fused_fit._release()
        self._params_dirty = True
        group = self._exec_group
        if self._update_on_kvstore:
            _update_params_on_kvstore(group.param_arrays, group.grad_arrays,
                                      self._kvstore, group.param_names,
                                      push_order=group.push_order)
        else:
            _update_params(group.param_arrays, group.grad_arrays,
                           updater=self._updater, num_device=1,
                           kvstore=self._kvstore,
                           param_names=group.param_names,
                           push_order=group.push_order)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized \
            and self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        self._exec_group.update_metric(eval_metric, labels, pre_sliced)

    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        if self._kvstore and self._update_on_kvstore:
            for name, value in sorted(self._arg_params.items()):
                self._kvstore.pull(name, value)
        self._params_dirty = False

    # -- optimizer state persistence ------------------------------------
    def _live_updater(self):
        """The updater actually applying updates right now (kvstore's
        when update_on_kvstore, else the worker-local one)."""
        return self._kvstore._updater if self._update_on_kvstore \
            else self._updater

    def _opt_state_key_maps(self):
        """(name→live updater key, any-scheme→live key) maps.

        Two key schemes exist (docs/TRAINING.md): kvstore updaters key
        state by param NAME (kvstore._updater_key), local updaters by
        interleaved index (model._local_updater_key) — both shared with
        the fused fit step since PR 3. Checkpoints persist states under
        canonical param names; the alias map lets a states file written
        under EITHER scheme load into the live one, so a checkpoint
        taken with one kvstore config resumes under the other instead
        of silently dropping all momentum."""
        from ..kvstore import _updater_key
        from ..model import _local_updater_key
        names = self._exec_group.param_names
        if self._update_on_kvstore:
            name_to_live = {n: _updater_key(n) for n in names}
        else:
            name_to_live = {n: _local_updater_key(i)
                            for i, n in enumerate(names)}
        alias = {}
        for i, n in enumerate(names):
            alias[_updater_key(n)] = name_to_live[n]
            alias[_local_updater_key(i)] = name_to_live[n]
        return name_to_live, alias

    def _states_use_kvstore_file(self):
        """True when state persistence must stay delegated to the
        kvstore (dist stores keep server-side optimizer state; local
        and tpu stores hold process-local/replicated state that the
        canonical name-key translation below may rewrite)."""
        return self._update_on_kvstore \
            and not getattr(self._kvstore, "_captures_local_state", False)

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._states_use_kvstore_file():
            self._kvstore.save_optimizer_states(fname)
            return
        from ..optimizer import Updater
        if getattr(self._kvstore, "_captures_local_state", False):
            self._kvstore._flush_pending()   # pending buckets touch state
        updater = self._live_updater()
        if not isinstance(updater, Updater):
            with open(fname, "wb") as fout:   # custom updater: raw dump
                fout.write(updater.get_states())
            return
        import pickle
        name_to_live, _ = self._opt_state_key_maps()
        live_to_name = {lk: n for n, lk in name_to_live.items()}
        states = {live_to_name.get(k, k): v
                  for k, v in updater.states.items()}
        with open(fname, "wb") as fout:
            fout.write(pickle.dumps(states))

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._states_use_kvstore_file():
            self._kvstore.load_optimizer_states(fname)
            return
        from ..optimizer import Updater
        if getattr(self._kvstore, "_captures_local_state", False):
            self._kvstore._flush_pending()   # pending buckets touch state
        updater = self._live_updater()
        with open(fname, "rb") as f:
            blob = f.read()
        if not isinstance(updater, Updater):
            updater.set_states(blob)
            return
        import pickle
        data = pickle.loads(blob)
        _, alias = self._opt_state_key_maps()
        if isinstance(data, tuple) and len(data) == 2:
            # dump_optimizer=True form: (states, optimizer) — adopt the
            # optimizer too, then translate the keys in place
            updater.set_states(blob)
            updater.states = {alias.get(k, k): v
                              for k, v in updater.states.items()}
            updater.states_synced = {k: False for k in updater.states}
            # keep the module's optimizer handle pointing at the LIVE
            # (unpickled) one — lr/schedule mutations must hit it
            self._optimizer = updater.optimizer
        else:
            updater.set_states({alias.get(k, k): v
                                for k, v in data.items()})

    def install_monitor(self, mon):
        assert self.binded
        # monitor taps run through the executor programs; the fused fit
        # step routes every batch back to the eager path while installed
        self._monitor_installed = True
        self._exec_group.install_monitor(mon)

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass
