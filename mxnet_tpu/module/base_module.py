"""BaseModule: the high-level train / score / predict interface.

API parity with the reference's ``python/mxnet/module/base_module.py``
(``fit`` :399, ``score`` :168, ``predict`` :264) — same signatures, same
log-line shapes — but the engine underneath is different and the loop is
built for it.  On TPU each ``forward_backward``+``update`` is ONE fused XLA
program whose dispatch returns immediately (the result arrays are futures);
the only host-blocking points are metric readback and data staging.  The
epoch loop here is therefore organised around a one-step-lookahead
``_Prefetcher`` (host decodes/stages batch N+1 while the device runs step N)
and metrics that read back only at callback boundaries, keeping the device
queue full instead of replaying the reference's synchronous
compute→wait→update sequence.
"""
from __future__ import annotations

import logging
import os
import time

import numpy as _np

from .. import io as io_mod
from .. import metric as metric_mod
from .. import telemetry as _telemetry
from ..initializer import Uniform
from ..model import BatchEndParam
from ..ndarray.ndarray import concatenate

__all__ = ["BaseModule"]

# per-step wall time of the fit loop body (dispatch + staging + metric
# bookkeeping — NOT device completion, which is async; the benchmark
# reads launch counters and the device trace for that reason)
FIT_STEP_MS = _telemetry.REGISTRY.histogram(
    "fit_step_ms", "wall time of one fit-loop step (host side)",
    unit="ms")


def _callbacks(spec):
    """Normalise a callback spec (None | callable | list) to a tuple."""
    if spec is None:
        return ()
    if callable(spec):
        return (spec,)
    return tuple(spec)


def _ensure_metric(m):
    return m if isinstance(m, metric_mod.EvalMetric) else metric_mod.create(m)


def _trim_pad(arrays, pad):
    """Drop the iterator's pad rows from each output array."""
    if not pad:
        return list(arrays)
    return [a[: a.shape[0] - pad] for a in arrays]


def _check_input_names(symbol, names, typename, throw):
    """Warn/raise when a user-declared input name is absent from the graph."""
    known = set(symbol.list_arguments()) | set(symbol.list_auxiliary_states())
    for name in names:
        if name in known:
            continue
        msg = (f"You created Module with Module(..., {typename}_names={names}) "
               f"but input with name '{name}' is not found in "
               f"symbol.list_arguments().")
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class _Prefetcher:
    """One-step-lookahead wrapper over a DataIter.

    ``advance()`` returns the staged batch and immediately pulls + stages the
    next one, so host-side staging (including sparse row-id pulls via
    ``module.prepare``) overlaps the device executing the current step.
    ``peek_done`` is True once the underlying iterator is exhausted, letting
    the loop know the batch in hand is the last.
    """

    def __init__(self, data_iter, module, sparse_row_id_fn=None):
        self._it = iter(data_iter)
        self._mod = module
        self._row_fn = sparse_row_id_fn
        self._staged = None
        self._pull()

    def _pull(self):
        try:
            self._staged = next(self._it)
        except StopIteration:
            self._staged = None

    @property
    def has_next(self):
        return self._staged is not None

    def advance(self):
        batch = self._staged
        self._pull()
        return batch

    def stage_next(self):
        """Stage the already-fetched lookahead batch (sparse row pulls etc.).
        Called after the current step's ``update`` so staged rows reflect
        post-update parameter values."""
        if self._staged is not None:
            self._mod.prepare(self._staged, sparse_row_id_fn=self._row_fn)


class BaseModule:
    """Abstract train/eval surface; concrete modules implement the
    bind/forward/backward/update primitives and inherit the loops."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def forward_backward(self, data_batch):
        """One fused fwd+bwd dispatch (a single XLA program downstream)."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def fit_step(self, data_batch, eval_metric=None):
        """One training step: the eager pair — a fused fwd+bwd dispatch,
        then the optimizer/kvstore update. Subclasses may fuse further
        (Module routes eligible configs through module/fused_fit.py as
        ONE donated program) and return True to signal the whole step —
        including device-side metric accumulation — ran as a single
        launch, making the loop's ``update_metric`` call a no-op."""
        self.forward_backward(data_batch)
        self.update()
        return False

    def _fit_sync(self):
        """Block until in-flight device work completes — the bounded-
        async-depth hook behind ``MXNET_FIT_SYNC_EVERY`` (overridden by
        Module; a no-op for modules without device-resident state)."""
        pass

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None, checkpoint_every=None,
            checkpoint_prefix=None):
        """Train for ``num_epoch`` epochs.  Signature parity with the
        reference ``fit`` (base_module.py:399); loop structure is the
        prefetched design described in the module docstring.

        ``checkpoint_every``/``checkpoint_prefix`` (env:
        ``MXNET_CHECKPOINT_EVERY`` / ``MXNET_CHECKPOINT_PREFIX``) arm
        mx.checkpoint (docs/CHECKPOINT.md): every N steps the COMPLETE
        training state — params, optimizer state, error-feedback
        residuals, RNG, lr position — snapshots at the step boundary
        and commits on a background writer; the loop blocks only for
        the device→host copy, the fused-step zero-retrace guarantee is
        untouched, and a SIGTERM triggers an emergency synchronous save
        plus graceful drain before ``fit`` returns."""
        if num_epoch is None:
            raise ValueError("please specify number of epochs")

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        train_metric = _ensure_metric(eval_metric)
        val_metric = validation_metric or train_metric
        on_batch = _callbacks(batch_end_callback)
        on_epoch = _callbacks(epoch_end_callback)

        ckpt = self._make_checkpointer(checkpoint_every, checkpoint_prefix)
        # pod health (straggler exchange) + hang watchdog — both no-ops
        # unless armed (multi-process world / env; docs/OBSERVABILITY.md)
        health = _telemetry.PodHealthMonitor.maybe_create(self.logger)
        # pod metrics aggregation + SLO rule evaluation on the merged
        # view (multi-process world, MXNET_SENTINEL_EVERY, or installed
        # sentinel rules — docs/OBSERVABILITY.md)
        sentinel = _telemetry.PodMetricsAggregator.maybe_create(
            self.logger)
        watchdog = None
        if float(os.environ.get("MXNET_WATCHDOG_FACTOR", "0") or 0) > 0:
            watchdog = _telemetry.Watchdog("fit")
        try:
            for epoch in range(begin_epoch, num_epoch):
                preempted = self._run_train_epoch(
                    epoch, train_data, train_metric, monitor, on_batch,
                    sparse_row_id_fn, ckpt, health, watchdog, sentinel)
                if preempted:
                    self.logger.warning(
                        "Epoch[%d] preempted — emergency checkpoint "
                        "committed, stopping fit", epoch)
                    return
                # Sync params out of the device-side optimizer state once
                # per epoch so epoch callbacks (checkpointing) see current
                # values.
                arg_now, aux_now = self.get_params()
                self.set_params(arg_now, aux_now)
                for cb in on_epoch:
                    cb(epoch, self.symbol, arg_now, aux_now)
                if eval_data is not None:
                    scores = self.score(
                        eval_data, val_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch)
                    for name, val in scores:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                train_data.reset()
        finally:
            if watchdog is not None:
                watchdog.disarm()
            if ckpt is not None:
                ckpt.close()        # drain pending writes, restore signals

    def _make_checkpointer(self, checkpoint_every, checkpoint_prefix):
        """A CheckpointManager when step checkpointing is requested (arg
        or env), else None."""
        every = checkpoint_every if checkpoint_every is not None \
            else int(os.environ.get("MXNET_CHECKPOINT_EVERY", "0") or 0)
        if not every:
            if checkpoint_prefix \
                    or os.environ.get("MXNET_CHECKPOINT_PREFIX"):
                self.logger.warning(
                    "checkpoint prefix given but checkpoint_every/"
                    "MXNET_CHECKPOINT_EVERY is unset — checkpointing is "
                    "NOT armed")
            return None
        prefix = checkpoint_prefix \
            or os.environ.get("MXNET_CHECKPOINT_PREFIX") or "checkpoint"
        from ..checkpoint import CheckpointManager
        return CheckpointManager(prefix, module=self, every=every,
                                 logger=self.logger)

    def _run_train_epoch(self, epoch, train_data, train_metric, monitor,
                         on_batch, sparse_row_id_fn, ckpt=None,
                         health=None, watchdog=None, sentinel=None):
        """One epoch: keep the device queue full, read metrics back only
        at callback boundaries. With the fused fit step active, the loop
        body performs ZERO blocking host syncs — metrics accumulate on
        device and step N+1 dispatches while step N executes; the
        ``MXNET_FIT_SYNC_EVERY`` env var (0 = unbounded, the default)
        bounds how many steps may be in flight. ``ckpt`` (a
        CheckpointManager) ticks at each step boundary; returns True
        when the epoch stopped early on a preemption (emergency
        checkpoint already committed). ``health`` (PodHealthMonitor)
        exchanges per-rank step times on its cadence; ``watchdog``
        heartbeats around each step (both host-only; mx.trace spans
        bracket the step and its children when tracing is enabled —
        docs/OBSERVABILITY.md)."""
        t0 = time.time()
        train_metric.reset()
        flow = _Prefetcher(train_data, self, sparse_row_id_fn)
        sync_every = int(os.environ.get("MXNET_FIT_SYNC_EVERY", "0") or 0)
        tracing = _telemetry.tracing
        nbatch = 0
        while flow.has_next:
            # the fit.step span parents every child opened inside —
            # prefetch data-wait (flow.advance may block on the input
            # pipeline), fused dispatch, kvstore push/pull — so one
            # step renders as one subtree. FIT_STEP_MS keeps its
            # historical meaning (dispatch + staging + bookkeeping,
            # data-wait excluded — that one has io_data_wait_ms).
            with tracing.span("fit.step", epoch=epoch, step=nbatch) as sp:
                batch = flow.advance()
                if monitor is not None:
                    monitor.tic()
                t_step = time.perf_counter()
                if watchdog is not None:
                    watchdog.begin()
                # fit_step enqueues async XLA work (one donated program
                # when fused); while the device runs, the host stages
                # the (already-fetched) next batch. update_metric is a
                # no-op for batches the fused step already folded on
                # device.
                self.fit_step(batch, train_metric)
                flow.stage_next()
                self.update_metric(train_metric, batch.label)
                step_ctx = getattr(sp, "context", None)
            if watchdog is not None:
                watchdog.end()
            # telemetry (all host-side, nothing enters traced code):
            # step-time histogram, flight-recorder cadence, chrome-trace
            # step marker — each a no-op-cheap call when idle
            step_ms = (time.perf_counter() - t_step) * 1e3
            FIT_STEP_MS.observe(step_ms)
            if health is not None:
                health.step(step_ms)
            if sentinel is not None:
                # an exchange step first drains the pipeline through the
                # EXISTING sync boundary (_fit_sync publishes the
                # in-launch sentinel scalars), so the shipped snapshot
                # carries fresh numerics; off-cadence steps pay one
                # attribute check
                if sentinel.due():
                    self._fit_sync()
                sentinel.step()
            _telemetry.RECORDER.tick()
            _telemetry.mark_step(nbatch)
            if monitor is not None:
                monitor.toc_print()
            if on_batch:
                info = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                     eval_metric=train_metric, locals=None)
                for cb in on_batch:
                    cb(info)
            nbatch += 1
            if sync_every and nbatch % sync_every == 0:
                self._fit_sync()
            # checkpoint tick LAST: the step (and its metric fold) is
            # fully dispatched, so the snapshot sees post-step handles.
            # Its span parents under the (already-ended) step span —
            # parent links are ids, a closed parent is fine.
            if ckpt is not None:
                with tracing.span("checkpoint.tick", parent=step_ctx):
                    if ckpt.tick(epoch=epoch):
                        return True
        # epoch boundary: the one scheduled metric readback of the epoch
        for name, val in train_metric.get_name_value():
            self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
        self.logger.info("Epoch[%d] Time cost=%.3f", epoch, time.time() - t0)
        return False

    # ------------------------------------------------------------------
    # evaluation / inference
    # ------------------------------------------------------------------
    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0, sparse_row_id_fn=None):
        """Run ``eval_data`` through forward and accumulate ``eval_metric``."""
        if not (self.binded and self.params_initialized):
            raise RuntimeError("score() requires bind() + init_params()")
        if reset:
            eval_data.reset()
        eval_metric = _ensure_metric(eval_metric)
        eval_metric.reset()
        on_batch = _callbacks(batch_end_callback)

        seen = 0
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.prepare(batch, sparse_row_id_fn=sparse_row_id_fn)
            self.forward(batch, is_train=False)
            self.update_metric(eval_metric, batch.label)
            for cb in on_batch:
                cb(BatchEndParam(epoch=epoch, nbatch=nbatch,
                                 eval_metric=eval_metric, locals=None))
            seen += 1
        for cb in _callbacks(score_end_callback):
            cb(BatchEndParam(epoch=epoch, nbatch=seen,
                             eval_metric=eval_metric, locals=None))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Yield ``(outputs, nbatch, batch)`` per forward pass (pad-trimmed)."""
        if not (self.binded and self.params_initialized):
            raise RuntimeError("iter_predict() requires bind() + init_params()")
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(batch, is_train=False)
            yield _trim_pad(self.get_outputs(), batch.pad), nbatch, batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Forward every batch; by default concatenate per-output across
        batches (and unwrap a single output, matching the reference)."""
        if not (self.binded and self.params_initialized):
            raise RuntimeError("predict() requires bind() + init_params()")
        if isinstance(eval_data, _np.ndarray) or hasattr(eval_data, "shape"):
            eval_data = io_mod.NDArrayIter(eval_data,
                                           batch_size=eval_data.shape[0])
        if reset:
            eval_data.reset()

        per_batch = []
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(batch, is_train=False)
            per_batch.append([o.copy() for o in
                              _trim_pad(self.get_outputs(), batch.pad)])
        if not per_batch:
            return per_batch
        if not merge_batches:
            return per_batch
        widths = {len(outs) for outs in per_batch}
        if len(widths) != 1:
            raise ValueError("Cannot merge batches: different number of outputs")
        merged = [concatenate([outs[i] for outs in per_batch])
                  for i in range(widths.pop())]
        if len(merged) == 1 and not always_output_list:
            return merged[0]
        return merged

    # ------------------------------------------------------------------
    # parameter persistence
    # ------------------------------------------------------------------
    def save_params(self, fname):
        """Save current params in the reference's ``arg:``/``aux:`` layout."""
        from .. import ndarray as nd
        arg_params, aux_params = self.get_params()
        blob = {f"arg:{k}": v for k, v in arg_params.items()}
        blob.update({f"aux:{k}": v for k, v in aux_params.items()})
        nd.save(fname, blob)

    def load_params(self, fname):
        """Load params saved by :meth:`save_params` (reference layout)."""
        from .. import ndarray as nd
        arg_params, aux_params = {}, {}
        for key, value in nd.load(fname).items():
            kind, _, name = key.partition(":")
            if kind == "arg":
                arg_params[name] = value
            elif kind == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    # ------------------------------------------------------------------
    # abstract surface (implemented by Module / BucketingModule / ...)
    # ------------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        raise NotImplementedError

    def install_monitor(self, mon):
        raise NotImplementedError

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        raise NotImplementedError

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError
