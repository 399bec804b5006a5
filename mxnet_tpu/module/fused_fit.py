"""Single-launch fit step (docs/TRAINING.md).

The eager Module fit step costs ~32 device launches: one fused fwd+bwd
program (executor.py) plus one compiled program per kvstore bucket
(kvstore_fused.py), with a blocking ``asnumpy`` in ``update_metric``
every batch. ``FusedFitStep`` collapses all of it into ONE jitted XLA
program per step for eligible configurations:

    forward + backward (jax.vjp over the compiled graph_fn)
      -> 2-bit quantize with donated error-feedback residual (optional)
      -> cross-device reduce (GSPMD psum when the batch is mesh-sharded)
      -> fused optimizer apply (Optimizer._fused_fit_sig)
      -> device-side metric accumulation (EvalMetric.device_fn)

What the program hands back of the graph's outputs is what a reader can
use: a loss head's float32 probabilities (tokens x vocabulary elements,
the largest array of a language model's step) are not written for a
training loop that reads only its metric. The program returns the
head's stem, the logits as their producer wrote them, and
``get_outputs()`` builds the head from it on demand (loss_head.py;
docs/TRAINING.md, "What a fused step returns").

Parameters, optimizer state, residuals, aux states, the scaler's and the
sentinel's carry are DONATED, so HBM holds one copy of the training state
and a steady-state step is a single device launch with zero host syncs —
the same shape as parallel/trainer.py's TrainStep, brought to the
Module/kvstore path that ``fit``, ``model.py``, and user scripts use.

What a step derives from the module, the optimizer and the metric, and
not from the batch, is its PLAN (``_StepPlan``): derived at the first
step, kept with the compiled program, and derived again only when
something it rests on was replaced (``FusedFitStep._plan_holds``;
counter ``fit_plan_builds``).  A step then places its batch, reads the
buffers off the plan's flat lists, advances the optimizer's counts and
launches.

Eligibility (checked once per optimizer init, cheaply re-checked per
batch): dense f32/f16/bf16 params with grad_req='write', an optimizer
describing its update via the shared fused-update protocol
(``_fused_fit_sig`` non-None — SGD, Adam, LAMB, RMSProp, AdaGrad,
Adamax, Nadam, LBSGD, each with or without multi-precision
``(inner, weight32)`` master-weight state), a local/device kvstore (or
none) with or without 2-bit compression, no installed monitor, no
inputs_need_grad. Everything else falls back to the eager fwd_bwd +
bucketed-kvstore path unchanged; error-feedback residuals move between
the two paths through the same spill/reseed mechanism the bucketed
engine uses, so no accumulated residual is lost.

Low-precision (bf16/f16) training is first-class: master weights and
optimizer state stay f32 inside the same donated program, 2-bit
residuals operate on the f32 master-gradient view, and a
``DynamicLossScaler`` (fused_update.py) rides along — its scale is a
runtime scalar, the inf/nan overflow check is folded into the program,
and the skip-update decision is a ``lax.cond``, so overflow handling
costs zero host syncs. A gradient reaches that ``cond`` in the dtype
the backward wrote it and is widened by the instruction that consumes
it: an operand of a conditional is a buffer in HBM, and a float32 view
of every bf16 gradient there is a second, wider copy of it.

The compiled step is cached per SYMBOL (sharing executables across
rebinds like executor._compiled_cache) and keyed by everything that
changes the program — param set, compression threshold, optimizer
signature, state templates, multi-precision flags, metric signature,
loss-scaler config. ``rescale_grad``, lr, wd, per-key extra scalars,
and the loss scale ride as runtime arguments, and jax's shape-keyed
jit cache handles ragged final batches: each distinct batch shape
traces once (``TRACE_COUNT``), steady state never retraces.
"""
from __future__ import annotations

import os
import weakref
from time import perf_counter_ns as _now_ns

import numpy as _np
import jax
import jax.numpy as jnp

from ..ndarray import NDArray
from .. import config as _config
from .. import fused_update as _fused
from .. import loss_head as _loss_head
from .. import optimizer as opt_mod
from .. import telemetry as _telemetry
from ..kvstore import KVStore, _updater_key
from ..kvstore_fused import two_bit_quantize
from ..executor import (_build_graph_fn, _compiled_cache, _count_dispatch,
                        _dispatch_span)
from ..model import _local_updater_key

__all__ = ["FusedFitStep", "TRACE_COUNT"]


def _fusable_kv(kv):
    """Stores whose reduce can live INSIDE the fit program: the plain
    local store, and kvstore='tpu' when compiled programs may span its
    world (every single-process world; multi-process only on backends
    whose XLA runtime executes cross-process programs — on the CPU
    backend a multi-process tpu kvstore keeps the eager fwd_bwd +
    collective-push path instead)."""
    from ..kvstore_tpu import KVStoreTPU
    if type(kv) is KVStore:
        return True
    return isinstance(kv, KVStoreTPU) and kv._gspmd_ok


def _global_fit_mesh(kv, n_local):
    """The 'dp' mesh of a multi-process fused fit step: every process
    contributes its first ``n_local`` devices, so the global batch
    shards process-major and the vjp's gradient psum spans hosts."""
    from ..kvstore_tpu import KVStoreTPU
    if not isinstance(kv, KVStoreTPU) or kv.num_workers == 1:
        return None
    from jax.sharding import Mesh
    devs = []
    for p in range(jax.process_count()):
        mine = [d for d in jax.devices() if d.process_index == p][:n_local]
        if len(mine) < n_local:
            return False        # a process with fewer devices: not fusable
        devs.extend(mine)
    # analyze: ok(hostsync) mesh construction from host device handles, once per build, no device data
    return Mesh(_np.array(devs), ("dp",))

# incremented inside the step function at trace time only; steady-state
# steps (including repeats of a ragged batch shape) leave it untouched.
# The count lives in the mx.telemetry registry (fit_step_retraces); the
# module-level ``TRACE_COUNT`` name stays a live alias via __getattr__.
FIT_RETRACES = _telemetry.REGISTRY.counter(
    "fit_step_retraces",
    "fused fit-step program (re)traces (the TRACE_COUNT witness)",
    vital=True)
# shared RetraceSite semantics with executor / kvstore_fused: the step
# body calls _note_retrace() at trace time; the launch times through it
_SITE = _telemetry.RetraceSite(FIT_RETRACES, _telemetry.JIT_COMPILE_MS,
                               site="fit_step")
_note_retrace = _SITE.note
# what a step derives from the module, the optimizer and the metric is
# derived once (FusedFitStep._build_plan): one over a steady run, one
# more whenever something the plan rests on was replaced
FIT_PLAN_BUILDS = _telemetry.REGISTRY.counter(
    "fit_plan_builds",
    "step plans the fused fit step built or rebuilt (one over a steady run)",
    vital=True)


def __getattr__(name):
    if name == "TRACE_COUNT":
        return int(FIT_RETRACES.value)
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))


# sample an HBM StepMemoryTracker every N fused launches (0 = off; a
# live-array census per step is not free on the host)
_MEM_EVERY = int(os.environ.get("MXNET_TELEMETRY_MEMORY_EVERY", "0") or 0)


def _sentinel_enabled():
    """In-launch numerics sentinels (docs/OBSERVABILITY.md): a handful
    of scalars — global grad norm, non-finite element count, metric
    EMA z-score, residual-norm drift — folded into the SAME donated
    program and read only at sync boundaries. On by default (the
    overhead contract is zero extra dispatches/syncs, held by
    tests/test_sentinel.py); ``MXNET_SENTINEL_NUMERICS=0`` disables."""
    from ..telemetry.sentinel import numerics_enabled
    return numerics_enabled()


# EMA decay for the sentinel metric/residual baselines, and how many
# steps the z-score stays muted while the baseline converges
_SENT_DECAY = 0.98
_SENT_WARMUP = 8.0


def _metric_closure(metric, label_names, output_names):
    """(metric_fn, cache_sig) folding ``metric``'s device accumulation
    into the step program with ``update_dict``'s output/label selection
    semantics; (None, None) when the metric accumulates on the host.
    Where the program deferred a loss head, ``outs`` holds a
    ``loss_head.DeferredHead`` in its place: a metric that reads the
    probabilities at the labels only takes it as it is, every other
    metric gets the head's full value, built inside the program."""
    fn = metric.device_fn() if metric is not None else None
    if fn is None:
        return None, None
    at_labels = metric.device_reads_at_labels
    out_sel = tuple(metric.output_names) if metric.output_names else None
    lab_sel = tuple(metric.label_names) if metric.label_names else None
    label_names = tuple(label_names)
    output_names = tuple(output_names)

    def metric_fn(inputs, outs):
        pred_d = dict(zip(output_names, outs))
        preds = ([pred_d[n] for n in out_sel if n in pred_d]
                 if out_sel is not None else list(outs))
        if not at_labels:
            preds = [p.value if isinstance(p, _loss_head.DeferredHead)
                     else p for p in preds]
        names = lab_sel if lab_sel is not None else label_names
        labels = [inputs[n] for n in names if n in inputs]
        return fn(labels, preds)

    sig = (type(metric).__name__, metric.device_sig(), out_sel, lab_sel,
           label_names, output_names)
    return metric_fn, sig


def _build_fit_program(graph_fn, param_order, threshold, mode, tpls,
                       mp_flags, use_wd, metric_fn, mirror, scaler,
                       sentinel=False, heads=()):
    """ONE jitted program: fwd+bwd+compress+reduce+update(+metric).

    With ``heads`` (loss_head.HeadPlan s; ``graph_fn`` then returns their
    stems as a third result) the program returns each such head's stem,
    after the outputs, and None in the head's own place.  The head is
    traced all the same, so its ``custom_vjp`` writes the gradient it
    always wrote; its forward value is left to the metric, and with no
    reader the compiler drops it.

    The compress and optimizer math are the SAME functions the bucketed
    kvstore step compiles (kvstore_fused.two_bit_quantize and the
    fused_update builder, themselves mirroring ops/optimizer_ops.py),
    so fused weights match the eager path within FMA-contraction ulps
    (tests/test_fused_fit.py pins the tolerance).

    With a loss scaler, the entire compress+update block sits under a
    ``lax.cond`` on a device-side finiteness check of the gradients
    as the backward wrote them — an overflow step updates neither weights,
    nor optimizer state, nor error-feedback residuals — and the
    scaler's (scale, good_steps, skips) triple is donated through the
    program so skip bookkeeping never touches the host. The scale
    itself stays a runtime scalar in that triple; MXNet loss heads
    (SoftmaxOutput & co) generate their own gradient independent of
    the output cotangent, so the backward chain is not cotangent-
    scaled — see docs/TRAINING.md on why bf16's f32-matched exponent
    range makes overflow DETECTION, not underflow scaling, the useful
    half of the scaler here."""
    upd = _fused.build(mode)

    # analyze: ok(retrace) upd is a pure memoized function of `mode`, which is a builder parameter and part of the fit-program cache key
    def step(params, states, residuals, macc, scaler_state, sent_state,
             inputs, auxs, lr_vec, wd_vec, rescale, extra, seed):
        _note_retrace()   # trace-time host side effect only

        def f(p):
            outs, new_auxs, *stems = graph_fn({**inputs, **p}, auxs, seed,
                                              True)
            return outs, (new_auxs, stems[0] if stems else ())

        if mirror:
            # MXNET_BACKWARD_DO_MIRROR: rematerialize the forward
            # (jax.checkpoint), matching executor._make_fwd_bwd
            f = jax.checkpoint(f)
        outs, vjp_fn, (new_auxs, stems) = jax.vjp(f, params, has_aux=True)
        cts = [jnp.ones_like(o) for o in outs]
        (grads,) = vjp_fn(cts)
        results = list(outs)
        if heads:
            outs = list(outs)
            for plan, stem in zip(heads, stems):
                outs[plan.index] = _loss_head.DeferredHead(
                    plan, stem, outs[plan.index])
                results[plan.index] = None

        # a gradient crosses into the update stage in the dtype the
        # backward wrote it: an operand of the scaler's ``lax.cond`` is
        # a buffer in HBM, so a float32 view built HERE would cost every
        # bf16 gradient a second, twice as wide, copy. Each consumer
        # widens in registers instead (apply_one, the 2-bit arm below,
        # the sentinel's sums); bf16 -> f32 is exact, so each sees the
        # value the backward wrote (docs/TRAINING.md, Mixed precision)
        f32 = jnp.float32

        def apply_updates(_):
            # 2-bit quantize with donated error-feedback residual, on
            # the f32 master-gradient view; a mesh-sharded batch
            # already yielded psum-reduced (replicated) grads from the
            # vjp, so there is no separate reduce stage to launch
            new_res, red = {}, {}
            for name in param_order:
                if threshold is not None:
                    red[name], new_res[name] = two_bit_quantize(
                        residuals[name], grads[name].astype(f32),
                        threshold)
                else:
                    red[name] = grads[name]
            new_ps, new_ss = {}, {}
            for i, name in enumerate(param_order):
                st = _fused.unflatten(tpls[i], states[name])
                e = extra[i] if upd.n_extra else ()
                new_w, new_s = _fused.apply_one(
                    upd, params[name], red[name], st, mp_flags[i],
                    lr_vec[i], wd_vec[i], rescale, e, use_wd)
                new_ps[name] = new_w
                new_ss[name] = tuple(_fused.flatten_state(new_s)[0])
            return (new_ps, new_ss,
                    new_res if threshold is not None else residuals)

        # stable region names in every instruction's op_name, whatever
        # the compiler calls the instruction: fit.update (also the
        # scaler's cond and what sits under it), fit.metric,
        # fit.sentinel; the graph's own nodes carry <op>/<node name>
        # (executor._build_graph_fn)
        #
        # every reader of a gradient outside the update is ONE
        # expression per gradient (the scaler's ``finite`` is the
        # sentinel's non-finite count == 0): the compiler then carries
        # the scalars out of the fusion that writes the gradient, and
        # nothing reads it again before the update
        gnsq = nonfin = f32(0.0)
        if sentinel or scaler is not None:
            with jax.named_scope("fit.sentinel" if sentinel
                                 else "fit.update"):
                for name in param_order:
                    g = grads[name]
                    nonfin = nonfin + jnp.sum(
                        (~jnp.isfinite(g)).astype(f32))
                    if sentinel:
                        gnsq = gnsq + jnp.sum(jnp.square(g.astype(f32)))
        if scaler is not None:
            with jax.named_scope("fit.update"):
                finite = nonfin == 0.0
                new_ps, new_ss, new_res = jax.lax.cond(
                    finite, apply_updates,
                    lambda _: (params, states, residuals), None)
                new_scaler = scaler.step_fn(finite, scaler_state)
        else:
            with jax.named_scope("fit.update"):
                new_ps, new_ss, new_res = apply_updates(None)
            new_scaler = scaler_state

        bsum = bnum = None
        if metric_fn is not None:
            with jax.named_scope("fit.metric"):
                bsum, bnum = metric_fn(inputs, outs)
                macc = (macc[0] + bsum, macc[1] + bnum)

        new_sent = sent_state
        if sentinel:
            with jax.named_scope("fit.sentinel"):
                # in-launch numerics witnesses: a few reductions over
                # arrays this program already holds, carried in one donated
                # f32[8] vector — [metric_ema, metric_var, n_steps,
                # cum_nonfinite, grad_norm, zscore, residual_ema,
                # residual_drift]. Same launch, zero host syncs; the host
                # reads it only at sync boundaries (publish_sentinels).
                gnorm = jnp.sqrt(gnsq)
                if bsum is not None:
                    mval = (bsum / jnp.maximum(bnum, 1)).astype(jnp.float32)
                else:
                    mval = gnorm    # no device metric: track the grad norm
                ema, emvar, n, cnf, rema = (sent_state[0], sent_state[1],
                                            sent_state[2], sent_state[3],
                                            sent_state[6])
                d = mval - ema
                z = jnp.where(n >= _SENT_WARMUP,
                              d * jax.lax.rsqrt(emvar + jnp.float32(1e-12)),
                              jnp.float32(0.0))
                # a non-finite sample must trip the z-score/counter, not
                # poison the running baseline forever
                ok = jnp.isfinite(mval)
                new_ema = jnp.where(ok, ema + (1.0 - _SENT_DECAY) * d, ema)
                new_var = jnp.where(
                    ok, _SENT_DECAY * (emvar + (1.0 - _SENT_DECAY) * d * d),
                    emvar)
                if threshold is not None:
                    rnsq = jnp.float32(0.0)
                    for name in param_order:
                        rnsq = rnsq + jnp.sum(jnp.square(new_res[name]))
                    rnorm = jnp.sqrt(rnsq)
                    drift = jnp.where(rema > 0.0,
                                      rnorm / (rema + jnp.float32(1e-30)),
                                      jnp.float32(1.0))
                    new_rema = _SENT_DECAY * rema \
                        + (1.0 - _SENT_DECAY) * rnorm
                else:
                    drift = jnp.float32(0.0)
                    new_rema = rema
                new_sent = jnp.stack(
                    [new_ema, new_var, n + 1.0, cnf + nonfin, gnorm, z,
                     new_rema, drift]).astype(jnp.float32)
        return (new_ps, new_ss, new_res, macc, new_scaler, new_sent,
                new_auxs, results, stems)

    # params/states/residuals/scaler/sentinels/auxs donate in place.  The
    # metric's pair (argument 3, eight bytes) does not: the step after a
    # ``metric.reset()`` is handed the plan's one pair of zeros, which
    # then serves every reset
    donate = (0, 1, 2, 4, 5, 7)
    fn = jax.jit(step, donate_argnums=donate)
    _telemetry.programs.note_donation(fn, donate)
    return fn


_MISSING = object()


class _StepPlan:
    """What a fused step derives from the module, the optimizer and the
    metric, and not from the batch.  ``FusedFitStep._build_plan`` fills
    it, ``_plan_holds`` says each step whether it still stands.

    ``group`` ``optimizer`` ``mode`` ``multi_precision`` ``metric``
    ``mirror`` ``scaler_sig`` ``sent_on`` and ``state_objs`` (the
    updater's state object of each parameter, as flattened) are what it
    is held against; ``order`` ``ukeys`` the parameters' names and
    updater keys; ``weights`` ``leaves`` ``fixed`` the NDArrays a step
    reads its buffers off and hands the results back to (each state
    flattened once); ``tpls`` ``mp_flags`` ``msig`` ``heads`` the
    program's static description, ``graph_fn`` ``metric_fn`` ``tail``
    what it is built from; ``programs`` the compiled step by ``use_wd``;
    ``zeros`` the metric's pair after a reset."""

    __slots__ = ("group", "optimizer", "mode", "multi_precision", "metric",
                 "mirror", "scaler_sig", "sent_on", "state_objs",
                 "order", "ukeys", "weights", "leaves", "fixed", "tpls",
                 "mp_flags", "metric_fn", "msig", "heads", "tail",
                 "graph_fn", "programs", "zeros")


class FusedFitStep:
    """Per-Module driver for the single-launch fit step."""

    def __init__(self, module, updater, kv, threshold, mode, pmesh=None,
                 scaler=None):
        self._mod = module
        self._updater = updater
        self._kv = kv                 # None, plain local KVStore, or tpu
        self._threshold = threshold
        self._mode = mode             # optimizer._fused_fit_sig() at build
        self._scaler = scaler         # DynamicLossScaler (low-prec params)
        # multi-process tpu kvstore on an accelerator backend: the fit
        # program runs over this global 'dp' mesh — the vjp's gradient
        # reduction becomes the cross-host psum, keeping one launch and
        # zero host syncs per step on a pod (None single-process)
        self._pmesh = pmesh or None
        self._residuals = None        # name -> jnp residual (2-bit arm)
        # step-invariant caches (the whole FusedFitStep is rebuilt on
        # rebind/init_optimizer, so these live as long as they are valid)
        self._order = None            # trainable param names, arg order
        self._ukeys = None            # matching updater state keys
        self._plan = None             # _StepPlan, while it holds
        # the carry as the last launch left it (metric pair, scaler
        # triple, sentinel vector): committed where the program runs
        self._carry_out = ()
        # donated sentinel vector (f32[8], see _build_fit_program) and
        # the cumulative non-finite count already pushed to the registry
        self._sent_state = None
        self._published_nonfinite = 0.0
        # (output index, held_first, held_count, top_k, rows_slack) when
        # the graph hands out its experts' token counts (telemetry.moe),
        # else None
        self._moe_counts = _telemetry.moe.find(module._symbol)
        # output index of the sparse attention's live-tile counts
        # (telemetry.dsa), else None
        self._dsa_tiles = _telemetry.dsa.find(module._symbol)
        # output index of a diffusion head's masked-row counts
        # (telemetry.diffusion), else None
        self._diffusion_rows = _telemetry.diffusion.find(module._symbol)
        self.launches = 0
        self._mem_tracker = _telemetry.StepMemoryTracker() \
            if _MEM_EVERY else None
        self._register_memory_groups()

    def _register_memory_groups(self):
        """Publish this step's donation sets to telemetry.memory so
        ``memory_snapshot()`` can attribute HBM to params / optimizer
        states / residuals / auxs (the 'one copy of training state'
        breakdown). Providers hold a weakref: a dead step contributes
        nothing, and the latest-built step wins the group names."""
        ref = weakref.ref(self)

        def provider(kind):
            def arrays():
                s = ref()
                if s is None or s._order is None:
                    return ()
                try:
                    exe = s._mod._exec_group._exec
                    if kind == "params":
                        return [exe.arg_dict[n]._data for n in s._order]
                    if kind == "auxs":
                        return list(exe._auxs_values().values())
                    if kind == "residuals":
                        return list((s._residuals or {}).values())
                    if kind == "opt_states":
                        out = []
                        for uk in (s._ukeys or ()):
                            leaves, _ = _fused.flatten_state(
                                s._updater.states.get(uk))
                            out.extend(l._data for l in leaves
                                       if hasattr(l, "_data"))
                        return out
                except Exception:
                    return ()
                return ()
            return arrays

        for kind in ("params", "opt_states", "residuals", "auxs"):
            _telemetry.memory.track_group(kind, provider(kind))

    # -- construction ---------------------------------------------------
    @staticmethod
    def build(module):
        """A FusedFitStep when ``module``'s configuration is eligible,
        else None (the fit loop then keeps the eager path)."""
        def no(reason):
            dbg = getattr(module.logger, "debug", None)
            if dbg:
                dbg("fused fit step disabled: %s", reason)
            return None

        # the env kill-switch is snapshotted into _fused_fit_enabled by
        # Module.__init__ — one source of truth for both knobs
        if not getattr(module, "_fused_fit_enabled", True):
            return no("disabled on this module")
        group = module._exec_group
        exe = group._exec
        if exe._group_devices is not None:
            return no("group2ctx-placed (model-parallel) executor")
        if module.inputs_need_grad:
            return no("inputs_need_grad")
        optimizer = module._optimizer
        sig = optimizer._fused_fit_sig()
        if sig is None:
            return no("optimizer %s has no fused signature"
                      % type(optimizer).__name__)
        if not _fused.supported(sig):
            return no("unsupported fused kind %r" % (sig[0],))
        kv = module._kvstore
        if module._update_on_kvstore:
            if not _fusable_kv(kv):
                return no("update_on_kvstore with %s" % type(kv).__name__)
            updater = kv._updater
        else:
            if kv is not None and not _fusable_kv(kv):
                return no("dist kvstore")
            updater = module._updater
        pmesh = _global_fit_mesh(kv, len(module._context))
        if pmesh is False:
            return no("uneven device counts across tpu kvstore processes")
        if not isinstance(updater, opt_mod.Updater):
            return no("custom updater")
        if updater.optimizer is not optimizer:
            return no("updater/optimizer mismatch")
        threshold = None
        comp = kv._compression if kv is not None else None
        if comp is not None:
            thr = getattr(comp, "threshold", None)
            if thr is None:
                return no("unsupported gradient compression")
            threshold = float(thr)
        low_prec = False
        for name in group.param_names:
            arr = exe.arg_dict.get(name)
            if arr is None or exe._grad_req.get(name, "null") == "null":
                continue
            if exe._grad_req[name] != "write":
                return no("grad_req %r on %s" % (exe._grad_req[name], name))
            if getattr(arr, "stype", "default") != "default" \
                    or (arr.dtype != _np.float32
                        and not _fused.is_low_precision(arr.dtype)):
                return no("non-dense-float param %s" % name)
            low_prec = low_prec or _fused.is_low_precision(arr.dtype)
        scaler = None
        if low_prec:
            # the scaler lives on the MODULE so it survives rebinds /
            # init_optimizer and round-trips through checkpoints
            scaler = getattr(module, "_loss_scaler", None)
            if scaler is None:
                scaler = _fused.DynamicLossScaler.from_config()
                module._loss_scaler = scaler   # None when scaling is off
        step = FusedFitStep(module, updater, kv, threshold, sig,
                            pmesh=pmesh, scaler=scaler)
        if not step._param_order():
            return no("no trainable parameters")
        return step

    # -- helpers --------------------------------------------------------
    def _param_order(self):
        group = self._mod._exec_group
        exe = group._exec
        return [n for n in group.param_names
                if n in exe.arg_dict
                and exe._grad_req.get(n, "null") != "null"]

    def _ukey(self, index, name):
        """Updater state key — matches what the eager path would use so
        optimizer state saved by one path loads into the other."""
        if self._mod._update_on_kvstore:
            return _updater_key(name)
        return _local_updater_key(index)

    def _place(self, group, exe, name, value):
        dst = exe.arg_dict[name]
        if self._pmesh is not None:
            # each process contributes its LOCAL batch as its rows of
            # the global batch, sharded over the cross-host 'dp' mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            # analyze: ok(hostsync) pod-path input staging: the process-local batch rows must cross the host to shard onto the global mesh
            host = value.asnumpy() if isinstance(value, NDArray) \
                else _np.asarray(value)  # analyze: ok(hostsync) iterator batches are host-resident; this is input staging, not a device readback
            # analyze: ok(hostsync) contiguity fix-up on the already-host staging copy
            host = _np.ascontiguousarray(host, dtype=dst._data.dtype)
            return jax.make_array_from_process_local_data(
                NamedSharding(self._pmesh, P("dp")), host)
        data = value._data if isinstance(value, NDArray) \
            else jnp.asarray(_np.asarray(value))  # analyze: ok(hostsync) iterator batches are host-resident; this is input staging, not a device readback
        if data.dtype != dst._data.dtype:
            data = data.astype(dst._data.dtype)
        if group._mesh is not None:
            return jax.device_put(data, group._batch_sharding())
        return exe._to_ctx(data)

    def _lift_repl(self, x):
        """Pod path: make a process-local array a replicated global
        array over the cross-host mesh. Arrays already carrying the
        target sharding (every output of the previous step) pass
        through jax.device_put as a no-op."""
        if x is None or self._pmesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(jnp.asarray(x),
                              NamedSharding(self._pmesh, P()))

    # -- residual spill/reseed (shared with the bucketed engine) --------
    def _seed_residuals(self, order, exe):
        # `order` is fixed for this FusedFitStep's lifetime, so any
        # non-None residual dict matches it; _release() forces a reseed
        if self._residuals is not None:
            return self._residuals
        kv = self._kv
        if kv is not None and kv._engine is not None:
            # flush pending buckets and spill their flat residuals back
            # to the per-(key,dev) dict before we take ownership
            kv._sync_engine()
        from .. import sharding as _sharding
        res = {}
        for n in order:
            w = exe.arg_dict[n]
            if kv is not None:
                # residuals live on the f32 master-gradient view; the
                # cast is a no-op for freshly seeded (already f32)
                # residuals and widens any pre-upgrade checkpoint state
                res[n] = kv._get_residual((n, 0), w)._data \
                    .astype(jnp.float32)
                kv._compression_residuals.pop((n, 0), None)
            else:
                res[n] = jnp.zeros(w.shape, jnp.float32)
            # f32 residuals ride their param's sharding (mp-sharded
            # params keep shard-local error feedback; device_put is an
            # identity when the placement already matches)
            res[n] = _sharding.match_param(res[n], w._data)
        self._residuals = res
        return res

    def _release(self):
        """Spill residual state back to the kvstore's per-(key,dev)
        dict so the eager path (and the bucketed engine's reseed)
        resumes with the exact accumulated error feedback."""
        if self._residuals and self._kv is not None:
            for n, r in self._residuals.items():
                self._kv._compression_residuals[(n, 0)] = NDArray(r)
        self._residuals = None
        # whatever the eager path replaces meanwhile, the next fused
        # step derives its plan again
        self._plan = None

    # -- sentinel publish (sync boundaries only) ------------------------
    def publish_sentinels(self):
        """Read the donated sentinel vector and push it into the
        registry — the DynamicLossScaler.publish pattern: called ONLY
        at existing sync boundaries (Module._fit_sync, checkpoint
        capture), never per step, so sentinels cost zero host syncs."""
        st = self._sent_state
        if st is None:
            return None
        # analyze: ok(hostsync) sentinel publish rides an existing sync boundary (_fit_sync / checkpoint capture), never the per-step path
        vals = _np.asarray(st)
        from ..telemetry import sentinel as _sentinel
        gnorm = float(vals[4])
        zscore = float(vals[5])
        _sentinel.GRAD_NORM.set(gnorm)
        _sentinel.LOSS_ZSCORE.set(zscore)
        if self._threshold is not None:
            _sentinel.RESIDUAL_DRIFT.set(float(vals[7]))
        cum = float(vals[3])
        delta = int(round(cum - self._published_nonfinite))
        if delta > 0:
            self._published_nonfinite = cum
            _sentinel.NONFINITE_GRADS.inc(delta)
            from ..telemetry.flight import RECORDER
            RECORDER.note("sentinel_trip", nonfinite=delta,
                          grad_norm=gnorm, loss_zscore=zscore)
        return vals

    # -- the step -------------------------------------------------------
    def step(self, data_batch, eval_metric=None):
        """Run one single-launch training step. Returns False when this
        batch can't take the fused path (residuals are spilled first so
        the eager fallback continues exactly).

        Three host spans on the profiler's clock split the step's host
        time (docs/OBSERVABILITY.md): ``fit.prepare`` (eligibility,
        placing inputs, gathering the program's arguments),
        ``fit.fused_dispatch`` (the jit call) and ``fit.rebind`` (every
        donated buffer handed its new value).  The same boundaries are
        stamped on the host's clock into the step timeline
        (``tracing.steps()``), whose record of a step begins here and
        ends at the next entry."""
        tracing = _telemetry.tracing
        rec = tracing.step_entry(self)
        with tracing.span("fit.prepare"):
            prep = self._prepare(data_batch, eval_metric)
        if prep is None:
            return False
        fn, args, carried = prep
        _count_dispatch()
        track_mem = (self._mem_tracker is not None
                     and self.launches % _MEM_EVERY == 0)
        if track_mem:
            self._mem_tracker.begin()
        try:
            with _dispatch_span("fit.fused_dispatch",
                                "Module::fused_fit_step"):
                result = _SITE.timed(fn, *args, timeline=rec)
        except Exception:
            # a runtime failure after donation consumes the donated
            # buffers — drop our residual refs so a later spill doesn't
            # resurrect deleted arrays, then surface the error (the
            # module's device state is not recoverable at this point)
            self._residuals = None
            self._sent_state = None
            self._carry_out = ()
            raise
        if track_mem:
            self._mem_tracker.end()
        with tracing.span("fit.rebind"):
            self._rebind(result, eval_metric, *carried)
        if rec is not None:
            rec.rebind1 = _now_ns()
            rec.fused = True
        self.launches += 1
        return True

    def _prepare(self, data_batch, eval_metric):
        """Everything of a step before its launch: ``(the compiled
        program, its arguments, what _rebind needs afterwards)``; None
        when this batch can't take the fused path."""
        mod = self._mod
        if getattr(mod, "_monitor_installed", False):
            self._release()
            return None
        # re-check the mutable bits of build-time eligibility: a swapped
        # updater (kv.set_updater after init) or a mutated optimizer
        # hyperparameter must not silently keep the stale program
        live_updater = mod._kvstore._updater if mod._update_on_kvstore \
            else mod._updater
        if live_updater is not self._updater:
            self._release()
            return None
        optimizer = mod._optimizer
        mode = optimizer._fused_fit_sig()
        if mode is None or not _fused.supported(mode):
            self._release()
            return None
        group = mod._exec_group
        exe = group._exec
        data = getattr(data_batch, "data", None)
        labels = getattr(data_batch, "label", None) or []
        if not data or len(data) != len(group.data_names) \
                or (group.label_names
                    and len(labels) < len(group.label_names)):
            self._release()
            return None
        for v in list(data) + list(labels):
            if isinstance(v, NDArray) \
                    and getattr(v, "stype", "default") != "default":
                self._release()
                return None

        inputs = {}
        try:
            for name, v in zip(group.data_names, data):
                inputs[name] = self._place(group, exe, name, v)
            for name, v in zip(group.label_names, labels):
                inputs[name] = self._place(group, exe, name, v)
        except Exception as e:              # e.g. unshardable ragged batch
            dbg = getattr(mod.logger, "debug", None)
            if dbg:
                dbg("fused fit step falling back for this batch: %s", e)
            self._release()
            return None

        scaler = self._scaler
        if scaler is not None:
            # a checkpoint restore may have swapped the module's scaler
            # object; its step_fn is pure in trace_sig so cached
            # programs built against the old object stay valid
            scaler = getattr(mod, "_loss_scaler", None) or scaler
            self._scaler = scaler
        plan, built = self._plan, False
        if plan is None or not self._plan_holds(plan, group, optimizer, mode,
                                                eval_metric, scaler):
            plan = self._plan = self._build_plan(group, optimizer, mode,
                                                 eval_metric, scaler)
            if plan is None:
                self._release()
                return None    # e.g. a host-side custom state blob
            built = True
        order = plan.order
        if self._kv is not None:
            # a preceding eager batch may still have overlapped pushes
            # applying weights on the kvstore pipeline thread
            # (kvstore_tpu.engine._OverlapPipeline); land them before
            # snapshotting weights/state into the donated program
            self._kv._flush_pending()
        params = {n: w._data for n, w in zip(order, plan.weights)}
        for n, a in plan.fixed:
            inputs[n] = a._data                     # fixed/no-grad args

        # `mode` re-read above and held against the plan's: mutating
        # optimizer hyperparams mid-training switches programs (one
        # retrace), like the eager path
        lr_vec, wd_vec, extra = optimizer._fused_runtime(plan.ukeys)
        use_wd = bool(wd_vec.any())
        fn = plan.programs.get(use_wd)
        if fn is None:
            fn = plan.programs[use_wd] = self._program(plan, use_wd)
        if group._mesh is not None:
            # optimizer-state leaves inherit each param's sharding, so
            # mp-sharded params carry mp-sharded moments/masters inside
            # the donated program (no resharding at the jit boundary)
            from .. import sharding as _sharding
            for w, leaves in zip(plan.weights, plan.leaves):
                for l in leaves:
                    l._set_data(_sharding.match_param(l._data, w._data))
        states = {n: tuple([l._data for l in leaves])
                  for n, leaves in zip(order, plan.leaves)}
        residuals = self._seed_residuals(order, exe) \
            if self._threshold is not None else {}

        # The carry goes in the way it comes back: the program's own
        # results from the second step on, and after ``metric.reset()``
        # the plan's pair of zeros, made once where the program runs
        # (an eager ``jnp.float32(0.0)`` is a program on the chip).
        macc = ()
        if plan.metric_fn is not None:
            macc = plan.zeros if eval_metric._dev_sum is None \
                else (eval_metric._dev_sum, eval_metric._dev_num)
        scaler_state = scaler.device_state() if scaler is not None else ()
        sent_state = ()
        if plan.sent_on:
            sent_state = self._sent_state
            if sent_state is None:
                sent_state = jnp.zeros(8, jnp.float32)
        auxs = exe._auxs_values()
        # Everything the program carries comes back COMMITTED to where
        # it ran — under a mesh typed with it (replicated NamedSharding).
        # jax keys the trace on that type and the lowering on the
        # placement, so fresh state that goes in uncommitted (a restored
        # scaler, initializer outputs, lazily created optimizer state)
        # costs a second trace under a mesh and a second full XLA
        # compile without one.  What is not yet where the program leaves
        # it is put there; what the last launch returned, and the plan's
        # zeros, are.
        left = plan.zeros + self._carry_out
        carry = (*macc, *scaler_state,
                 *([sent_state] if plan.sent_on else ()))
        if not all(any(leaf is known for known in left) for leaf in carry):
            macc, scaler_state, sent_state = jax.device_put(
                (macc, scaler_state, sent_state),
                self._carry_placement(group, exe))
        if built:
            params, states, residuals, auxs = jax.tree.map(
                lambda a: jax.device_put(a, a.sharding),
                (params, states, residuals, auxs))
        if self._pmesh is not None:
            # lift every program input onto the cross-host mesh (no-op
            # for arrays the previous step already left there)
            params = {n: self._lift_repl(v) for n, v in params.items()}
            states = {n: tuple(self._lift_repl(l) for l in v)
                      for n, v in states.items()}
            residuals = {n: self._lift_repl(v)
                         for n, v in residuals.items()}
            auxs = {n: self._lift_repl(v) for n, v in auxs.items()}
            inputs = {n: (v if getattr(getattr(v, "sharding", None),
                                       "mesh", None) is self._pmesh
                          else self._lift_repl(v))
                      for n, v in inputs.items()}
            macc = tuple(self._lift_repl(m) for m in macc)
            scaler_state = tuple(self._lift_repl(s) for s in scaler_state)
            if plan.sent_on:
                sent_state = self._lift_repl(sent_state)

        seed = exe._next_seed()
        rescale = _np.float32(optimizer.rescale_grad)
        args = (params, states, residuals, macc, scaler_state, sent_state,
                inputs, auxs, lr_vec, wd_vec, rescale, extra, seed)
        deferred = (plan.heads, [inputs[p.label] for p in plan.heads],
                    plan.tail) if plan.heads else None
        return fn, args, (plan, scaler, deferred)

    def _carry_placement(self, group, exe):
        """Where the program leaves what it carries."""
        return group._repl_sharding() if group._mesh is not None \
            else exe._ctx.jax_device

    def _plan_holds(self, plan, group, optimizer, mode, eval_metric, scaler):
        """Whether everything ``plan`` was derived from is still what it
        was, by what a step can observe at the cost of a few
        comparisons.  (The monitor flag and the live updater's identity
        are held before this: they send the batch to the eager pair.)"""
        if not (plan.group is group and plan.optimizer is optimizer
                and plan.mode == mode
                and plan.multi_precision == bool(optimizer.multi_precision)
                and plan.metric is eval_metric
                and plan.sent_on == _sentinel_enabled()
                and plan.mirror == _config.backward_do_mirror()
                and plan.scaler_sig == (scaler.trace_sig()
                                        if scaler is not None else None)):
            return False
        # load_optimizer_states, or a state set by hand, replaces the
        # objects the plan flattened
        get = self._updater.states.get
        for uk, st in zip(plan.ukeys, plan.state_objs):
            if get(uk, _MISSING) is not st:
                return False
        return True

    def _build_plan(self, group, optimizer, mode, eval_metric, scaler):
        """Derive the step's plan; None where the optimizer's state
        cannot ride the program (the batch then takes the eager pair)."""
        mod, updater, exe = self._mod, self._updater, group._exec
        if self._order is None:
            self._order = self._param_order()
            # keys use the param's position in the FULL param_names list
            # — frozen params keep their index slots in the eager path
            # (model._update_params / Module._param_index_names), and
            # the keys must agree for lr/wd mults and state interchange
            pos = {n: i for i, n in enumerate(group.param_names)}
            self._ukeys = [self._ukey(pos[n], n) for n in self._order]
        order, ukeys = self._order, self._ukeys
        # validate loaded states BEFORE any side effects: an abort here
        # must not have advanced update counts or created state entries
        flat = {}
        for uk in ukeys:
            st = updater.states.get(uk)
            if st is not None:
                flat[uk] = _fused.flatten_state(st)
                if not all(isinstance(l, NDArray) for l in flat[uk][0]):
                    return None
        plan = _StepPlan()
        plan.group, plan.optimizer, plan.mode = group, optimizer, mode
        plan.multi_precision = bool(optimizer.multi_precision)
        plan.order, plan.ukeys = tuple(order), ukeys
        plan.weights = [exe.arg_dict[n] for n in order]
        # the group fixes which inputs a step places; every other
        # argument that is no parameter goes in as it stands
        placed = set(group.data_names) | set(group.label_names)
        plan.fixed = [(n, exe.arg_dict[n]) for n in exe._arg_names
                      if n not in placed and n not in order]
        placed.update(n for n, _ in plan.fixed)
        plan.state_objs, plan.leaves, tpls, mp_flags = [], [], [], []
        for uk, w in zip(ukeys, plan.weights):
            if uk not in updater.states:
                updater.states[uk] = optimizer.create_state_multi_precision(
                    uk, w)
                updater.states_synced[uk] = True
            st = updater.states[uk]
            leaves, tpl = flat.get(uk) or _fused.flatten_state(st)
            plan.state_objs.append(st)
            plan.leaves.append(leaves)
            tpls.append(tpl)
            # multi-precision is an EXPLICIT static flag (an Adam
            # (mean, var) pair is structurally ambiguous with an
            # (inner, weight32) master tuple)
            mp_flags.append(plan.multi_precision
                            and _fused.is_low_precision(w.dtype))
        plan.tpls, plan.mp_flags = tuple(tpls), tuple(mp_flags)

        plan.metric = eval_metric
        plan.metric_fn, plan.msig = _metric_closure(
            eval_metric, group.label_names, mod._symbol.list_outputs())
        plan.mirror = _config.backward_do_mirror()
        plan.scaler_sig = scaler.trace_sig() if scaler is not None else None
        plan.sent_on = _sentinel_enabled()
        # a loss head's output is deferred (loss_head.py) where no one
        # reads the outputs step by step: the metric folds inside the
        # program, or there is none.  A metric that accumulates on the
        # host reads get_outputs() after every step, so its program
        # keeps returning them.  (A pod's outputs span processes and
        # stay as they were: a tail program there would be a collective
        # that only the reading rank enters.)
        plan.heads, plan.tail = (), None
        if (eval_metric is None or plan.metric_fn is not None) \
                and self._pmesh is None:
            plan.heads = tuple(p for p in _loss_head.plans(mod._symbol)
                               if p.label in placed)
        at = tuple(p.index for p in plan.heads)
        sym_cache = _compiled_cache(mod._symbol)
        plan.graph_fn = sym_cache["graph_fn"]
        if plan.heads:
            head_fns = sym_cache.setdefault("fit_heads", {})
            if at not in head_fns:
                head_fns[at] = (
                    _build_graph_fn(mod._symbol,
                                    also=[p.stem for p in plan.heads]),
                    _loss_head.tail_program(plan.heads))
            plan.graph_fn, plan.tail = head_fns[at]
        plan.programs = {}
        plan.zeros = ()
        if plan.metric_fn is not None:
            zero = _np.float32(0.0)
            plan.zeros = tuple(self._lift_repl(z) for z in jax.device_put(
                (zero, zero), self._carry_placement(group, exe)))
        FIT_PLAN_BUILDS.inc()
        return plan

    def _program(self, plan, use_wd):
        """The compiled step for ``plan`` with or without the weight
        decay's term (the decays are runtime scalars), from the symbol's
        cache."""
        cache = _compiled_cache(self._mod._symbol).setdefault("fit_step", {})
        key = (plan.order, self._threshold, plan.mode, plan.tpls,
               plan.mp_flags, use_wd, plan.msig, plan.mirror,
               plan.scaler_sig, plan.sent_on,
               tuple(p.index for p in plan.heads))
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = _build_fit_program(
                plan.graph_fn, plan.order, self._threshold, plan.mode,
                plan.tpls, plan.mp_flags, use_wd, plan.metric_fn,
                plan.mirror, self._scaler, sentinel=plan.sent_on,
                heads=plan.heads)
        return fn

    def _rebind(self, result, eval_metric, plan, scaler, deferred):
        """Hand every donated buffer its new value."""
        (new_ps, new_ss, new_res, macc, new_scaler, new_sent, new_auxs,
         outs, stems) = result
        mod, exe = self._mod, plan.group._exec
        for n, w, leaves in zip(plan.order, plan.weights, plan.leaves):
            w._set_data(new_ps[n])
            for leaf, new_leaf in zip(leaves, new_ss[n]):
                leaf._set_data(new_leaf)
        if self._kv is not None and mod._update_on_kvstore:
            # the store's own copy of each weight, looked up by name: a
            # checkpoint restore replaces the store's arrays
            store = self._kv._store
            for n in plan.order:
                twin = store.get(n)
                if twin is not None:
                    twin._set_data(new_ps[n])
        if self._threshold is not None:
            self._residuals = dict(new_res)
        if scaler is not None:
            scaler.set_device_state(new_scaler)
        self._sent_state = new_sent if plan.sent_on else None
        self._carry_out = (*macc, *new_scaler,
                           *([new_sent] if plan.sent_on else ()))
        exe._write_auxs(new_auxs)
        if deferred is not None:
            # the heads' values are made when Executor.outputs is read
            exe._outputs = _loss_head.DeferredOutputs(exe._ctx, outs,
                                                      stems, *deferred)
            _loss_head.DEFERRED.inc()
        else:
            exe._outputs = [NDArray(o, exe._ctx) for o in outs]
        if self._moe_counts is not None:
            # a reference to the counts' device array, read only when
            # telemetry.moe.publish() is asked: no sync in the step
            i, *sizing = self._moe_counts
            _telemetry.moe.note(outs[i], *sizing)
        if self._dsa_tiles is not None:
            _telemetry.dsa.note(outs[self._dsa_tiles])
        if self._diffusion_rows is not None:
            _telemetry.diffusion.note(outs[self._diffusion_rows])
        exe._pending_train_fwd = False
        exe._train_seed = None
        exe._train_auxs = None
        if plan.metric_fn is not None:
            eval_metric._dev_sum, eval_metric._dev_num = macc
            eval_metric._device_consumed = True
        mod._params_dirty = True
