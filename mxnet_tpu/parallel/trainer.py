"""Fused data/tensor-parallel training step.

The reference's training step is Python-orchestrated: per-device executors
run fwd/bwd (executor_group.py:436,571), kvstore push/pull aggregates
gradients (model.py:145), then per-key fused optimizer ops update weights.
The TPU-native realization collapses all of that into ONE pjit'd XLA
computation per step: forward + backward + cross-device gradient reduction
(inserted by GSPMD from the shardings) + optimizer update, with parameter /
state buffers donated so HBM holds a single copy.

Sharding model (SURVEY.md §2.3):
* batch axis       → mesh axis ``dp``  (replaces kvstore local/device/nccl)
* weight shards    → mesh axis ``tp``  (GSPMD tensor parallelism; the
                     reference's closest analog is group2ctx model
                     parallelism, graph_executor.cc:408)
* gradients        → psum over ``dp`` inserted by XLA, riding ICI

Its callers are tests, one example and ``__graft_entry__``; ``Module.fit``
runs ``module/fused_fit.py`` instead.
"""
from __future__ import annotations

import numpy as _np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..ops import optimizer_ops as _oo
from .. import telemetry as _telemetry

__all__ = ["TrainStep", "default_tp_rule"]

# trace-time retrace witness + program-registry registration, same
# RetraceSite contract as executor/fused_fit/kvstore (the step body
# calls _note_retrace(); step() dispatches through _SITE.timed)
PARALLEL_RETRACES = _telemetry.REGISTRY.counter(
    "parallel_step_retraces",
    "parallel TrainStep program (re)traces (trace-time witness)",
    vital=True)
_SITE = _telemetry.RetraceSite(PARALLEL_RETRACES,
                               _telemetry.JIT_COMPILE_MS,
                               site="parallel_step")
_note_retrace = _SITE.note


def default_tp_rule(name, shape, mesh):
    """Heuristic parameter PartitionSpec for a mesh with a 'tp' axis:
    shard the output-channel axis of large matmul/conv weights, replicate
    everything else. GSPMD propagates the rest of the sharding."""
    if "tp" not in mesh.axis_names:
        return P()
    tp = mesh.shape["tp"]
    if len(shape) >= 2 and shape[0] % tp == 0 and shape[0] >= 2 * tp:
        return P("tp")
    return P()


def _wd_for(optimizer, name):
    """Per-parameter weight decay keyed by NAME (not index — this TrainStep
    may not share idx2name with a Module that used the same optimizer).
    Reproduces Optimizer.set_wd_mult's default: wd=0 unless the name ends in
    _weight/_gamma (reference optimizer.py:330)."""
    if name in optimizer.param_dict:
        return optimizer.wd * optimizer.param_dict[name].wd_mult
    if name in optimizer.wd_mult:
        return optimizer.wd * optimizer.wd_mult[name]
    if not (name.endswith("_weight") or name.endswith("_gamma")):
        return 0.0
    return optimizer.wd


def _functional_update(optimizer, idx, name, weight, grad, state, lr):
    """Apply ``optimizer`` to one parameter functionally, using the same
    pure update ops the eager path uses (ops/optimizer_ops.py; reference
    src/operator/optimizer_op.cc). Returns (new_weight, new_state)."""
    from .. import optimizer as _opt

    wd = _wd_for(optimizer, name)
    lr = lr * (optimizer.lr_mult.get(name, 1.0)
               if name not in optimizer.param_dict else
               optimizer.param_dict[name].lr_mult)
    kw = dict(rescale_grad=optimizer.rescale_grad,
              clip_gradient=(optimizer.clip_gradient
                             if optimizer.clip_gradient is not None else -1.0))

    if isinstance(optimizer, _opt.SGD):
        mom = optimizer.momentum
        use_mp = optimizer.multi_precision and weight.dtype in (
            jnp.float16, jnp.bfloat16)
        if use_mp:
            if mom:
                m, w32 = state
                new_w, new_m, new_w32 = _oo.mp_sgd_mom_update(
                    weight, grad, m, w32, lr=lr, momentum=mom, wd=wd, **kw)
                return new_w, (new_m, new_w32)
            (w32,) = state
            new_w, new_w32 = _oo.mp_sgd_update(weight, grad, w32, lr=lr,
                                               wd=wd, **kw)
            return new_w, (new_w32,)
        if mom:
            (m,) = state
            new_w, new_m = _oo.sgd_mom_update(weight, grad, m, lr=lr,
                                              momentum=mom, wd=wd, **kw)
            return new_w, (new_m,)
        return _oo.sgd_update(weight, grad, lr=lr, wd=wd, **kw), ()
    if isinstance(optimizer, _opt.Signum):
        (m,) = state
        new_w, new_m = _oo.signum_update(
            weight, grad, m, lr=lr, momentum=optimizer.momentum, wd=wd,
            wd_lh=getattr(optimizer, "wd_lh", 0.0), **kw)
        return new_w, (new_m,)
    if isinstance(optimizer, _opt.Adam):
        mean, var = state
        new_w, new_mean, new_var = _oo.adam_update(
            weight, grad, mean, var, lr=lr, beta1=optimizer.beta1,
            beta2=optimizer.beta2, epsilon=optimizer.epsilon, wd=wd, **kw)
        return new_w, (new_mean, new_var)
    if isinstance(optimizer, _opt.RMSProp):
        if optimizer.clip_weights:
            kw["clip_weights"] = optimizer.clip_weights
        if optimizer.centered:
            n, g, delta = state
            new_w, new_n, new_g, new_d = _oo.rmspropalex_update(
                weight, grad, n, g, delta, lr=lr, gamma1=optimizer.gamma1,
                gamma2=optimizer.gamma2, epsilon=optimizer.epsilon, wd=wd,
                **kw)
            return new_w, (new_n, new_g, new_d)
        (n,) = state
        new_w, new_n = _oo.rmsprop_update(
            weight, grad, n, lr=lr, gamma1=optimizer.gamma1,
            epsilon=optimizer.epsilon, wd=wd, **kw)
        return new_w, (new_n,)
    if isinstance(optimizer, _opt.AdaGrad):
        (h,) = state
        new_w, new_h = _oo.adagrad_update(weight, grad, h, lr=lr,
                                          epsilon=optimizer.eps, wd=wd, **kw)
        return new_w, (new_h,)
    raise MXNetError(
        "fused TrainStep supports sgd/signum/adam/rmsprop/adagrad; %r must "
        "run through Module.update()" % type(optimizer).__name__)


def _init_state(optimizer, weight):
    """fp32 state pytree per parameter (mirrors Optimizer.create_state)."""
    from .. import optimizer as _opt
    w32 = lambda: jnp.asarray(weight, jnp.float32)
    zeros = lambda: jnp.zeros(weight.shape, jnp.float32)
    if isinstance(optimizer, _opt.SGD):
        use_mp = optimizer.multi_precision and weight.dtype in (
            jnp.float16, jnp.bfloat16)
        if use_mp:
            return (zeros(), w32()) if optimizer.momentum else (w32(),)
        return (zeros(),) if optimizer.momentum else ()
    if isinstance(optimizer, _opt.Signum):
        return (zeros(),)
    if isinstance(optimizer, _opt.Adam):
        return (zeros(), zeros())
    if isinstance(optimizer, _opt.RMSProp):
        return (zeros(), zeros(), zeros()) if optimizer.centered else (zeros(),)
    if isinstance(optimizer, _opt.AdaGrad):
        return (zeros(),)
    return ()


def _device_weight_rule(initializer, shape, dtype):
    """fn(key) -> device array applying ``initializer``'s WEIGHT rule
    (Xavier/Normal/Uniform/Zero/One/Constant), or None."""
    from .. import initializer as _init

    cls = type(initializer)
    if isinstance(initializer, _init.Zero):
        return lambda key: jnp.zeros(shape, dtype)
    if isinstance(initializer, _init.One):
        return lambda key: jnp.ones(shape, dtype)
    if isinstance(initializer, _init.Constant):
        return lambda key: jnp.full(shape, initializer.value, dtype)
    if isinstance(initializer, _init.Xavier) \
            and cls._init_weight is _init.Xavier._init_weight:
        if len(shape) < 2:
            return None
        hw = 1.0
        for s in shape[2:]:
            hw *= s
        fan_in, fan_out = shape[1] * hw, shape[0] * hw
        factor = {"avg": (fan_in + fan_out) / 2.0, "in": fan_in,
                  "out": fan_out}[initializer.factor_type]
        scale = float(_np.sqrt(initializer.magnitude / factor))
        if initializer.rnd_type == "uniform":
            return lambda key: jax.random.uniform(
                key, shape, jnp.float32, -scale, scale).astype(dtype)
        return lambda key: (jax.random.normal(key, shape, jnp.float32)
                            * scale).astype(dtype)
    if cls is _init.Normal:
        s = float(initializer.sigma)
        return lambda key: (jax.random.normal(key, shape, jnp.float32)
                            * s).astype(dtype)
    if cls is _init.Uniform:
        s = float(initializer.scale)
        return lambda key: jax.random.uniform(
            key, shape, jnp.float32, -s, s).astype(dtype)
    return None


def _device_init_rule(initializer, name, attrs, shape, dtype):
    """Device-side analog of Initializer.__call__'s name dispatch
    (initializer.py:55): returns fn(key) -> jax array, or None when the
    (initializer, name) pair has no closed-form device rule
    (Orthogonal/Bilinear/..., packed RNN vecs, custom subclasses).

    TPU-first: the reference initializes on the host and copies every
    parameter to the device; generating with XLA's on-chip RNG instead
    means a multi-GB model materializes in HBM without a single
    host->device weight transfer."""
    import json as _json

    from .. import initializer as _init

    if attrs and attrs.get("__init__"):
        # per-variable init attr (Variable(init=...)): the host path
        # applies that initializer's WEIGHT rule — mirror it on device
        # (bailing here would force e.g. multi-GB MoE expert stacks
        # through host RAM)
        try:
            klass, kw = _json.loads(attrs["__init__"])
            inst = _init.get(klass, **kw)
        except Exception:
            return None
        return _device_weight_rule(inst, shape, dtype)
    cls = type(initializer)
    # any overridden dispatch or rule method means the initializer has
    # custom semantics (Mixed, Load, user subclasses) — host path only
    if cls.__call__ is not _init.Initializer.__call__:
        return None
    base = _init.Initializer
    for meth in ("_init_bias", "_init_gamma", "_init_beta", "_init_zero",
                 "_init_one", "_init_default"):
        if getattr(cls, meth) is not getattr(base, meth):
            return None
    lname = name.lower()
    if lname.endswith(("_bias", "_beta", "_moving_mean", "_running_mean",
                       "_moving_avg", "_min", "_max")):
        return lambda key: jnp.zeros(shape, dtype)
    if lname.endswith(("_gamma", "_moving_var", "_running_var")):
        return lambda key: jnp.ones(shape, dtype)
    if lname.endswith("_parameters"):
        return None
    return _device_weight_rule(initializer, shape, dtype)


class TrainStep:
    """symbol + optimizer + mesh → one compiled training step.

    Usage::

        mesh = Mesh(np.array(jax.devices()).reshape(-1), ('dp',))
        ts = TrainStep(sym, optimizer, mesh=mesh,
                       data_shapes={'data': (256, 3, 224, 224)},
                       label_shapes={'softmax_label': (256,)})
        ts.init_params(mx.init.Xavier())
        for batch in loader:
            outs = ts.step(batch)          # donates & replaces params
    """

    def __init__(self, symbol, optimizer, data_shapes, label_shapes=None,
                 mesh=None, dtype="float32", tp_rule=default_tp_rule,
                 batch_axis="dp"):
        from ..executor import _build_graph_fn

        self._symbol = symbol
        self._optimizer = optimizer
        self._graph_fn = _build_graph_fn(symbol)
        self._mesh = mesh
        self._batch_axis = batch_axis
        self._tp_rule = tp_rule

        input_shapes = dict(data_shapes)
        input_shapes.update(label_shapes or {})
        self._input_names = list(input_shapes)
        arg_names = symbol.list_arguments()
        self._param_names = [n for n in arg_names if n not in input_shapes]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        type_kwargs = {n: dtype for n in data_shapes} if dtype != "float32" else {}
        arg_shapes, arg_types, aux_shapes, aux_types = \
            symbol.infer_shape_type(input_shapes, type_kwargs)
        self._arg_shapes = dict(zip(arg_names, arg_shapes))
        self._arg_types = dict(zip(arg_names, arg_types))
        self._aux_shapes = dict(zip(self._aux_names, aux_shapes))
        self._aux_types = dict(zip(self._aux_names, aux_types))

        # wd/lr multipliers are resolved by NAME inside _functional_update
        # (_wd_for), so the optimizer's idx2name — possibly owned by a
        # Module with different indices — is never touched
        self._idx = {n: i for i, n in enumerate(self._param_names)}

        self.params = None       # name -> jax.Array
        self.states = None       # name -> tuple of jax.Array
        self.auxs = None         # name -> jax.Array
        self._step_fn = None
        self._nstep = 0
        from .. import random as _rand
        self._base_seed = int(_rand.next_seed())

    # ------------------------------------------------------------------
    def _param_sharding(self, name):
        if self._mesh is None:
            return None
        spec = (self._tp_rule(name, self._arg_shapes[name], self._mesh)
                if self._tp_rule else P())
        return NamedSharding(self._mesh, spec)

    def _batch_sharding(self):
        if self._mesh is None:
            return None
        return NamedSharding(self._mesh, P(self._batch_axis))

    def _repl_sharding(self):
        if self._mesh is None:
            return None
        return NamedSharding(self._mesh, P())

    def init_params(self, initializer, arg_params=None, aux_params=None,
                    device_init=True):
        """Initialize parameters. With ``device_init`` (default), params
        whose initializer rule has a closed form (Xavier/Normal/Uniform/
        Zero/One/Constant + the standard name-suffix rules) generate
        directly on the accelerator with XLA's RNG — no host->device
        weight transfer at all (the reference always inits on cpu and
        copies, module.py:270; for multi-GB models over PCIe the device
        path is the difference between seconds and minutes).
        Everything else falls back to the host initializer."""
        from ..initializer import InitDesc
        from ..ndarray.ndarray import NDArray

        attrs = self._symbol.attr_dict()
        key = jax.random.key(self._base_seed)

        def materialize(name, shp, dt, provided, sharding):
            nonlocal key
            if provided is not None:
                host = provided.asnumpy() \
                    if isinstance(provided, NDArray) else provided
                return jax.device_put(jnp.asarray(host, dt), sharding)
            if device_init:
                rule = _device_init_rule(initializer, name,
                                         attrs.get(name), shp, dt)
                if rule is not None:
                    key, sub = jax.random.split(key)
                    return jax.device_put(rule(sub), sharding)
            nd_host = NDArray(jnp.zeros(shp, dt))
            initializer(InitDesc(name, attrs.get(name)), nd_host)
            return jax.device_put(jnp.asarray(nd_host.asnumpy(), dt),
                                  sharding)

        params = {}
        for name in self._param_names:
            params[name] = materialize(
                name, self._arg_shapes[name], self._arg_types[name],
                (arg_params or {}).get(name), self._param_sharding(name))
        auxs = {}
        for name in self._aux_names:
            auxs[name] = materialize(
                name, self._aux_shapes[name], self._aux_types[name],
                (aux_params or {}).get(name), self._repl_sharding())
        states = {n: tuple(
            jax.device_put(s, self._param_sharding(n))
            for s in _init_state(self._optimizer, params[n]))
            for n in self._param_names}
        self.params, self.states, self.auxs = params, states, auxs

    # ------------------------------------------------------------------
    def _build_step(self):
        graph_fn = self._graph_fn
        optimizer = self._optimizer
        param_names = self._param_names
        idx = self._idx

        def step_fn(params, states, auxs, batch, lr, seed):
            _note_retrace()   # trace-time host side effect only

            def f(p):
                outs, new_auxs = graph_fn({**batch, **p}, auxs, seed, True)
                return outs, new_auxs

            outs, vjp_fn, new_auxs = jax.vjp(f, params, has_aux=True)
            cts = [jnp.ones_like(o) for o in outs]
            (grads,) = vjp_fn(cts)
            new_params, new_states = {}, {}
            for n in param_names:
                g = grads[n]
                if g is None:
                    new_params[n], new_states[n] = params[n], states[n]
                    continue
                new_params[n], new_states[n] = _functional_update(
                    optimizer, idx[n], n, params[n], g, states[n], lr)
            return new_params, new_states, new_auxs, outs

        if self._mesh is None:
            return jax.jit(step_fn, donate_argnums=(0, 1, 2))

        param_sh = {n: self._param_sharding(n) for n in param_names}
        state_sh = {n: tuple(param_sh[n] for _ in self.states[n])
                    for n in param_names}
        aux_sh = {n: self._repl_sharding() for n in self._aux_names}
        batch_sh = {n: self._batch_sharding() for n in self._input_names}
        repl = self._repl_sharding()
        return jax.jit(
            step_fn,
            in_shardings=(param_sh, state_sh, aux_sh, batch_sh, repl, repl),
            out_shardings=(param_sh, state_sh, aux_sh, None),
            donate_argnums=(0, 1, 2))

    def step(self, batch):
        """Run one training step; ``batch`` maps input name → array.
        Returns the forward outputs."""
        if self.params is None:
            raise MXNetError("call init_params() first")
        if self._step_fn is None:
            self._step_fn = self._build_step()
        self._nstep += 1
        optimizer = self._optimizer
        optimizer.num_update = max(optimizer.num_update, self._nstep)
        lr = (optimizer.lr_scheduler(optimizer.num_update)
              if optimizer.lr_scheduler is not None else optimizer.lr)
        from .. import optimizer as _opt
        if isinstance(optimizer, _opt.Adam):
            # Adam bias correction folded into lr host-side, one global t:
            # in the fused whole-graph step EVERY parameter updates EVERY
            # step, so the single counter equals the reference's per-index
            # update counts exactly (indexes can only diverge in the eager
            # per-key path, where optimizer.py keeps per-index counts).
            t = self._nstep
            lr *= ((1.0 - optimizer.beta2 ** t) ** 0.5
                   / (1.0 - optimizer.beta1 ** t))
        # cast to the inferred input dtype (e.g. TrainStep(dtype='bfloat16')
        # on a symbol with no explicit Cast) before placing on device
        def _place(n, v):
            dt = self._arg_types.get(n)
            # fast path only for UNcommitted arrays (already free to live
            # on the default device); a cpu-committed iterator batch must
            # be re-placed or the jit sees mixed devices
            if isinstance(v, jax.Array) and (dt is None or v.dtype == dt) \
                    and self._mesh is None and not getattr(v, "committed",
                                                           True):
                return v
            v = jnp.asarray(v, dt)
            if self._mesh is not None:
                return jax.device_put(v, self._batch_sharding())
            if getattr(v, "committed", False):
                # cpu-context iterator batch: move to the step's device
                v = jax.device_put(v, jax.devices()[0])
            return v

        batch = {n: _place(n, v) for n, v in batch.items()}
        seed = _np.uint32((self._base_seed + self._nstep * 2654435761)
                          & 0x7FFFFFFF)
        self.params, self.states, self.auxs, outs = _SITE.timed(
            self._step_fn, self.params, self.states, self.auxs, batch,
            jnp.float32(lr), seed)
        return outs

    # ------------------------------------------------------------------
    def get_params(self):
        """Gather params/auxs to host NDArrays (for checkpointing)."""
        from ..ndarray.ndarray import NDArray
        arg = {n: NDArray(jnp.asarray(v)) for n, v in self.params.items()}
        aux = {n: NDArray(jnp.asarray(v)) for n, v in self.auxs.items()}
        return arg, aux
