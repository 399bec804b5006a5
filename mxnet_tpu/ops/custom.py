"""The 'Custom' operator — dispatch into user CustomOpProp/CustomOp.

Reference parity: src/operator/custom/custom.cc (the C++ trampoline op
behind mx.nd.Custom / mx.sym.Custom). Here the trampoline is
``jax.pure_callback`` + ``jax.custom_vjp``: the user's Python
forward/backward run on host, embedded at the right point of the XLA
program, with shapes/dtypes declared up front from the prop's
infer_shape/infer_type so tracing (jit, eval_shape) never executes them.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from .registry import register, op_context


def _custom_num_outputs(attrs):
    from ..operator import _make_prop
    return len(_make_prop(attrs).list_outputs())


def _custom_kw_input_order(attrs):
    from ..operator import _make_prop
    prop = _make_prop(attrs)
    return prop.list_arguments() + prop.list_auxiliary_states()


def _set_custom_hooks():
    from .registry import get_op
    get_op("Custom").kw_input_order = _custom_kw_input_order


@register("Custom", num_outputs=_custom_num_outputs)
def _custom(*inputs, op_type=None, **prop_kwargs):
    """User-defined op: forwards to the CustomOpProp registered as
    ``op_type`` (reference operator.py register / custom.cc).

    Backend note: requires PJRT host callbacks (jax.pure_callback),
    which the CPU and TPU runtimes support."""
    from ..operator import _make_prop
    from ..ndarray.ndarray import NDArray

    attrs = dict(prop_kwargs, op_type=op_type)
    prop = _make_prop(attrs)
    is_train = bool(op_context.is_train)

    # trailing inputs beyond list_arguments are auxiliary states
    # (reference custom.cc: arguments then aux states)
    n_args = len(prop.list_arguments())
    n_aux = len(inputs) - n_args
    if n_aux < 0:
        raise ValueError("Custom op '%s' expects %d arguments, got %d"
                         % (op_type, n_args, len(inputs)))

    in_shapes = [tuple(x.shape) for x in inputs[:n_args]]
    aux_shapes = [tuple(x.shape) for x in inputs[n_args:]]
    inferred = prop.infer_shape(list(in_shapes))
    out_shapes = [tuple(s) for s in inferred[1]]
    in_types = [_np.dtype(x.dtype) for x in inputs[:n_args]]
    aux_types = [_np.dtype(x.dtype) for x in inputs[n_args:]]
    out_types = [_np.dtype(t) for t in prop.infer_type(list(in_types))[1]]
    out_specs = tuple(jax.ShapeDtypeStruct(s, t)
                      for s, t in zip(out_shapes, out_types))
    in_specs = tuple(jax.ShapeDtypeStruct(s, t)
                     for s, t in zip(in_shapes, in_types))
    n_out = len(out_specs)
    n_in = n_args

    def _split(arrs):
        nds = [NDArray(jnp.asarray(a)) for a in arrs]
        return nds[:n_in], nds[n_in:]

    def _host_forward(*arrs):
        op = prop.create_operator(None, in_shapes, in_types)
        in_nd, aux_nd = _split(arrs)
        out_nd = [NDArray(jnp.zeros(s, t))
                  for s, t in zip(out_shapes, out_types)]
        op.forward(is_train, ["write"] * n_out, in_nd, out_nd, aux_nd)
        return tuple(_np.asarray(o._data, dtype=t)
                     for o, t in zip(out_nd, out_types))

    def _host_backward(*arrs):
        ograds = [NDArray(jnp.asarray(a)) for a in arrs[:n_out]]
        ins, aux_nd = _split(arrs[n_out:n_out + n_in + n_aux])
        outs = [NDArray(jnp.asarray(a))
                for a in arrs[n_out + n_in + n_aux:]]
        op = prop.create_operator(None, in_shapes, in_types)
        in_grad = [NDArray(jnp.zeros(s, t))
                   for s, t in zip(in_shapes, in_types)]
        op.backward(["write"] * n_in, ograds, ins, outs, in_grad, aux_nd)
        return tuple(_np.asarray(g._data, dtype=t)
                     for g, t in zip(in_grad, in_types))

    @jax.custom_vjp
    def f(*ins):
        out = jax.pure_callback(_host_forward, out_specs, *ins)
        return tuple(out)

    def f_fwd(*ins):
        outs = f(*ins)
        return outs, (ins, outs)

    def f_bwd(res, cts):
        ins, outs = res
        grads = jax.pure_callback(_host_backward, in_specs,
                                  *(tuple(cts) + tuple(ins) + tuple(outs)))
        # aux states receive zero cotangents (reference: aux is not
        # differentiated)
        aux_zeros = tuple(jnp.zeros(s, t)
                          for s, t in zip(aux_shapes, aux_types))
        return tuple(grads) + aux_zeros

    f.defvjp(f_fwd, f_bwd)
    outs = f(*inputs)
    return outs if len(outs) > 1 else outs[0]


_set_custom_hooks()
