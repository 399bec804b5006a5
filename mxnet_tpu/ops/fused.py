"""Fused BN→ReLU→1×1-Conv operator with a Pallas TPU kernel.

The single-chip ResNet step is HBM-bandwidth-bound (docs/ROOFLINE.md):
its top ops by device time are elementwise/reduce fusions sustaining
540–740 GB/s with negligible FLOPs. XLA fuses elementwise chains with
each other and into conv *outputs*, but it does not fuse an elementwise
producer into a convolution's *input* operand — so every BN-apply+ReLU
before a conv costs one full activation read + write that the MXU pass
then reads again. This module deletes that pass for the 1×1 convolutions
(2 of every 3 convs in a ResNet bottleneck):

    y = relu(x · scale + shift) @ W  (+ residual)

runs as ONE Pallas kernel: the per-channel affine (BN apply) and ReLU
happen in VMEM on the tile the MXU is about to consume, so ``x`` is read
exactly once and the ReLU'd activation never exists in HBM. The BN
*stats* pass stays in XLA (sum/sum² multi-output-fuse into one read);
``scale``/``shift`` are computed from (γ, β, mean, var) in plain jnp, so
JAX autodiff assembles the full BatchNorm backward through the stats —
the custom VJP here only supplies the big-tensor passes.

Reference parity: this replaces the composition BatchNorm → Activation →
Convolution(1×1) (src/operator/nn/batch_norm.cc, activation.cc,
convolution.cc); the graph rewrite lives in symbol/fuse.py (the TPU
analog of a graph-executor fusion pass, graph_executor.cc:905's
memory-plan/bulking stage being XLA's job already).
"""
from __future__ import annotations

import os
from functools import partial

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, current_op_context

__all__ = ["fused_scale_relu_matmul", "fused_bn_relu_conv"]


def _pallas_wanted():
    """Pallas only on real TPU backends (the CPU test mesh and the
    multichip dryrun use the jnp fallback — same math, same VJP)."""
    mode = os.environ.get("MXTPU_FUSED_PALLAS", "auto")
    if mode in ("0", "off"):
        return False
    if mode in ("1", "on", "interpret"):
        return True
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # no backend yet
        return False


def _interpret_mode():
    return os.environ.get("MXTPU_FUSED_PALLAS", "auto") == "interpret"


def _pick_tile_m(m):
    for tm in (512, 256, 128):
        if m % tm == 0:
            return tm
    return None


def _matmul_kernel(x_ref, scale_ref, shift_ref, w_ref, out_ref, *,
                   relu, out_dtype):
    xf = x_ref[:].astype(jnp.float32)
    z = xf * scale_ref[:] + shift_ref[:]
    if relu:
        z = jnp.maximum(z, 0.0)
    a = z.astype(w_ref.dtype)
    acc = jnp.dot(a, w_ref[:], preferred_element_type=jnp.float32)
    out_ref[:] = acc.astype(out_dtype)


def _matmul_res_kernel(x_ref, scale_ref, shift_ref, w_ref, res_ref,
                       out_ref, *, relu, out_dtype):
    xf = x_ref[:].astype(jnp.float32)
    z = xf * scale_ref[:] + shift_ref[:]
    if relu:
        z = jnp.maximum(z, 0.0)
    a = z.astype(w_ref.dtype)
    acc = jnp.dot(a, w_ref[:], preferred_element_type=jnp.float32)
    acc = acc + res_ref[:].astype(jnp.float32)
    out_ref[:] = acc.astype(out_dtype)


def _pallas_fwd(x2d, scale, shift, w2d, res):
    """One-pass relu(x·scale+shift) @ W (+res) on the MXU; grid over row
    tiles, weights resident in VMEM across the grid."""
    from jax.experimental import pallas as pl

    from ..pallas.attention import _count_launch

    m, k = x2d.shape
    n = w2d.shape[1]
    tm = _pick_tile_m(m)
    if tm is None:
        return None
    grid = (m // tm,)
    scale2 = scale.reshape(1, k).astype(jnp.float32)
    shift2 = shift.reshape(1, k).astype(jnp.float32)
    in_specs = [
        pl.BlockSpec((tm, k), lambda i: (i, 0)),
        pl.BlockSpec((1, k), lambda i: (0, 0)),
        pl.BlockSpec((1, k), lambda i: (0, 0)),
        pl.BlockSpec((k, n), lambda i: (0, 0)),
    ]
    args = [x2d, scale2, shift2, w2d]
    if res is not None:
        kern = partial(_matmul_res_kernel, relu=True, out_dtype=x2d.dtype)
        in_specs.append(pl.BlockSpec((tm, n), lambda i: (i, 0)))
        args.append(res)
    else:
        kern = partial(_matmul_kernel, relu=True, out_dtype=x2d.dtype)
    _count_launch("fused_scale_relu_matmul")
    fn = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x2d.dtype),
        interpret=_interpret_mode(),
    )
    with jax.named_scope("pallas.fused_scale_relu_matmul"):
        return fn(*args)


def _jnp_fwd(x2d, scale, shift, w2d, res):
    z = x2d.astype(jnp.float32) * scale + shift
    a = jnp.maximum(z, 0.0).astype(x2d.dtype)
    y = lax.dot_general(a, w2d, (((1,), (0,)), ((), ())))
    if res is not None:
        y = y + res
    return y.astype(x2d.dtype)


def _core_fwd(x2d, scale, shift, w2d, res):
    if _pallas_wanted():
        out = _pallas_fwd(x2d, scale, shift, w2d, res)
        if out is not None:
            return out
    return _jnp_fwd(x2d, scale, shift, w2d, res)


@partial(jax.custom_vjp, nondiff_argnums=())
def _core(x2d, scale, shift, w2d, res):
    return _core_fwd(x2d, scale, shift, w2d, res)


def _core_fwd_rule(x2d, scale, shift, w2d, res):
    y = _core_fwd(x2d, scale, shift, w2d, res)
    return y, (x2d, scale, shift, w2d, None if res is None else ())


def _core_bwd_rule(saved, dy):
    x2d, scale, shift, w2d, res_tag = saved
    f32 = jnp.float32
    # dz = (dy @ W^T) masked by relu'(z); z recomputed from x (elementwise
    # producer XLA fuses into the matmul output's consumer chain)
    da = lax.dot_general(dy, w2d, (((1,), (1,)), ((), ())))
    z = x2d.astype(f32) * scale + shift
    dz = jnp.where(z > 0, da.astype(f32), 0.0)
    # per-channel affine grads: one fused multi-output reduction pass
    dscale = jnp.sum(dz * x2d.astype(f32), axis=0)
    dshift = jnp.sum(dz, axis=0)
    dx = (dz * scale).astype(x2d.dtype)
    # dW = a^T @ dy with a recomputed from x
    a = jnp.maximum(z, 0.0).astype(x2d.dtype)
    dw = lax.dot_general(a, dy, (((0,), (0,)), ((), ())))
    dres = None if res_tag is None else dy
    return (dx, dscale.astype(scale.dtype), dshift.astype(shift.dtype),
            dw.astype(w2d.dtype), dres)


_core.defvjp(_core_fwd_rule, _core_bwd_rule)


def fused_scale_relu_matmul(x2d, scale, shift, w2d, res=None):
    """relu(x·scale + shift) @ W (+res) — differentiable fused primitive.

    x2d (M, K); scale/shift (K,) fp32; w2d (K, N); res (M, N) or None
    (None is a static empty pytree, so both arities share one VJP).
    """
    return _core(x2d, scale, shift, w2d, res)


@register("_FusedBNReluConv", num_outputs=3, num_visible_outputs=1,
          mutate_inputs=(("moving_mean", 1), ("moving_var", 2)))
def fused_bn_relu_conv(data, gamma, beta, moving_mean, moving_var, weight,
                       residual=None, *, num_filter, eps=2e-5, momentum=0.9,
                       fix_gamma=False, use_global_stats=False, layout="NHWC",
                       with_residual=False):
    """BatchNorm → ReLU → Convolution(1×1, stride 1, no bias) fused into
    one MXU pass (channel-last only). Optional ``residual`` is added to
    the conv output inside the same kernel (the shortcut add of a
    post-activation ResNet block). Outputs (y, new_moving_mean,
    new_moving_var); the moving stats update exactly like BatchNorm
    (ops/nn.py batch_norm). Created by symbol/fuse.py's graph rewrite —
    not part of the reference op set (cited ops: batch_norm.cc,
    activation.cc, convolution.cc)."""
    if not str(layout).endswith("C"):
        raise ValueError("_FusedBNReluConv requires a channel-last layout")
    ctx = current_op_context()
    f32 = jnp.float32
    k = data.shape[-1]
    red = tuple(range(data.ndim - 1))

    if moving_mean is None:
        moving_mean = jnp.zeros((k,), f32)
    if moving_var is None:
        moving_var = jnp.ones((k,), f32)

    if ctx.is_train and not use_global_stats:
        n = 1
        for i in red:
            n *= data.shape[i]
        # one fused read: sum and sum² multi-output-fuse (docs/PERF.md);
        # differentiable, so autodiff carries the full BN-through-stats
        # backward — _core's VJP only supplies the big-tensor passes
        s = jnp.sum(data, axis=red, dtype=f32)
        s2 = jnp.sum(jnp.square(data.astype(f32)), axis=red)
        mean = s / n
        var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
        mean_s = lax.stop_gradient(mean)
        var_s = lax.stop_gradient(var)
        new_mm = (moving_mean.astype(f32) * momentum
                  + mean_s * (1 - momentum)).astype(moving_mean.dtype)
        new_mv = (moving_var.astype(f32) * momentum
                  + var_s * (1 - momentum)).astype(moving_var.dtype)
    else:
        mean = lax.stop_gradient(moving_mean.astype(f32))
        var = lax.stop_gradient(moving_var.astype(f32))
        new_mm, new_mv = moving_mean, moving_var

    inv_std = lax.rsqrt(var + eps)
    g32 = jnp.ones_like(inv_std) if fix_gamma else gamma.astype(f32)
    scale = g32 * inv_std
    shift = beta.astype(f32) - mean * scale

    o = int(num_filter)
    w2d = weight.reshape(o, k).T            # OHWI (O,1,1,K) -> (K,O)
    x2d = data.reshape(-1, k)
    out_shape = data.shape[:-1] + (o,)
    res2d = None
    post_add = None
    if with_residual and residual is not None:
        if residual.shape == out_shape:
            res2d = residual.reshape(-1, o)
        else:                               # broadcasting add: keep outside
            post_add = residual
    y2d = fused_scale_relu_matmul(x2d, scale, shift, w2d, res2d)
    y = y2d.reshape(out_shape)
    if post_add is not None:
        y = y + post_add
    return (y, lax.stop_gradient(new_mm), lax.stop_gradient(new_mv))


def _fused_shapes(known, attrs):
    """Backward shape rule: data (…, K) + num_filter O infer the BN
    vectors (K,) and the channel-last conv weight (O, 1, 1, K)."""
    data = known.get("data")
    if data is None:
        return {}
    k = data[-1]
    o = int(attrs["num_filter"])
    nd = len(data)
    out = {"gamma": (k,), "beta": (k,), "moving_mean": (k,),
           "moving_var": (k,),
           "weight": (o,) + (1,) * (nd - 2) + (k,)}
    if attrs.get("with_residual"):
        out["residual"] = tuple(data[:-1]) + (o,)
    return out


def _fused_unused(attrs):
    return set() if attrs.get("with_residual") else {"residual"}


from .registry import get_op as _get_op  # noqa: E402

_op = _get_op("_FusedBNReluConv")
_op.param_shapes = _fused_shapes
_op.unused_inputs = _fused_unused
