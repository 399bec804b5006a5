"""Neural-network operators lowering to XLA.

Reference parity: src/operator/nn/ (fully_connected.cc:231, convolution.cc,
batch_norm.cc, pooling.cc, activation.cc, dropout-inl.h, layer_norm.cc,
softmax_output.cc, lrn.cc) and src/operator/tensor/indexing_op.cc(Embedding).

TPU-first notes: matmuls/convs map onto the MXU via lax.dot_general /
lax.conv_general_dilated; XLA layout assignment picks the TPU-internal
layout so the NCHW API surface carries no transpose cost. Ops that the
reference implements with cuDNN become single XLA HLOs here. Gradients come
from JAX autodiff except where MXNet semantics differ (SoftmaxOutput's
fused softmax-CE gradient → jax.custom_vjp).
"""
from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, current_op_context


def needs_rng(fn):
    fn._needs_rng = True
    return fn


def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        t = tuple(int(x) for x in v)
        return t if t else (1,) * n
    return (int(v),) * n


# ----------------------------------------------------------------------
# FullyConnected
# ----------------------------------------------------------------------
@register("FullyConnected", aliases=("fully_connected",))
def fully_connected(data, weight, bias=None, *, num_hidden, no_bias=False,
                    flatten=True):
    """y = x W^T + b (ref src/operator/nn/fully_connected-inl.h:85-166).
    weight layout (num_hidden, in_dim) matches the reference."""
    x = data
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    # bf16 inputs accumulate in fp32 on the MXU by default; no explicit
    # preferred_element_type (its transpose rule breaks mixed-dtype vjp)
    y = lax.dot_general(x, weight, (((x.ndim - 1,), (1,)), ((), ())))
    if not no_bias and bias is not None:
        y = y + bias
    return y


# ----------------------------------------------------------------------
# Convolution / Deconvolution
# ----------------------------------------------------------------------
def _conv_dnums(ndim, layout=None):
    """(lhs, rhs, out) layout strings. ``layout`` is the MXNet layout
    attr for the DATA tensor; channel-last layouts pair with
    channel-last weights (num_filter, *kernel, in_ch/g), matching the
    reference's NHWC contract (convolution.cc layout param)."""
    if layout:
        layout = str(layout)
        if layout.endswith("C"):            # NWC / NHWC / NDHWC
            rhs = "O" + layout[1:-1] + "I"
            return (layout, rhs, layout)
        rhs = "OI" + layout[2:]             # NCW / NCHW / NCDHW
        return (layout, rhs, layout)
    if ndim == 3:
        return ("NCH", "OIH", "NCH")
    if ndim == 4:
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


def _channel_axis(ndim, layout=None):
    return (ndim - 1) if (layout and str(layout).endswith("C")) else 1


@register("Convolution", aliases=("convolution",))
def convolution(data, weight, bias=None, *, kernel, num_filter, stride=(),
                dilate=(), pad=(), num_group=1, no_bias=False, cudnn_tune=None,
                cudnn_off=False, workspace=1024, layout=None):
    """N-D convolution (ref src/operator/nn/convolution.cc). Lowers to a
    single conv HLO on the MXU; groups via feature_group_count. TPU-first:
    ``layout='NHWC'`` (channel-last data AND weights) avoids every
    relayout copy around the conv — the preferred training layout."""
    nd = len(kernel)
    stride = _pair(stride, nd) if stride else (1,) * nd
    dilate = _pair(dilate, nd) if dilate else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dnums(data.ndim, layout))
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(num_group),
    ).astype(data.dtype)
    if not no_bias and bias is not None:
        ax = _channel_axis(data.ndim, layout)
        bshape = tuple(-1 if i == ax else 1 for i in range(data.ndim))
        out = out + bias.reshape(bshape)
    return out


@register("Deconvolution", aliases=("deconvolution",))
def deconvolution(data, weight, bias=None, *, kernel, num_filter, stride=(),
                  dilate=(), pad=(), adj=(), target_shape=(), num_group=1,
                  no_bias=True, cudnn_tune=None, cudnn_off=False,
                  workspace=512, layout=None):
    """Transposed convolution (ref src/operator/nn/deconvolution.cc).
    weight layout (in_ch, out_ch/g, kh, kw); implemented as the gradient of
    conv = conv with lhs_dilation."""
    nd = len(kernel)
    stride = _pair(stride, nd) if stride else (1,) * nd
    dilate = _pair(dilate, nd) if dilate else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    adj = _pair(adj, nd) if adj else (0,) * nd
    kernel = tuple(int(k) for k in kernel)
    # flip spatial dims; swap in/out channel axes → standard conv weight
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if int(num_group) > 1:
        g = int(num_group)
        w = w.reshape((g, w.shape[0] // g) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((w.shape[0] * w.shape[1],) + w.shape[2:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _conv_dnums(data.ndim))
    eff_k = tuple((kernel[i] - 1) * dilate[i] + 1 for i in range(nd))
    padding = [(eff_k[i] - 1 - pad[i], eff_k[i] - 1 - pad[i] + adj[i])
               for i in range(nd)]
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=int(num_group),
    ).astype(data.dtype)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * (data.ndim - 2))
    return out


# ----------------------------------------------------------------------
# BatchNorm
# ----------------------------------------------------------------------
def _bn_train_fused(red, bshape, eps, fix_gamma, n):
    """Training-mode batch norm as ONE fused stats pass + ONE apply pass,
    with a hand-derived backward (ONE reduction pass + ONE elementwise
    pass). The HBM-bandwidth-optimal schedule:

    * stats: sum(x) and sum(x^2) are independent reductions over the same
      operand, so XLA multi-output-fuses them into a single read of the
      activation with fp32 accumulators (vs the naive mean-then-var
      serial double pass). var = E[x^2] - E[x]^2, the cuDNN "persistent"
      formulation.
    * apply/backward passes read and write the activation dtype (bf16 on
      TPU); fp32 math happens in registers inside the fusion, so no fp32
      copy of any activation ever hits HBM.

    Gradients for save_mean/save_var outputs are intentionally dropped
    (reference semantics: batch_norm.cc differentiates only through out).
    """
    f32 = jnp.float32

    def _stats(x):
        s = jnp.sum(x, axis=red, dtype=f32)
        s2 = jnp.sum(jnp.square(x.astype(f32)), axis=red)
        mean = s / n
        var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
        return mean, var

    @jax.custom_vjp
    def f(x, gamma, beta):
        mean, var = _stats(x)
        inv_std = lax.rsqrt(var + eps)
        g32 = jnp.ones_like(inv_std) if fix_gamma else gamma.astype(f32)
        scale = g32 * inv_std
        shift = beta.astype(f32) - mean * scale
        out = (x.astype(f32) * scale.reshape(bshape)
               + shift.reshape(bshape)).astype(x.dtype)
        return out, mean, var

    def f_fwd(x, gamma, beta):
        mean, var = _stats(x)
        inv_std = lax.rsqrt(var + eps)
        g32 = jnp.ones_like(inv_std) if fix_gamma else gamma.astype(f32)
        scale = g32 * inv_std
        shift = beta.astype(f32) - mean * scale
        out = (x.astype(f32) * scale.reshape(bshape)
               + shift.reshape(bshape)).astype(x.dtype)
        return (out, mean, var), (x, gamma, mean, inv_std, g32)

    def f_bwd(res, cts):
        x, gamma, mean, inv_std, g32 = res
        dy = cts[0]                     # cotangents of mean/var dropped
        t1 = jnp.sum(dy, axis=red, dtype=f32)
        t2 = jnp.sum(dy.astype(f32) * x.astype(f32), axis=red)
        dgamma = (t2 - mean * t1) * inv_std
        dbeta = t1
        # dx = scale*(dy - dbeta/n - xhat*dgamma/n) expanded to a single
        # a*dy + b*x + c per-channel affine pass
        scale = g32 * inv_std
        bcoef = -scale * inv_std * dgamma / n
        ccoef = (scale * inv_std * dgamma * mean - scale * dbeta) / n
        dx = (dy.astype(f32) * scale.reshape(bshape)
              + x.astype(f32) * bcoef.reshape(bshape)
              + ccoef.reshape(bshape)).astype(x.dtype)
        if fix_gamma:
            dgamma = jnp.zeros_like(dgamma)
        return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)

    f.defvjp(f_fwd, f_bwd)
    return f


@register("BatchNorm", aliases=("batch_norm", "CuDNNBatchNorm"), num_outputs=5,
          num_visible_outputs=lambda attrs: 3 if attrs.get("output_mean_var") else 1,
          mutate_inputs=(("moving_mean", 3), ("moving_var", 4)))
def batch_norm(data, gamma, beta, moving_mean=None, moving_var=None, *,
               eps=1e-3, momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False):
    """Batch normalization (ref src/operator/nn/batch_norm.cc).
    Returns (out, save_mean, save_inv_var, new_moving_mean, new_moving_var);
    the last two update the aux states (reference mutates them in place).
    Training mode runs the fused one-pass schedule (_bn_train_fused)."""
    ctx = current_op_context()
    ax = int(axis) % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    bshape = tuple(data.shape[i] if i == ax else 1 for i in range(data.ndim))

    if moving_mean is None:
        moving_mean = jnp.zeros(data.shape[ax], dtype=jnp.float32)
    if moving_var is None:
        moving_var = jnp.ones(data.shape[ax], dtype=jnp.float32)

    use_batch_stats = ctx.is_train and not use_global_stats
    if use_batch_stats:
        n = 1
        for i in red:
            n *= data.shape[i]
        out, mean, var = _bn_train_fused(red, bshape, float(eps),
                                         bool(fix_gamma), float(n))(
            data, gamma, beta)
        inv_std = lax.rsqrt(var + eps)
        # keep the aux dtype: fp32 math, cast back so the moving stats
        # never drift dtype step-over-step (which would silently retrace
        # the jitted step after the first update)
        new_mm = (moving_mean.astype(jnp.float32) * momentum
                  + mean * (1 - momentum)).astype(moving_mean.dtype)
        new_mv = (moving_var.astype(jnp.float32) * momentum
                  + var * (1 - momentum)).astype(moving_var.dtype)
    else:
        mean = lax.stop_gradient(moving_mean.astype(jnp.float32))
        var = lax.stop_gradient(moving_var.astype(jnp.float32))
        new_mm, new_mv = moving_mean, moving_var
        inv_std = lax.rsqrt(var + eps)
        g32 = (jnp.ones_like(inv_std) if fix_gamma
               else gamma.astype(jnp.float32))
        scale = g32 * inv_std
        shift = beta.astype(jnp.float32) - mean * scale
        out = (data.astype(jnp.float32) * scale.reshape(bshape)
               + shift.reshape(bshape)).astype(data.dtype)
    return (out, mean, inv_std,
            lax.stop_gradient(new_mm), lax.stop_gradient(new_mv))


def _use_layernorm_kernel(axis_last):
    """Select the fused Pallas LayerNorm kernel.  MXNET_LN_IMPL:
    ``auto`` (default) = the fused kernel on TPU when normalizing the
    last axis, ``xla`` = the reference chain, ``pallas`` = require the
    kernel (interpret mode off-TPU — the tier-1 parity convention).
    Semantics shared with the other kernel knobs via
    ``pallas.dispatch.choose_impl`` (docs/KERNELS.md)."""
    from ..pallas.dispatch import use_layernorm_pallas
    return use_layernorm_pallas(axis_last)


@register("LayerNorm", aliases=("layer_norm",), num_outputs=3,
          num_visible_outputs=lambda attrs: 3 if attrs.get("output_mean_var") else 1)
def layer_norm(data, gamma, beta, *, axis=-1, eps=1e-5, output_mean_var=False):
    """Layer normalization (ref src/operator/nn/layer_norm.cc).

    The transformer symbol path (axis=-1, stats outputs hidden) routes
    through the fused Pallas forward/backward kernel when selected by
    ``MXNET_LN_IMPL`` — one VMEM pass instead of XLA's separate
    mean/var/normalize/scale chains; the kernel's custom VJP does not
    propagate mean/inv_std cotangents, so routing requires
    ``output_mean_var=False`` (where they are structurally unused)."""
    ax = int(axis) % data.ndim
    kernel = (not output_mean_var and data.ndim >= 2
              and _use_layernorm_kernel(ax == data.ndim - 1))
    if kernel:
        from ..pallas import layernorm_fused
        out, mean, inv_std = layernorm_fused(
            data, gamma.reshape(-1), beta.reshape(-1), eps=eps,
            interpret=kernel == "interpret")
        return (out, mean, inv_std)
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=ax, keepdims=True)
    var = jnp.var(xf, axis=ax, keepdims=True)
    inv_std = lax.rsqrt(var + eps)
    out = (xf - mean) * inv_std
    bshape = tuple(data.shape[i] if i == ax else 1 for i in range(data.ndim))
    out = out * gamma.reshape(bshape) + beta.reshape(bshape)
    return (out.astype(data.dtype), jnp.squeeze(mean, ax), jnp.squeeze(inv_std, ax))


@register("RMSNorm", aliases=("rms_norm",))
def rms_norm(data, gamma, *, axis=-1, eps=1e-5, zero_centered=False):
    """Root-mean-square normalization with a gain and no shift:
    ``x / sqrt(mean(x^2) + eps) * gamma`` over ``axis``; the statistic
    and the scaling in float32, the result in the data's dtype.  With
    ``zero_centered`` the gain is ``1 + gamma`` (a gamma initialised 0
    is the identity, and weight decay pulls the gain towards 1)."""
    ax = int(axis) % data.ndim
    xf = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=ax, keepdims=True) + eps)
    bshape = tuple(data.shape[i] if i == ax else 1 for i in range(data.ndim))
    gain = gamma.astype(jnp.float32).reshape(bshape)
    out = xf * inv * (1.0 + gain if zero_centered else gain)
    return out.astype(data.dtype)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, *, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization")
def l2_normalization(data, *, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
    elif mode == "channel":
        red = (1,)
    else:  # spatial
        red = tuple(range(2, data.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / norm


@register("LRN")
def lrn(data, *, nsize, alpha=1e-4, beta=0.75, knorm=2.0):
    """Local response norm across channels (ref src/operator/nn/lrn.cc)."""
    sq = jnp.square(data)
    half = int(nsize) // 2
    summed = lax.reduce_window(
        sq, 0.0, lax.add, (1, int(nsize), 1, 1), (1, 1, 1, 1),
        [(0, 0), (half, half), (0, 0), (0, 0)])
    return data * jnp.power(knorm + alpha * summed / nsize, -beta)


# ----------------------------------------------------------------------
# Pooling
# ----------------------------------------------------------------------
@register("Pooling", aliases=("pooling",))
def pooling(data, *, kernel=(), pool_type="max", global_pool=False, stride=(),
            pad=(), pooling_convention="valid", cudnn_off=False,
            count_include_pad=True, p_value=2, layout=None):
    """Max/avg/sum/lp pooling (ref src/operator/nn/pooling.cc).
    ``layout`` accepts channel-last strings (NWC/NHWC/NDHWC) so pooling
    composes with NHWC convolutions without relayouts."""
    nd = data.ndim - 2
    chlast = bool(layout) and str(layout).endswith("C")
    sp0 = 1 if chlast else 2            # first spatial axis
    if global_pool:
        red = tuple(range(sp0, sp0 + nd))
        if pool_type == "max":
            out = jnp.max(data, axis=red, keepdims=True)
        elif pool_type == "sum":
            out = jnp.sum(data, axis=red, keepdims=True)
        else:
            out = jnp.mean(data, axis=red, keepdims=True)
        return out
    kernel = _pair(kernel, nd)
    stride = _pair(stride, nd) if stride else (1,) * nd
    pad = _pair(pad, nd) if pad else (0,) * nd
    if chlast:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        base_pad = [(0, 0)] + [(p, p) for p in pad] + [(0, 0)]
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        base_pad = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pooling_convention == "full":
        # ceil semantics: add extra right-pad so the last window fits
        for i in range(nd):
            size = data.shape[sp0 + i] + 2 * pad[i]
            out_sz = -(-(size - kernel[i]) // stride[i]) + 1  # ceil
            need = (out_sz - 1) * stride[i] + kernel[i] - size
            base_pad[sp0 + i] = (pad[i], pad[i] + max(0, need))
    if pool_type == "max":
        init = (-jnp.inf if jnp.issubdtype(data.dtype, jnp.floating)
                else jnp.iinfo(data.dtype).min)
        return lax.reduce_window(data, init, lax.max, window, strides, base_pad)
    summed = lax.reduce_window(data, 0.0, lax.add, window, strides, base_pad)
    if pool_type == "sum":
        return summed
    if pool_type == "avg":
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return summed / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, jnp.asarray(0, data.dtype), lax.add,
                                   window, strides, base_pad)
        return summed / counts
    raise ValueError("unsupported pool_type %s" % pool_type)



@register("UpSampling", key_var_num_args="num_args")
def upsampling(*args, scale=2, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=512):
    """Nearest/bilinear upsampling (ref src/operator/upsampling.cc)."""
    data = args[0]
    s = int(scale)
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, s, axis=2), s, axis=3)
        return out
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * s, w * s), method="bilinear")


# ----------------------------------------------------------------------
# Activations
# ----------------------------------------------------------------------
@register("Activation", aliases=("activation",))
def activation(data, *, act_type):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError("unknown act_type %s" % act_type)


@register("LeakyReLU")
@needs_rng
def leaky_relu(data, gamma=None, *, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    """leaky/prelu/elu/selu/gelu/rrelu (ref src/operator/leaky_relu.cc)."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, lam = 1.6732632423543772, 1.0507009873554805
        return lam * jnp.where(data > 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "gelu_tanh":
        # tanh-approximated GELU (GPT-2 convention) — extension beyond the
        # reference's erf GELU; polynomial VPU math, no erf transcendental
        return jax.nn.gelu(data, approximate=True)
    if act_type == "rrelu":
        ctx = current_op_context()
        if ctx.is_train:
            key = ctx.next_rng_key()
            slope_s = jax.random.uniform(key, data.shape, dtype=data.dtype,
                                         minval=lower_bound, maxval=upper_bound)
        else:
            slope_s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, slope_s * data)
    raise ValueError("unknown act_type %s" % act_type)


@register("softmax_cross_entropy", aliases=("SoftmaxCrossEntropy",))
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype("int32"), data.shape[-1], dtype=logp.dtype)
    return -jnp.sum(oh * logp)


# ----------------------------------------------------------------------
# Dropout
# ----------------------------------------------------------------------
@register("Dropout", aliases=("dropout",), num_outputs=2, num_visible_outputs=1)
@needs_rng
def dropout_op(data, *, p=0.5, mode="training", axes=(), cudnn_off=False):
    """Dropout (ref src/operator/nn/dropout-inl.h). mask is the 2nd output."""
    ctx = current_op_context()
    if (not ctx.is_train and mode != "always") or p <= 0.0:
        return data, jnp.ones_like(data)
    key = ctx.next_rng_key()
    shape = data.shape
    if axes:
        shape = tuple(1 if i in tuple(axes) else s for i, s in enumerate(shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(data.dtype) / keep
    return data * mask, jnp.broadcast_to(mask, data.shape)


# ----------------------------------------------------------------------
# SoftmaxOutput — custom gradient identical to the reference's fused
# softmax + cross-entropy backward (src/operator/softmax_output-inl.h).
# ----------------------------------------------------------------------
def _softmax_fwd(data, label, attrs):
    if attrs["multi_output"]:
        # data (n, c, d1...): softmax over axis 1
        prob = jax.nn.softmax(data, axis=1)
    else:
        prob = jax.nn.softmax(data, axis=-1)
    return prob


def _softmax_grad(prob, label, attrs):
    grad_scale = attrs["grad_scale"]
    ignore_label = attrs["ignore_label"]
    use_ignore = attrs["use_ignore"]
    normalization = attrs["normalization"]
    smooth_alpha = attrs["smooth_alpha"]
    if attrs["multi_output"]:
        caxis, nclass = 1, prob.shape[1]
        lab = label.astype("int32")
        oh = jnp.moveaxis(jax.nn.one_hot(lab, nclass, dtype=prob.dtype), -1, 1)
    else:
        caxis, nclass = prob.ndim - 1, prob.shape[-1]
        lab = label.astype("int32")
        oh = jax.nn.one_hot(lab, nclass, dtype=prob.dtype)
    if smooth_alpha:
        oh = oh * (1.0 - smooth_alpha) + smooth_alpha / (nclass - 1) * (1.0 - oh)
    grad = prob - oh
    valid = jnp.ones(lab.shape, dtype=prob.dtype)
    if use_ignore:
        valid = (lab != int(ignore_label)).astype(prob.dtype)
        grad = grad * jnp.expand_dims(valid, caxis)
    if normalization == "valid":
        grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
    elif normalization == "batch":
        grad = grad / prob.shape[0]
    return grad * grad_scale


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, *, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    attrs = dict(grad_scale=grad_scale, ignore_label=ignore_label,
                 multi_output=multi_output, use_ignore=use_ignore,
                 normalization=normalization, smooth_alpha=smooth_alpha)

    @jax.custom_vjp
    def _f(d, l):
        return _softmax_fwd(d, l, attrs)

    def _f_fwd(d, l):
        prob = _softmax_fwd(d, l, attrs)
        return prob, (prob, l)

    def _f_bwd(res, g):
        prob, l = res
        # reference ignores upstream out_grad unless out_grad=True
        return _softmax_grad(prob, l, attrs).astype(prob.dtype), jnp.zeros_like(l)

    _f.defvjp(_f_fwd, _f_bwd)
    return _f(data, label)


@register("LinearRegressionOutput")
def linear_regression_output(data, label, *, grad_scale=1.0):
    """Identity fwd; grad = (pred - label)/batch (ref src/operator/regression_output-inl.h)."""
    @jax.custom_vjp
    def _f(d, l):
        return d

    def _fwd(d, l):
        return d, (d, l)

    def _bwd(res, g):
        d, l = res
        grad = (d - l.reshape(d.shape)) * grad_scale / d.shape[0]
        return grad, jnp.zeros_like(l)

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register("MAERegressionOutput")
def mae_regression_output(data, label, *, grad_scale=1.0):
    @jax.custom_vjp
    def _f(d, l):
        return d

    def _fwd(d, l):
        return d, (d, l)

    def _bwd(res, g):
        d, l = res
        grad = jnp.sign(d - l.reshape(d.shape)) * grad_scale / d.shape[0]
        return grad, jnp.zeros_like(l)

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, *, grad_scale=1.0):
    @jax.custom_vjp
    def _f(d, l):
        return jax.nn.sigmoid(d)

    def _fwd(d, l):
        out = jax.nn.sigmoid(d)
        return out, (out, l)

    def _bwd(res, g):
        out, l = res
        grad = (out - l.reshape(out.shape)) * grad_scale / out.shape[0]
        return grad, jnp.zeros_like(l)

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register("SoftmaxActivation")
def softmax_activation(data, *, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _diffusion_rows(logits, noised, weight, mask_id):
    masked = (noised == mask_id)[..., None]
    kept = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1) \
        == noised.astype(jnp.int32)[..., None]
    low = -0.7 * float(jnp.finfo(logits.dtype).max)
    return jnp.where(masked, logits,
                     jnp.where(kept, 0.0, low).astype(logits.dtype))


def _diffusion_rows_fwd(logits, noised, weight, mask_id):
    return _diffusion_rows(logits, noised, weight, mask_id), (noised, weight)


def _diffusion_rows_bwd(mask_id, res, g):
    noised, weight = res
    scale = jnp.where(noised == mask_id, weight.astype(jnp.float32), 0.0)
    return ((g.astype(jnp.float32) * scale[..., None]).astype(g.dtype),
            jnp.zeros_like(noised), jnp.zeros_like(weight))


_diffusion_rows.defvjp(_diffusion_rows_fwd, _diffusion_rows_bwd)


@register("_contrib_DiffusionHead", aliases=("DiffusionHead",),
          num_outputs=2)
def diffusion_head(data, noised, weight, *, mask_id):
    """The masked, weighted rows of a masked-diffusion loss, put between
    a model's logits and its normalising loss head: ``data`` (..., V)
    the logits of the noised rows, ``noised`` (...) their input ids
    ``xt`` (float or int) and ``weight`` (...) a row's loss weight.

    Forward, a row whose ``xt`` is ``mask_id`` passes; any other row is
    replaced by one that puts all its mass on ``xt`` itself, so that its
    cross-entropy at the label (the token it shows) reads 0: the
    carry-over of the masked-diffusion parameterisation.  Backward, a
    masked row's cotangent is multiplied by its weight (in float32,
    rounded once) and every other row's is 0.  So a ``SoftmaxOutput``
    behind it reads ``(1 / rows) sum_i m_i ce_i`` through ``ce`` and
    sends the gradient of ``(1 / rows) sum_i m_i w_i ce_i``, and the
    operator is that head's stem (``loss_head.py``): the probabilities
    stay deferred.

    Output 1 is ``(masked rows, rows)`` int32, for
    ``telemetry.diffusion``.  Scope ``head.diffusion``."""
    with jax.named_scope("head.diffusion"):
        masked = noised == mask_id
        rows = jnp.stack([jnp.sum(masked), masked.size]).astype(jnp.int32)
        return _diffusion_rows(data, noised, weight, float(mask_id)), rows


# ----------------------------------------------------------------------
# Attention (new TPU-native capability — the reference predates
# attention entirely, SURVEY.md §5.7; sequence-parallel forms live in
# parallel/ring_attention.py)
# ----------------------------------------------------------------------
def _use_flash_attention(seq_len, head_dim, dtype, v_dim=None, window=None,
                         blocks=None):
    """Whether the fused Pallas flash pair runs the masked softmax core
    (``"compiled"``) or XLA does (False).  No knob: the choice is where
    the program runs (``pallas.dispatch._compiles_here``: one TPU
    device) and the geometry, and a refusal is counted under
    ``pallas_fallbacks{reason}``: ``backend``, ``mesh``,
    ``flash-geometry``, ``flash-window``, ``flash-blocks``.

    The geometry is the same whatever the head counts: the kernel shares
    a key/value head among its query heads itself (``_flash_attention``).
    ``head_dim`` is the width of queries and keys, ``v_dim`` that of the
    values where it differs (latent attention: 192 and 128).  Values and
    the output fill whole lane tiles of 128; queries and keys at least
    one and then whole halves of one (the contraction of QK^T is padded
    to the MXU's edge by the compiler, not by the caller).  A band
    (``window``, None for plain causal attention) is whole score blocks
    of 512; one that is not is the only refusal counted under reason
    ``flash-window``.  A block-diffusion mask (``blocks``, the block
    length; ``seq_len`` is the clean and the noised half together) is a
    block length that divides 512 and halves of whole score blocks; one
    that is not is counted under ``flash-blocks``.
    It is never interpreted here: only a test passes ``interpret=True``
    to ``_flash_attention``."""
    from ..pallas.dispatch import (PALLAS_FALLBACKS, RETRACE_SUPPRESS,
                                   _compiles_here)
    here, _, reason = _compiles_here()
    v_dim = head_dim if v_dim is None else v_dim
    causal = (here and head_dim >= 128 and head_dim % 64 == 0
              and v_dim % 128 == 0 and seq_len % 512 == 0
              and dtype in (jnp.bfloat16, jnp.float32))
    if blocks is not None:
        masked = blocks > 0 and 512 % blocks == 0 and seq_len % 1024 == 0
    else:
        masked = window is None or (window > 0 and window % 512 == 0)
    if causal and masked:
        return "compiled"
    if not RETRACE_SUPPRESS.on:         # not a program-registry re-lower
        PALLAS_FALLBACKS.labels(reason=reason or (
            "flash-geometry" if not causal else
            "flash-window" if blocks is None else "flash-blocks")).inc()
    return False


def _flash_block_sizes(seq_len):
    """The forward flash kernel's tiles, from the sequence alone (a
    multiple of 512: the geometry gate): 1024 query rows against 1024
    resident key/value rows where that divides it, else 512, the scores
    computed 512 columns at a time; no width has asked for other tiles.
    The backward is the repo's own kernel and takes its shapes itself
    (``pallas/flash_backward.py`` ``plan``: 512 x 512 score blocks, a
    key/value head's rows resident, cut into segments where they pass its
    VMEM budget)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes
    rows = 1024 if seq_len % 1024 == 0 else 512
    return BlockSizes(block_q=rows, block_kv=rows, block_kv_compute=512)


def _block_diffusion_allowed(seq_len, blocks):
    """``allowed(q_ids, kv_ids)`` of the block-diffusion mask over
    ``seq_len`` rows, a clean sequence of ``L = seq_len / 2`` rows (whole
    blocks of ``blocks``) and its noised copy side by side.  With
    ``pos(r) = r mod L`` and ``blk(r) = pos(r) // blocks``: a clean
    query sees the clean keys of ``blk(s) <= blk(t)``, a noised query
    the clean keys of ``blk(s) < blk(t)`` and the noised keys of
    ``blk(s) == blk(t)``; a clean query never sees a noised key.
    Operators alone (numpy blocks for a kernel's tables, int32 tiles
    inside a kernel, index arrays for XLA), and few: a partial block
    pays them a cell."""
    half = seq_len // 2
    if blocks & (blocks - 1):           # any length: XLA's path alone
        blk = lambda r: r // blocks
    else:
        shift = blocks.bit_length() - 1
        blk = lambda r: r >> shift
    # blocks counted over all 2 L rows: a noised row's lies ``ahead``
    # past its clean twin's
    ahead = half // blocks

    def allowed(q_ids, kv_ids):
        # Same block: a clean row's own block, a noised row's own noised
        # block.  Else a clean query takes the blocks before its own,
        # and a noised one those before its clean twin's: ``ahead + 1``
        # back, which no noised key's block is.
        bq, bk = blk(q_ids), blk(kv_ids)
        return (bk == bq) | (bk <= bq - (ahead + 1) * (q_ids >= half))

    return allowed


def _block_diffusion_mask(seq_len, blocks):
    """The block-diffusion mask (``_block_diffusion_allowed``) as a
    splash-attention ``Mask`` that is never an array: the kernel's
    tables ask it for a block at a time (``__getitem__``) and the kernel
    computes a partial block's cells from the row numbers."""
    from jax.experimental.pallas.ops.tpu.splash_attention import \
        splash_attention_mask as _masks

    class BlockDiffusionMask(_masks._ComputableMask):
        def __init__(self, seq_len, blocks):
            self.blocks = int(blocks)
            super().__init__((seq_len, seq_len),
                             _block_diffusion_allowed(seq_len, blocks))

        # two masks are one where length AND block length agree: the
        # kernel's cached tables are keyed by the mask
        def __eq__(self, other):
            return isinstance(other, type(self)) \
                and (self.shape, self.blocks) == (other.shape, other.blocks)

        def __hash__(self):
            return hash((type(self).__name__, self.shape, self.blocks))

    return BlockDiffusionMask(seq_len, blocks)


@_functools.lru_cache(maxsize=None)
def _flash_kernel(q_heads, seq_len, interpret, residuals=False, window=None,
                  blocks=None):
    """jax's splash-attention forward kernel for one sequence under one
    of the three static masks, built once per head count, length and
    mask (the widths and the key/value head count are the operands'
    own): the mask's block tables are host numpy work at trace time, and
    every layer of a model asks for the same ones.  Causal (neither
    ``window`` nor ``blocks``): jax's ``CausalMask``, 136 blocks of 1024
    rows at 16 384 rows.  With ``window`` the mask is jax's
    ``LocalMask`` (``t - (window - 1) <= s <= t``) and its tables drop
    the blocks left of the band: at a window of 4096, 70 of the 136.
    With ``blocks`` it is ``_block_diffusion_mask`` and the tables drop
    the dead quadrant and everything above the two block-triangles: 80
    of the 256 blocks (36 + 36 + 8).  With ``residuals`` the kernel
    returns ``(o, (lse,))``, the rows' log-sum-exp beside the result, as
    a backward needs it."""
    import numpy as np
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        CausalMask, LocalMask, MultiHeadMask, make_splash_mha)
    shape = (seq_len, seq_len)
    one = _block_diffusion_mask(seq_len, blocks) if blocks is not None \
        else CausalMask(shape) if window is None \
        else LocalMask(shape, window_size=(window - 1, 0), offset=0)
    mask = MultiHeadMask([one] * q_heads)
    # the kernel keeps its tables as arrays: made outside whatever trace
    # asks first, and kept on the host, they are constants of every
    # program that uses them
    with jax.ensure_compile_time_eval():
        kernel = make_splash_mha(
            mask, head_shards=1, q_seq_shards=1, interpret=interpret,
            save_residuals=residuals,
            block_sizes=_flash_block_sizes(seq_len))
    return jax.tree_util.tree_map(np.asarray, kernel)


def _flash_scope(window, blocks=None):
    """The scope, and the launch counter's label, of the flash pair:
    the band's and the block-diffusion mask's kernels are counted apart
    from the causal ones."""
    return "flash_attention_blocks" if blocks is not None \
        else "flash_attention" if window is None else "flash_attention_window"


def _flash_kernel_of(q_heads, seq_len, interpret, residuals, window, blocks):
    # one cache entry a geometry: the causal kernel is asked for as ever
    return _flash_kernel(q_heads, seq_len, interpret, residuals,
                         *(() if window is None else (window,)),
                         **({} if blocks is None else {"blocks": blocks}))


def _flash_forward(q, k, v, interpret, residuals, window=None, blocks=None):
    kernel = _flash_kernel_of(q.shape[1], q.shape[2], interpret, residuals,
                              window, blocks)
    with jax.named_scope("pallas." + _flash_scope(window, blocks)):
        return jax.vmap(kernel)(q, k, v)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, interpret, window, blocks):
    return _flash_forward(q, k, v, interpret, False, window, blocks)


def _flash_fwd(q, k, v, interpret, window, blocks):
    o, (lse,) = _flash_forward(q, k, v, interpret, True, window, blocks)
    return o, (q, k, v, o, lse)


def _flash_bwd(interpret, window, blocks, res, do):
    from ..pallas.flash_backward import flash_attention_backward
    with jax.named_scope("pallas." + _flash_scope(window, blocks)):
        return flash_attention_backward(*res, do, window=window,
                                        blocks=blocks, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _count_flash_blocks(batch, q_heads, seq_len, window, interpret,
                        blocks=None):
    """Books, at trace time, the 512 x 512 score blocks one banded or
    block-diffusion flash pair executes beside those the causal pair
    would over the same rows (``flash_blocks_walked`` /
    ``flash_blocks_causal``, by kernel): forward what the kernel's own
    tables keep (its blocks counted in 512s) against the triangle of
    such blocks, backward the walk."""
    import numpy as np
    from ..pallas.dispatch import (FLASH_BLOCKS_CAUSAL, FLASH_BLOCKS_WALKED,
                                   RETRACE_SUPPRESS)
    from ..pallas.flash_backward import _BLOCK, blocks_walked
    if RETRACE_SUPPRESS.on:     # a program-registry re-lower, as launches
        return
    table = _flash_kernel_of(q_heads, seq_len, interpret, True, window,
                             blocks).fwd_mask_info.block_mask
    bs = _flash_block_sizes(seq_len)
    per = (bs.block_q // _BLOCK) * (bs.block_kv // _BLOCK)
    rows = seq_len // bs.block_q
    # one table serves all heads where they share a mask
    kept = int(np.count_nonzero(table)) * (q_heads // table.shape[0])
    scope = _flash_scope(window, blocks)
    for kernel, walked, causal in (
            (scope, kept * per, q_heads * per * rows * (rows + 1) // 2),
            (scope + "_bwd", q_heads * blocks_walked(seq_len, window, blocks),
             q_heads * blocks_walked(seq_len))):
        FLASH_BLOCKS_WALKED.labels(kernel=kernel).inc(batch * walked)
        FLASH_BLOCKS_CAUSAL.labels(kernel=kernel).inc(batch * causal)


def _flash_attention(q, k, v, *, window=None, blocks=None, interpret=False):
    """Softmax attention under one of three static masks (causal,
    causal and banded, block diffusion) of head-major q (B, Hq, S,
    D) over k (B, Hk, S, D) and v (B, Hk, S, Dv), Hq a multiple of Hk
    and Dv = D unless the values are narrower (the result is (B, Hq, S,
    Dv)): float32 scores, statistics and accumulators whatever the
    operands' dtype, and a key/value head shared by its Hq / Hk query
    heads inside the kernels (no repeated K/V, dK and dV summed over the
    group in VMEM).  Forward it is jax's splash Pallas kernel, which
    keeps the rows' log-sum-exp where a gradient is asked for; backward
    it is the repo's own kernel (``pallas/flash_backward.py``), which
    computes every live score block once (causal: those on or under the
    diagonal, 528 a head at 16 384 rows) and emits dq, dk, dv from it
    with no partial sums in HBM.

    ``window`` (static; a multiple of 512, anything else raises): a
    query attends the ``window`` keys that end with its own (``query -
    key < window``), and neither kernel computes a block left of that
    band (252 of the 528 at a window of 4096 backward; the forward's
    1024-row tables keep 70 of 136).  ``None``, or a window no shorter
    than the sequence, builds exactly the causal kernels.

    ``blocks`` (static; the block length ``Bk``, a divisor of 512, with S
    two halves of whole score blocks: anything else raises, as does a
    window beside it): the rows are a clean sequence and its noised copy
    side by side, and the mask is ``_block_diffusion_mask``'s: keys right
    of the query are attended inside a block, the clean-noised quadrant
    is dead.  At 16 384 rows (two halves of 8192) the backward walks 288
    of the 1024 blocks a head (136 clean-clean, 136 noised-clean, 16 on
    the noised diagonal), the forward's 1024-row tables keep 80 of 256.

    The kernels take no softmax scale: ``q`` carries it (a caller scales
    q where it is still float32, so q is rounded once).  ``interpret``
    is for the tests; ``_use_flash_attention`` never asks for it."""
    from ..pallas.attention import _count_launch
    S = q.shape[2]
    if window is not None:
        window = int(window)
        if window <= 0 or window % 512:
            raise ValueError("flash attention: window=%d is not a positive "
                             "multiple of 512" % window)
        if window >= S:
            window = None
    if blocks is not None:
        blocks = int(blocks)
        if blocks <= 0 or 512 % blocks or S % 1024 or window is not None:
            raise ValueError(
                "flash attention: blocks=%d does not divide 512, S=%d is "
                "not two halves of whole blocks of 512, or a window was "
                "given beside it" % (blocks, S))
    _count_launch(_flash_scope(window, blocks))
    if window is not None or blocks is not None:
        _count_flash_blocks(q.shape[0], q.shape[1], S, window,
                            bool(interpret), blocks)
    return _flash(q, k, v, bool(interpret), window, blocks)


def _grouped_causal_attention(q, k, v, scale, window=None, blocks=None):
    """Masked softmax attention of head-major q (B, Hq, S, D) over k
    (B, Hk, S, D) and v (B, Hk, S, Dv) by XLA, each key/value head
    shared by its Hq / Hk query heads; float32 scores, the probabilities
    in q's dtype; checkpointed, so the (S, S) scores are not kept for
    the backward pass.  Causal; with ``window`` a query attends the
    ``window`` keys that end with its own (``query - key < window``),
    any width; with ``blocks`` the mask is the block-diffusion one over
    a clean and a noised half (``_block_diffusion_allowed``), any block
    length that divides a half.  What the core runs where the flash pair cannot; equal head
    counts are the case of one query head a group."""
    B, Hq, S, D = q.shape
    Hk, Dv = k.shape[1], v.shape[3]

    @jax.checkpoint
    def attn(q, k, v):
        s = jnp.einsum("bgrqe,bgke->bgrqk",
                       q.reshape(B, Hk, Hq // Hk, S, D), k) * scale
        t, u = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        if blocks is not None:
            mask = _block_diffusion_allowed(S, blocks)(t, u)
        else:
            back = t - u
            mask = back >= 0 if window is None \
                else (back >= 0) & (back < window)
        s = jnp.where(mask, s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bgrqk,bgke->bgrqe", p, v).reshape(B, Hq, S, Dv)

    return attn(q, k, v)


def _causal_attention_core(seq_len, head_dim, dtype, scale, v_dim=None,
                           window=None, blocks=None):
    """The one place that knows which masked softmax core a mixer runs,
    who carries the softmax scale, and what runs otherwise.  The mask is
    one of three static kinds: causal (528 score blocks of 512 a head at
    16 384 rows), causal and banded (``window``: 252 at a window of
    4096), or the block-diffusion mask (``blocks``, the block length;
    ``seq_len`` is then the clean and the noised half together: 288).
    ``window=None, blocks=None`` builds exactly the causal kernels.  A
    front asks once a call with what the choice depends on and gets
    ``(fold, attend)``:

    ``fold``: the factor q takes in the front's last float32 stage, so
    that q is rounded once: ``scale`` where the chosen core takes none
    (the flash pair), 1.0 where the core applies the scale itself.
    Multiplying by it is always right; a front that would open a float32
    stage for it alone skips the stage at 1.0.

    ``attend(q, k, v)``: head-major q (B, Hq, S, D) over k (B, Hk, S, D)
    and v (B, Hk, S, Dv) -> (B, Hq, S, Dv), under the front's own scope:
    ``_flash_attention`` where ``_use_flash_attention`` says so, else
    ``_grouped_causal_attention``, either under the same mask."""
    # ``blocks`` goes on only where it is set: the causal and banded
    # callers, and what a test puts in the kernels' place, see what
    # they saw
    kind = {} if blocks is None else {"blocks": int(blocks)}
    if _use_flash_attention(seq_len, head_dim, dtype, v_dim, window, **kind):
        return scale, _functools.partial(_flash_attention, window=window,
                                         **kind)
    return 1.0, lambda q, k, v: _grouped_causal_attention(
        q, k, v, scale, window, **kind)


def _project_heads(spec, data, weight, bias, fold=1.0):
    """``data`` times a head-major ``weight`` plus ``bias``.  With a
    ``fold`` other than 1 (``_causal_attention_core``'s, for a query)
    the float32 accumulator takes it before it is rounded to ``data``'s
    dtype: one rounding."""
    if fold == 1.0:
        return jnp.einsum(spec, data, weight) + bias
    acc = jnp.einsum(spec, data, weight,
                     preferred_element_type=jnp.float32)
    return ((acc + bias) * fold).astype(data.dtype)


@register("_contrib_CausalSelfAttention", aliases=("CausalSelfAttention",))
def causal_self_attention(qkv, *, num_heads, scale=None):
    """Fused causal multi-head self-attention over a packed QKV tensor:
    (B, S, 3*d_model) -> (B, S, d_model).

    Head-major through ``_causal_attention_core``: one kernel pair
    whose (S, S) scores never reach HBM, or two MXU einsums round a
    float32 softmax, rematerialized in backward; either way no (S, S)
    matrix is saved as a residual and live memory stays O(S·d) a layer.
    """
    B, S, d3 = qkv.shape
    d = d3 // 3
    H = int(num_heads)
    if d % H:
        raise ValueError("d_model %d not divisible by num_heads %d" % (d, H))
    D = d // H
    sc = (1.0 / D ** 0.5) if scale is None else float(scale)

    fold, attend = _causal_attention_core(S, D, qkv.dtype, sc)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    to_heads = lambda t: t.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    if fold != 1.0:
        # q arrives rounded: scaled in float32 and rounded once more
        q = (q.astype(jnp.float32) * fold).astype(qkv.dtype)
    o = attend(to_heads(q), to_heads(k), to_heads(v))
    return o.transpose(0, 2, 1, 3).reshape(B, S, d)


@register("_contrib_FusedCausalSelfAttention",
          aliases=("FusedCausalSelfAttention",))
def fused_causal_self_attention(data, qkv_weight, qkv_bias, proj_weight,
                                proj_bias, *, num_heads, scale=None,
                                head_axis=None):
    """Whole attention sublayer in one op: QKV projection -> causal MHA ->
    output projection, (B, S, d) -> (B, S, d).

    TPU-first layout trick: the projections are dot_generals that emit /
    consume the HEAD-MAJOR (B, H, S, D) layout directly, so no transpose
    ever materialises between the matmuls and the causal core
    (``_causal_attention_core``; a separate (B,S,H,D)->(B,H,S,D) copy
    costs ~0.5 ms/layer fwd+bwd at d2048/S1024 on v5e, builders'
    measurement).  Weight
    layouts match the reference FullyConnected convention ((3d, d) /
    (d, d) row-major), so checkpoints from the unfused pair load
    unchanged.

    ``head_axis`` (docs/SHARDING.md): a mesh-axis name partitioning the
    HEAD dim for tensor parallelism — q/k/v/o get GSPMD sharding
    constraints over (None, head_axis) so each mp shard computes its own
    heads locally (the Megatron split).  Inert when no mesh is selected
    or the selected mesh lacks the axis; programs are cached per mesh
    fingerprint so the trace-time mesh read cannot go stale.
    """
    B, S, d = data.shape
    H = int(num_heads)
    if d % H:
        raise ValueError("d_model %d not divisible by num_heads %d" % (d, H))
    D = d // H
    sc = (1.0 / D ** 0.5) if scale is None else float(scale)

    _shard_heads = lambda t: t
    if head_axis is not None:
        from .. import sharding as _sharding
        _mesh = _sharding.get_mesh()
        if _mesh is not None and str(head_axis) in _mesh.axis_names:
            from jax.sharding import NamedSharding, PartitionSpec as P
            _ns = NamedSharding(_mesh, P(None, str(head_axis)))
            _shard_heads = lambda t: jax.lax.with_sharding_constraint(t, _ns)

    Wqkv = qkv_weight.reshape(3, H, D, d)
    bqkv = qkv_bias.reshape(3, H, 1, D)
    fold, attend = _causal_attention_core(S, D, data.dtype, sc)
    proj = _functools.partial(_project_heads, "bsd,hed->bhse", data)
    q = _shard_heads(proj(Wqkv[0], bqkv[0], fold))
    k = _shard_heads(proj(Wqkv[1], bqkv[1]))
    v = _shard_heads(proj(Wqkv[2], bqkv[2]))
    o = _shard_heads(attend(q, k, v))
    return jnp.einsum("bhse,dhe->bsd", o,
                      proj_weight.reshape(d, H, D)) + proj_bias


def _shift_right(x, n, axis):
    """``x`` moved ``n`` positions later along ``axis``, zeros first: a
    causal tap (position t reads t - n, and nothing before position 0)."""
    if n == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (n, 0)
    return lax.slice_in_dim(jnp.pad(x, pad), 0, x.shape[axis], axis=axis)


def _rotary_half(x, rot, theta):
    """Rotary position on the first ``rot`` of the last axis of a
    head-major (B, H, S, D) float32 tensor, the default pairing of
    halves (i with i + rot/2); positions 0..S-1."""
    if not rot:
        return x
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


@register("_contrib_CompressedConvAttention",
          aliases=("CompressedConvAttention",))
def compressed_conv_attention(data, q_weight, k_weight, v_weight,
                              conv0_weight, conv1_weight, temp, o_weight, *,
                              q_heads, kv_heads, head_dim, conv_k0=2,
                              conv_k1=2, rotary_frac=0.5, rope_theta=5e6):
    """Compressed convolutional attention (CCA, arXiv:2510.04476) as
    one sublayer, (B, S, d) -> (B, S, d), on an already normalised
    stream; no linear map has a bias.

    Queries live in a ``q_heads * head_dim`` latent and keys/values in a
    ``kv_heads * head_dim`` one, both narrower than the model:
    ``q0 = h Wq``, ``k0 = h Wk``; the channels of ``[q0, k0]`` pass a
    depthwise causal convolution over the sequence (``conv0_weight``
    (channels, conv_k0)) and a causal convolution that mixes the
    channels of each head (``conv1_weight`` (heads, out, in, conv_k1));
    the mean of q0 and its key head's k0 is added back (to the key: that
    mean over its query heads); the value's first head comes from this
    token and its second from the token before; q and k are scaled to
    length sqrt(head_dim), k times a learned ``temp`` a key/value head;
    rotary position turns the first ``rotary_frac`` of each head; causal
    softmax attention with ``q_heads / kv_heads`` query heads to a
    key/value head; output projection.  Weights are (out, in), as
    FullyConnected's.

    Head-major like FusedCausalSelfAttention: the projections emit and
    consume (B, H, S, D), and ``_causal_attention_core`` takes K and V
    at their own ``kv_heads`` (its fold joins the length q is given:
    1 where q carries the softmax scale, not sqrt(head_dim)).
    Everything between the projections and the attention is cheap and
    rematerialized in the backward pass, so a layer saves its latents and no float32 copy of
    them.  Scopes for a reader of the raw trace: ``cca.proj``,
    ``cca.conv``, ``cca.attention``."""
    B, S, d = data.shape
    Hq, Hk, D = int(q_heads), int(kv_heads), int(head_dim)
    K0, K1 = int(conv_k0), int(conv_k1)
    if Hq % Hk:
        raise ValueError("q_heads %d not a multiple of kv_heads %d"
                         % (Hq, Hk))
    if Hk != 2:
        raise ValueError("the value shift gives one value head to this "
                         "token and one to the token before: kv_heads "
                         "must be 2, got %d" % Hk)
    G, H = Hq // Hk, Hq + Hk
    rot = int(round(float(rotary_frac) * D))
    f32 = jnp.float32

    with jax.named_scope("cca.proj"):
        z = jnp.einsum("bsd,hed->bhse", data,
                       jnp.concatenate([q_weight, k_weight], 0)
                       .reshape(H, D, d))
        v2 = jnp.einsum("bsd,hed->bhse", data, v_weight.reshape(2, D, d))

    fold, attend = _causal_attention_core(S, D, data.dtype, 1.0 / D ** 0.5)
    # the fold joins the float32 length q is given before its one
    # rounding: 1 where q carries the softmax scale, sqrt(head_dim) else
    q_length = D ** 0.5 * fold

    @jax.checkpoint
    def mix(z, v2, w0, w1, temp):
        zf = z.astype(f32)
        w0 = w0.astype(f32).reshape(1, H, 1, D, K0)
        z1 = sum(_shift_right(zf, K0 - 1 - j, 2) * w0[..., j]
                 for j in range(K0)).astype(z.dtype)
        z2 = sum(jnp.einsum("bhsi,hoi->bhso", _shift_right(z1, K1 - 1 - j, 2),
                            w1[..., j]) for j in range(K1)).astype(f32)
        q0 = zf[:, :Hq].reshape(B, Hk, G, S, D)
        mq = 0.5 * (q0 + zf[:, Hq:, None])
        q = z2[:, :Hq] + mq.reshape(B, Hq, S, D)
        k = z2[:, Hq:] + jnp.mean(mq, axis=2)
        unit = lambda t, length: t * lax.rsqrt(
            jnp.sum(jnp.square(t), -1, keepdims=True)) * length
        q = _rotary_half(unit(q, q_length), rot, float(rope_theta))
        k = _rotary_half(unit(k, D ** 0.5)
                         * temp.astype(f32).reshape(1, Hk, 1, 1),
                         rot, float(rope_theta))
        v = jnp.stack([v2[:, 0], _shift_right(v2[:, 1], 1, 1)], axis=1)
        return q.astype(z.dtype), k.astype(z.dtype), v

    with jax.named_scope("cca.conv"):
        q, k, v = mix(z, v2, conv0_weight, conv1_weight, temp)

    with jax.named_scope("cca.attention"):
        o = attend(q, k, v)

    with jax.named_scope("cca.proj"):
        return jnp.einsum("bhse,dhe->bsd", o, o_weight.reshape(d, Hq, D))


@register("_contrib_GatedCausalSelfAttention",
          aliases=("GatedCausalSelfAttention",))
def gated_causal_self_attention(data, q_weight, k_weight, v_weight,
                                q_norm_gamma, k_norm_gamma, o_weight, *,
                                q_heads, kv_heads, head_dim,
                                rotary_frac=0.25, rope_theta=1e7, eps=1e-6):
    """Causal softmax attention with an output gate, as one sublayer
    (B, S, d) -> (B, S, d) on an already normalised stream; no bias.

    ``q_weight`` (q_heads * 2 * head_dim, d) gives each query head its
    query and, beside it, a gate of the same width; ``k_weight``,
    ``v_weight`` (kv_heads * head_dim, d).  Query and key heads are
    RMS-normalised over their channels with the zero-centred gain
    ``1 + gamma`` (one gain vector for all heads); rotary position turns
    the first ``rotary_frac`` of each head; causal attention at scale
    ``head_dim ** -0.5`` with ``q_heads / kv_heads`` query heads to a
    key/value head; the result times ``sigmoid(gate)``, then
    ``o_weight`` (d, q_heads * head_dim).

    Head-major like CompressedConvAttention; ``_causal_attention_core``
    takes K and V at their own ``kv_heads`` (its fold joins the query
    norm's float32 gain).  Norms and rotary are rematerialized in the
    backward pass.  Scopes: ``gattn.proj``,
    ``gattn.norm``, ``gattn.attention``."""
    B, S, d = data.shape
    Hq, Hk, D = int(q_heads), int(kv_heads), int(head_dim)
    if Hq % Hk:
        raise ValueError("q_heads %d not a multiple of kv_heads %d"
                         % (Hq, Hk))
    rot = int(round(float(rotary_frac) * D))
    f32 = jnp.float32
    with jax.named_scope("gattn.proj"):
        qg = jnp.einsum("bsd,hted->tbhse", data,
                        q_weight.reshape(Hq, 2, D, d))
        q0, gate = qg[0], qg[1]
        k0 = jnp.einsum("bsd,hed->bhse", data, k_weight.reshape(Hk, D, d))
        v = jnp.einsum("bsd,hed->bhse", data, v_weight.reshape(Hk, D, d))

    fold, attend = _causal_attention_core(S, D, data.dtype, D ** -0.5)

    @jax.checkpoint
    def prepare(q0, k0, gq, gk):
        def norm(t, gain, scale):
            t = t.astype(f32)
            inv = lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True) + eps)
            return t * inv * ((1.0 + gain.astype(f32)) * scale)
        q = _rotary_half(norm(q0, gq, fold), rot, float(rope_theta))
        k = _rotary_half(norm(k0, gk, 1.0), rot, float(rope_theta))
        return q.astype(q0.dtype), k.astype(k0.dtype)

    with jax.named_scope("gattn.norm"):
        q, k = prepare(q0, k0, q_norm_gamma, k_norm_gamma)

    with jax.named_scope("gattn.attention"):
        o = attend(q, k, v)
        o = (o.astype(f32) * jax.nn.sigmoid(gate.astype(f32))) \
            .astype(data.dtype)

    with jax.named_scope("gattn.proj"):
        return jnp.einsum("bhse,dhe->bsd", o, o_weight.reshape(d, Hq, D))


@register("_contrib_GroupedQueryAttention",
          aliases=("GroupedQueryAttention",))
def grouped_query_attention(data, q_weight, k_weight, v_weight, o_weight,
                            q_norm_gamma=None, k_norm_gamma=None, *,
                            q_heads, kv_heads, head_dim, window=0,
                            rotary=True, rope_theta=1e6, qk_norm=False,
                            eps=1e-6, blocks=0):
    """Plain grouped-query attention as one sublayer (B, S, d) ->
    (B, S, d) on an already normalised stream: no bias, no gate.
    ``q_weight`` (q_heads * head_dim, d), ``k_weight``,
    ``v_weight`` (kv_heads * head_dim, d), ``q_heads / kv_heads`` query
    heads to a key/value head (head j reads key/value head ``j //
    (q_heads / kv_heads)``), scale ``head_dim ** -0.5``, then
    ``o_weight`` (d, q_heads * head_dim).

    ``rotary``: rotary position on all ``head_dim`` channels of queries
    and keys (halves paired, ``rope_theta``, positions 0..S-1); False is
    a layer with no position at all.  ``window`` > 0: a query attends
    the ``window`` keys that end with its own (``query - key < window``);
    0, or a window no shorter than the sequence, is full causal
    attention.  A model mixes both kinds of layer by giving each its own
    attributes.

    ``qk_norm`` brings the two inputs ``q_norm_gamma`` and
    ``k_norm_gamma`` (head_dim each; absent without it): query and key
    heads are RMS-normalised over their channels in float32 with a plain
    gain, one vector for all heads (``eps``), before the rotation.

    ``blocks`` > 0 (the block length) is a block-diffusion training
    pass: the S rows are a clean sequence of ``L = S / 2`` rows and its
    noised copy side by side, the rotation turns each half by positions
    ``0 .. L - 1`` (row ``L + i`` stands where row ``i`` does), and the
    mask is the block-diffusion one (``_causal_attention_core``): a
    clean row attends the clean blocks up to its own, a noised row the
    clean blocks before its own and the noised rows of its own block.
    0 is causal attention.

    Head-major like GatedCausalSelfAttention; ``_causal_attention_core``
    runs the core under the layer's mask (its fold joins the
    projection's float32 accumulator on a layer without position or
    norm, the rotation's or the norm's float32 on one with).  The
    rotation is linear: its backward needs the angles alone, which are
    made again from the positions, and nothing of its input is kept;
    with the norms, both are made again in the backward pass from the
    projections' results.  Scopes: ``gqa.proj``, ``gqa.norm`` (the norms
    and the rotation behind them), ``gqa.rope``, and the core under
    ``gqa.window`` (a band that bites), ``gqa.blockdiff`` or
    ``gqa.full``."""
    B, S, d = data.shape
    Hq, Hk, D = int(q_heads), int(kv_heads), int(head_dim)
    if Hq % Hk:
        raise ValueError("q_heads %d not a multiple of kv_heads %d"
                         % (Hq, Hk))
    Bk = int(blocks) or None
    if Bk and (S % (2 * Bk) or int(window)):
        raise ValueError("GroupedQueryAttention: blocks=%d takes a clean "
                         "and a noised half of whole blocks (S=%d) and no "
                         "window" % (Bk, S))
    band = int(window) if 0 < int(window) < S else None
    fold, attend = _causal_attention_core(S, D, data.dtype, D ** -0.5,
                                          window=band, blocks=Bk)
    f32 = jnp.float32
    turned, normed = bool(rotary), bool(qk_norm)
    with jax.named_scope("gqa.proj"):
        # q takes the fold in float32: the norm's or the rotation's
        # where the layer has one, else the projection's accumulator
        heads = lambda w, n, **kw: jnp.einsum(
            "bsd,hed->bhse", data, w.reshape(n, D, d), **kw)
        q = heads(q_weight, Hq) if turned or normed or fold == 1.0 else (
            heads(q_weight, Hq, preferred_element_type=f32) * fold) \
            .astype(data.dtype)
        k, v = heads(k_weight, Hk), heads(v_weight, Hk)

    def turn(t):
        # a block-diffusion pass turns each half by its own positions:
        # the halves as heads of their own
        if not turned:
            return t
        halves = t.reshape(B, -1, S // 2, D) if Bk else t
        return _rotary_half(halves, D, float(rope_theta)).reshape(t.shape)

    if normed:
        @jax.checkpoint
        def prepare(q0, k0, gq, gk):
            def norm(t, gain, scale):
                t = t.astype(f32)
                inv = lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                                + eps)
                return t * inv * (gain.astype(f32) * scale)
            return (turn(norm(q0, gq, fold)).astype(q0.dtype),
                    turn(norm(k0, gk, 1.0)).astype(k0.dtype))

        with jax.named_scope("gqa.norm"):
            q, k = prepare(q, k, q_norm_gamma, k_norm_gamma)
    elif turned:
        with jax.named_scope("gqa.rope"):
            spin = lambda t, scale: (turn(t.astype(f32)) * scale) \
                .astype(t.dtype)
            q, k = spin(q, fold), spin(k, 1.0)
    with jax.named_scope("gqa.blockdiff" if Bk else
                         "gqa.window" if band else "gqa.full"):
        o = attend(q, k, v)
    with jax.named_scope("gqa.proj"):
        return jnp.einsum("bhse,dhe->bsd", o, o_weight.reshape(d, Hq, D))


def _rotary_interleaved(x, theta):
    """Rotary position on ALL of the last axis of a float32 (..., S, R)
    tensor, neighbours paired (2i with 2i + 1: the layout of a
    checkpoint whose config says ``rope_interleave``); positions
    0..S-1.  ``_rotary_half`` pairs halves and turns the first
    channels."""
    S, R = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(R // 2, dtype=jnp.float32) * 2.0 / R)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (R // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


@register("_contrib_LatentAttention", aliases=("LatentAttention",))
def latent_attention(data, q_weight, kva_weight, kv_norm_gamma, kvb_weight,
                     o_weight, *, heads, nope_dim, rope_dim, v_dim, kv_rank,
                     rope_theta=1e6, eps=1e-6, rotary=True):
    """Multi-head latent attention (MLA, DeepSeek-V2/V3's, without the
    query's low-rank path) as one sublayer, (B, S, d) -> (B, S, d), on
    an already normalised stream; no bias.

    ``q_weight`` (heads * (nope_dim + rope_dim), d) gives each head its
    query, ``nope_dim`` channels without position and ``rope_dim`` with;
    ``kva_weight`` (kv_rank + rope_dim, d) the latent ``c`` and ONE
    rotary key that all heads share; ``kvb_weight`` (heads * (nope_dim +
    v_dim), kv_rank) turns ``RMSNorm(c) * kv_norm_gamma`` into each
    head's positionless key and its value.  Rotary position (neighbours
    paired, ``_rotary_interleaved``) turns the rotary channels of the
    queries and the shared key; a head's key is its own ``nope_dim``
    channels beside the shared ``rope_dim``; causal attention at scale
    ``(nope_dim + rope_dim) ** -0.5`` with values ``v_dim`` wide, then
    ``o_weight`` (d, heads * v_dim).  Weights are (out, in), as
    FullyConnected's, in the source's row order.

    ``rotary=False`` is a layer with no position at all (Kimi-Linear's,
    whose linear layers carry the order): the ``rope_dim`` channels stay
    in every query and as the one key part all heads share, unturned,
    and ``rope_theta`` is unused.

    Head-major like the other mixers; ``_causal_attention_core`` runs
    the core at keys 192 wide and values 128 (its fold joins q's float32
    before the rotation).  The latent's norm, the rotary and the assembly of q and
    k are float32 and rematerialized in the backward pass.  Scopes:
    ``mla.proj``, ``mla.norm``, ``mla.attention``."""
    B, S, d = data.shape
    H, Dn, Dr, Dv, C = (int(heads), int(nope_dim), int(rope_dim),
                        int(v_dim), int(kv_rank))
    D = Dn + Dr
    f32 = jnp.float32
    with jax.named_scope("mla.proj"):
        q0 = jnp.einsum("bsd,hed->bhse", data, q_weight.reshape(H, D, d))
        ckr = jnp.einsum("bsd,ed->bse", data, kva_weight)

    fold, attend = _causal_attention_core(S, D, data.dtype, D ** -0.5, Dv)
    theta = float(rope_theta)

    @jax.checkpoint
    def latent(ckr, gain):
        c = ckr[..., :C].astype(f32)
        inv = lax.rsqrt(jnp.mean(jnp.square(c), -1, keepdims=True) + eps)
        return (c * inv * gain.astype(f32)).astype(ckr.dtype)

    with jax.named_scope("mla.norm"):
        cn = latent(ckr, kv_norm_gamma)

    with jax.named_scope("mla.proj"):
        wkv = kvb_weight.reshape(H, Dn + Dv, C)
        k0 = jnp.einsum("bsc,hec->bhse", cn, wkv[:, :Dn])
        v = jnp.einsum("bsc,hec->bhse", cn, wkv[:, Dn:])

    @jax.checkpoint
    def position(q0, k0, ckr):
        turn = (lambda t: _rotary_interleaved(t, theta)) if rotary \
            else (lambda t: t)
        qf = q0.astype(f32) * fold
        q = jnp.concatenate([qf[..., :Dn], turn(qf[..., Dn:])], -1)
        kr = turn(ckr[..., C:].astype(f32))
        k = jnp.concatenate(
            [k0, jnp.broadcast_to(kr[:, None].astype(k0.dtype),
                                  (B, H, S, Dr))], -1)
        return q.astype(q0.dtype), k

    with jax.named_scope("mla.norm"):
        q, k = position(q0, k0, ckr)

    with jax.named_scope("mla.attention"):
        o = attend(q, k, v)

    with jax.named_scope("mla.proj"):
        return jnp.einsum("bhse,dhe->bsd", o, o_weight.reshape(d, H, Dv))


@register("_contrib_SparseIndexedAttention",
          aliases=("SparseIndexedAttention",), num_outputs=3)
def sparse_indexed_attention(data, q_weight, k_weight, v_weight,
                             q_norm_gamma, k_norm_gamma, o_weight,
                             idx_q_weight, idx_k_weight, idx_w_weight,
                             idx_k_norm_gamma, idx_k_norm_beta, *, q_heads,
                             kv_heads, head_dim, idx_heads, idx_dim, topk,
                             rope_theta=1e7, eps=1e-6, q_chunk=512,
                             kv_chunk=512):
    """Grouped-query causal attention over the ``topk`` keys that a
    learned index scorer picks for each query (DeepSeek Sparse
    Attention), as one sublayer (B, S, d) -> (B, S, d) on an already
    normalised stream; no bias.

    Main attention: ``q_weight`` (q_heads * head_dim, d), ``k_weight``,
    ``v_weight`` (kv_heads * head_dim, d); query and key heads
    RMS-normalised over their channels with a plain gain (one vector for
    all heads), rotary position on all channels (halves paired), scale
    ``head_dim ** -0.5``, ``q_heads / kv_heads`` query heads to a
    key/value head, then ``o_weight`` (d, q_heads * head_dim).

    The scorer reads ``stop_gradient(data)`` in float32 whatever the
    model's dtype: ``idx_q_weight`` (idx_heads * idx_dim, d) its
    queries, ``idx_k_weight`` (idx_dim, d) ONE key a token behind a
    LayerNorm (``idx_k_norm_gamma``, ``idx_k_norm_beta``), rotary on all
    ``idx_dim`` channels of both, ``idx_w_weight`` (idx_heads, d) a
    weight a head and token; ``I[t, s] = idx_heads ** -0.5 * idx_dim **
    -0.5 * sum_j w[t, j] relu(q_j[t] . k[s])``.  Query t attends the
    ``topk`` keys s <= t with the largest I (a tie to the lower s; all
    of them while t < topk).

    Outputs: ``y``; the index loss (1,) float32, the mean over tokens of
    ``KL(p_t || softmax over the chosen of I[t])`` with ``p_t`` the
    heads' mean probabilities as a constant; int32 (2,): the ``q_chunk``
    x ``kv_chunk`` score tiles that hold a chosen pair, and the tiles on
    or under the diagonal.  The gradient of ``y`` reaches the main
    attention's inputs and never the scorer's; the index loss's reaches
    the five ``idx_`` inputs and nothing else (``ops/sparse_attention.py``
    writes both out, and makes q, k, v and the scorer's operands again in
    the backward pass: between the passes the layer keeps its input, the
    heads' result, the rows' statistics and the choice as bits).  Scopes: ``dsa.proj``, ``dsa.norm``,
    ``dsa.indexer``, ``dsa.select``, ``dsa.attention``,
    ``dsa.index_loss``."""
    from .sparse_attention import sparse_indexed_attention as core
    B, S, d = data.shape
    Hq, Hk, D = int(q_heads), int(kv_heads), int(head_dim)
    Hi, Di = int(idx_heads), int(idx_dim)
    if Hq % Hk:
        raise ValueError("q_heads %d not a multiple of kv_heads %d"
                         % (Hq, Hk))
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    theta = float(rope_theta)

    def front(data, wq, wk, wv, gq, gk, wiq, wik, wiw, gik, bik):
        """(q, k, v) of the main attention and (qi, ki, wi) of the
        scorer from the layer's operands; made again in the backward
        pass, so nothing of it is kept."""
        def heads(w, n, gain):
            with jax.named_scope("dsa.proj"):
                t = jnp.einsum("bsd,hed->bhse", data, w.reshape(n, D, d))
            if gain is None:
                return t
            with jax.named_scope("dsa.norm"):
                tf = t.astype(f32)
                inv = lax.rsqrt(jnp.mean(jnp.square(tf), -1, keepdims=True)
                                + eps)
                return _rotary_half(tf * inv * gain.astype(f32), D,
                                    theta).astype(t.dtype)

        with jax.named_scope("dsa.indexer"):
            h = lax.stop_gradient(data).astype(f32)
            qi = _rotary_half(jnp.einsum(
                "bsd,hed->bhse", h, wiq.astype(f32).reshape(Hi, Di, d),
                precision=hi), Di, theta)
            ki = jnp.einsum("bsd,ed->bse", h, wik.astype(f32), precision=hi)
            mean = jnp.mean(ki, -1, keepdims=True)
            var = jnp.mean(jnp.square(ki - mean), -1, keepdims=True)
            ki = (ki - mean) * lax.rsqrt(var + eps) * gik.astype(f32) \
                + bik.astype(f32)
            ki = _rotary_half(ki[:, None], Di, theta)[:, 0]
            wi = jnp.einsum("bsd,hd->bsh", h, wiw.astype(f32),
                            precision=hi) * (Hi ** -0.5 * Di ** -0.5)
        return heads(wq, Hq, gq), heads(wk, Hk, gk), heads(wv, Hk, None), \
            qi, ki, wi

    o, kl, live = core(
        front, (data, q_weight, k_weight, v_weight, q_norm_gamma,
                k_norm_gamma, idx_q_weight, idx_k_weight, idx_w_weight,
                idx_k_norm_gamma, idx_k_norm_beta),
        topk=int(topk), q_chunk=int(q_chunk), kv_chunk=int(kv_chunk))
    with jax.named_scope("dsa.proj"):
        y = jnp.einsum("bhse,dhe->bsd", o, o_weight.reshape(d, Hq, D))
    return (y, (jnp.sum(kl) / (B * S)).reshape(1),
            lax.stop_gradient(jnp.sum(live, axis=0)))


def gdn_conv(qkv, conv_weight, k_heads):
    """What Gated DeltaNet's ``[q; k; v]`` (B, 2 k_heads + v_heads, S, D),
    head-major, passes before the scan, in ``jax.numpy``: a causal
    depthwise convolution over the sequence (``conv_weight`` (channels,
    conv_kernel), the last tap the position itself, zeros before
    position 0), SiLU, and for the q and k heads an L2 norm a head, q
    scaled by ``D ** -0.5``.  float32 from the widening to the one
    rounding back to the input's dtype.  Returns (q, k, v)."""
    _, H, _, D = qkv.shape
    K = conv_weight.shape[-1]
    f32 = jnp.float32
    x = qkv.astype(f32)
    wc = conv_weight.astype(f32).reshape(1, H, 1, D, K)
    x = jax.nn.silu(sum(_shift_right(x, K - 1 - j, 2) * wc[..., j]
                        for j in range(K)))
    unit = lambda t: t * lax.rsqrt(
        jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
    q = unit(x[:, :k_heads]) * D ** -0.5
    k = unit(x[:, k_heads:2 * k_heads])
    low = qkv.dtype
    return q.astype(low), k.astype(low), x[:, 2 * k_heads:].astype(low)


def _gdn_mix_impl(qkv, k_heads, conv_kernel):
    """How :func:`gdn_mix` runs when not told: the Pallas kernels
    (``"compiled"``) in a one-device TPU program whose head width fills
    whole lane tiles, else :func:`gdn_conv` (False; the fallback is
    counted in ``pallas_fallbacks{reason}``).  No knob: a test passes
    ``impl``."""
    from ..pallas.dispatch import _compiles_here, choose_impl
    from ..pallas.gdn_mix import supported
    here, why, reason = _compiles_here()
    fits, shapes = supported(qkv, k_heads, conv_kernel)
    return choose_impl(
        "gdn_mix (no knob)", "auto", "gdn_mix", here and fits,
        why="%s, %s" % (why or "one TPU device", shapes),
        fallback_reason=reason or "gdn-mix-geometry")


def gdn_mix(qkv, conv_weight, k_heads, impl=None):
    """:func:`gdn_conv`, rematerialized in the backward pass.  ``impl``:
    None chooses (:func:`_gdn_mix_impl`); ``"compiled"`` /
    ``"interpret"`` is the Pallas kernels under scope ``pallas.gdn_mix``
    (``pallas/gdn_mix.py``), forward and backward; False is
    :func:`gdn_conv` under ``jax.checkpoint``."""
    if impl is None:
        impl = _gdn_mix_impl(qkv, k_heads, conv_weight.shape[-1])
    if not impl:
        return jax.checkpoint(gdn_conv, static_argnums=2)(
            qkv, conv_weight, k_heads)
    from ..pallas.gdn_mix import gdn_mix as kernels
    return kernels(qkv, conv_weight, k_heads, interpret=impl == "interpret")


@register("_contrib_GatedDeltaNet", aliases=("GatedDeltaNet",))
def gated_delta_net(data, qkvz_weight, ba_weight, conv_weight, A_log,
                    dt_bias, norm_gamma, out_weight, *, k_heads, v_heads,
                    k_dim, v_dim, conv_kernel=4, eps=1e-6):
    """The Gated DeltaNet mixer (arXiv:2412.06464) as one sublayer,
    (B, S, d) -> (B, S, d), on an already normalised stream; no bias.

    ``qkvz_weight`` ((2 k_heads k_dim + 2 v_heads v_dim), d) gives, as
    contiguous blocks of rows, q and k (``k_heads`` heads of ``k_dim``),
    v and the output gate z (``v_heads`` of ``v_dim``); ``ba_weight``
    (2 v_heads, d) the write strength b and the decay input a of every
    value head.  ``[q; k; v]`` pass a causal depthwise convolution over
    the sequence (``conv_weight`` (channels, conv_kernel), tap
    ``conv_kernel - 1`` the position itself) and SiLU; q and k are
    L2-normalised a head, q scaled by ``k_dim ** -0.5``; ``beta =
    sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` in float32
    (``A_log``, ``dt_bias`` (v_heads,)); each key head serves
    ``v_heads / k_heads`` value heads through the gated delta rule
    (``ops/delta_rule.py``: chunks of 64 tokens, state float32);
    the result is RMS-normalised a value head with gain ``norm_gamma``
    (v_dim,) and gated by ``silu(z)``, then ``out_weight`` (d, v_heads
    v_dim).

    Head-major: the projection emits (B, H, S, D).  Convolution,
    normalisation and gates are rematerialized in the backward pass.
    The convolution, SiLU and L2 norms keep ``qkv`` and the weight only
    (:func:`gdn_mix`): as Pallas kernels (a one-device TPU program) the
    backward kernel computes a block's pre-activation again in VMEM;
    as ``jax.numpy`` (everywhere else) the whole of :func:`gdn_conv`
    runs again in the backward pass.  The gates ``g`` and ``beta`` are
    ``jax.numpy`` under ``jax.checkpoint`` on both paths.
    The scan keeps its inputs only (``ops/delta_rule.py``
    ``gated_delta_rule``): as Pallas kernels (a one-device TPU program)
    it also keeps the state every run of 8 chunks starts from, and its
    backward computes a run's chunks again in VMEM; as ``jax.numpy``
    (everywhere else) its whole forward runs again there.  Scopes:
    ``gdn.proj``, ``gdn.conv`` (``pallas.gdn_mix`` inside it),
    ``gdn.scan`` (``pallas.gated_delta_rule`` inside it), ``gdn.norm``."""
    from .delta_rule import gated_delta_rule
    B, S, d = data.shape
    Hk, Hv, Dk, Dv = int(k_heads), int(v_heads), int(k_dim), int(v_dim)
    if Dk != Dv:
        raise ValueError("the mixed q, k, v projection is head-major over "
                         "one head width: k_dim %d != v_dim %d" % (Dk, Dv))
    H = 2 * Hk + Hv
    f32 = jnp.float32

    with jax.named_scope("gdn.proj"):
        w = qkvz_weight.reshape(H + Hv, Dk, d)
        qkv = jnp.einsum("bsd,hed->bhse", data, w[:H])
        z = jnp.einsum("bsd,hed->bhse", data, w[H:])
        ba = jnp.einsum("bsd,thd->tbhs", data, ba_weight.reshape(2, Hv, d),
                        preferred_element_type=f32)

    @jax.checkpoint
    def gates(ba, a_log, dt):
        beta = jax.nn.sigmoid(ba[0])
        g = -jnp.exp(a_log.astype(f32)).reshape(1, Hv, 1) \
            * jax.nn.softplus(ba[1] + dt.astype(f32).reshape(1, Hv, 1))
        return g, beta

    with jax.named_scope("gdn.conv"):
        q, k, v = gdn_mix(qkv, conv_weight, Hk)
        g, beta = gates(ba, A_log, dt_bias)

    with jax.named_scope("gdn.scan"):
        o = gated_delta_rule(q, k, v, g, beta)

    @jax.checkpoint
    def gated_norm(o, z, gain):
        o = o.astype(f32)
        inv = lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
        return (gain.astype(f32) * o * inv
                * jax.nn.silu(z.astype(f32))).astype(z.dtype)

    with jax.named_scope("gdn.norm"):
        o = gated_norm(o, z, norm_gamma)

    with jax.named_scope("gdn.proj"):
        return jnp.einsum("bhse,dhe->bsd", o, out_weight.reshape(d, Hv, Dv))


@register("_contrib_KimiDeltaAttention", aliases=("KimiDeltaAttention",))
def kimi_delta_attention(data, q_weight, k_weight, v_weight, conv_weight,
                         fa_weight, fb_weight, A_log, dt_bias, b_weight,
                         ga_weight, gb_weight, gb_bias, norm_gamma, o_weight,
                         *, heads, head_dim, conv_kernel=4, eps=1e-5):
    """The Kimi Delta Attention mixer (arXiv:2510.26692) as one
    sublayer, (B, S, d) -> (B, S, d), on an already normalised stream:
    a delta rule whose forget gate is one value a KEY CHANNEL.

    ``q_weight``, ``k_weight``, ``v_weight`` (heads head_dim, d) give
    every head its own query, key and value; each passes its own causal
    depthwise convolution over the sequence (``conv_weight`` (3 heads
    head_dim, conv_kernel), the rows of ``[q; k; v]`` head-major, tap
    ``conv_kernel - 1`` the position itself) and SiLU; q and k are
    L2-normalised a head, q scaled by ``head_dim ** -0.5``.  The forget
    gate comes through a low-rank pair, ``fa_weight`` (head_dim, d) then
    ``fb_weight`` (heads head_dim, head_dim), no bias: ``g = -exp(A_log)
    softplus(a + dt_bias)`` in float32, one value a head and key channel
    (``A_log`` (heads,), ``dt_bias`` (heads head_dim,)); ``beta =
    sigmoid(data b_weight^T)``, one a head (``b_weight`` (heads, d)).
    The channel-gated delta rule (``ops/delta_rule.py``
    ``gated_delta_rule`` with a gate of rank 4: chunks of 64 tokens,
    state float32) gives ``o``, which is RMS-normalised a head with gain
    ``norm_gamma`` (head_dim,) and gated by ``sigmoid`` of a second
    low-rank pair (``ga_weight`` (head_dim, d), ``gb_weight`` (heads
    head_dim, head_dim), ``gb_bias``), then ``o_weight`` (d, heads
    head_dim).

    Head-major like the other mixers.  Between the passes the layer
    keeps its input, the three projections' result (``[q; k; v]`` before
    the convolution), the two 128-wide low-rank rows, the scan's result
    and, on the kernels' path, the state every run of chunks starts
    from; the convolution (:func:`gdn_mix`, as Gated DeltaNet's), the
    gates, so the scan's inputs q, k, v, g and beta, the norm and the
    output gate's second map are made again in the backward pass.
    Scopes: ``kda.proj``, ``kda.conv`` (``pallas.gdn_mix`` inside it),
    ``kda.gate``, ``kda.scan`` (``pallas.kda_delta_rule`` inside it),
    ``kda.norm``."""
    from .delta_rule import RUN_STARTS, gated_delta_rule
    B, S, d = data.shape
    H, D = int(heads), int(head_dim)
    f32 = jnp.float32

    with jax.named_scope("kda.proj"):
        w = jnp.concatenate([q_weight, k_weight, v_weight]).reshape(
            3 * H, D, d)
        qkv = jnp.einsum("bsd,hed->bhse", data, w)
        low_rank = jnp.einsum(
            "bsd,ted->tbse", data, jnp.stack([fa_weight, ga_weight]))
        b = jnp.einsum("bsd,hd->bhs", data, b_weight,
                       preferred_element_type=f32)

    # convolution, gates and scan as one rematerialized stretch: of all
    # they make the backward pass finds only the state every run of
    # chunks started from (the scan's kernels name it), and makes q, k,
    # v, g and beta again from ``qkv`` and the low-rank rows
    @_functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(RUN_STARTS))
    def scanned(qkv, wc, fa, b, wb, a_log, dt):
        with jax.named_scope("kda.conv"):
            q, k, v = gdn_mix(qkv, wc, H)
        with jax.named_scope("kda.gate"):
            a = jnp.einsum("bse,hfe->bhsf", fa, wb.reshape(H, D, D),
                           preferred_element_type=f32)
            g = -jnp.exp(a_log.astype(f32)).reshape(1, H, 1, 1) \
                * jax.nn.softplus(a + dt.astype(f32).reshape(1, H, 1, D))
            beta = jax.nn.sigmoid(b)
        with jax.named_scope("kda.scan"):
            return gated_delta_rule(q, k, v, g, beta)

    o = scanned(qkv, conv_weight, low_rank[0], b, fb_weight, A_log, dt_bias)

    @jax.checkpoint
    def gated_norm(o, ga, wb, bias, gain):
        z = jnp.einsum("bse,hfe->bhsf", ga, wb.reshape(H, D, D),
                       preferred_element_type=f32) \
            + bias.astype(f32).reshape(1, H, 1, D)
        o = o.astype(f32)
        inv = lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
        return (gain.astype(f32) * o * inv
                * jax.nn.sigmoid(z)).astype(data.dtype)

    with jax.named_scope("kda.norm"):
        o = gated_norm(o, low_rank[1], gb_weight, gb_bias, norm_gamma)

    with jax.named_scope("kda.proj"):
        return jnp.einsum("bhse,dhe->bsd", o, o_weight.reshape(d, H, D))


# ----------------------------------------------------------------------
# Paged-KV-cache attention (mx.decode — docs/DECODE.md)
#
# The generative-serving pair of FusedCausalSelfAttention: the KV cache
# lives in fixed-size device blocks ((num_blocks, block_size, H, D) per
# layer) and each sequence addresses it through a runtime block table —
# PagedAttention (vLLM, SOSP '23) expressed as XLA gather/scatter so
# one compiled program serves every ragged batch of sequences with
# zero retraces.  Block tables / positions / lengths are ARRAY inputs,
# never static attrs, so nothing about sequence state is baked into
# the trace.  Out-of-range scatter indices (padded slots, positions
# past a prompt) use ``num_blocks*block_size`` — one past the end —
# with mode='drop': negative sentinels would WRAP to the last cache
# row and corrupt a live block.
# ----------------------------------------------------------------------
def _paged_qkv_weights(qkv_weight, qkv_bias, d, H, D):
    """Reference FullyConnected layout ((3d, d) packed rows) viewed
    head-major so checkpoints from the training graph load unchanged."""
    return qkv_weight.reshape(3, H, D, d), qkv_bias.reshape(3, H, D)


@register("_contrib_PagedDecodeAttention",
          aliases=("PagedDecodeAttention",), num_outputs=3)
def paged_decode_attention(data, qkv_weight, qkv_bias, proj_weight,
                           proj_bias, k_cache, v_cache, block_table,
                           positions, *, num_heads, scale=None):
    """One autoregressive decode step over a paged KV cache.

    data (C, 1, d): current-token hidden states for C fixed batch
    slots; k_cache/v_cache (num_blocks, block_size, H, D); block_table
    (C, M) block ids per slot; positions (C, 1) the 0-based position of
    the current token (< 0 marks an inactive/padded slot — its write is
    dropped and its output is garbage the engine masks).  Outputs
    (attn_out (C, 1, d), new_k_cache, new_v_cache): the current token's
    K/V are scattered into the cache first, then attention runs over
    the gathered context 0..position.  Weight names/layouts match
    FusedCausalSelfAttention, so the training checkpoint serves decode
    with no conversion."""
    C, _, d = data.shape
    H = int(num_heads)
    if d % H:
        raise ValueError("d_model %d not divisible by num_heads %d" % (d, H))
    D = d // H
    sc = (1.0 / D ** 0.5) if scale is None else float(scale)

    x = data.reshape(C, d)
    Wqkv, bqkv = _paged_qkv_weights(qkv_weight, qkv_bias, d, H, D)
    q = jnp.einsum("cd,hed->che", x, Wqkv[0]) + bqkv[0]
    k = jnp.einsum("cd,hed->che", x, Wqkv[1]) + bqkv[1]
    v = jnp.einsum("cd,hed->che", x, Wqkv[2]) + bqkv[2]

    nb, bs = k_cache.shape[0], k_cache.shape[1]
    kf = k_cache.reshape(nb * bs, H, D)
    vf = v_cache.reshape(nb * bs, H, D)
    pos = positions.reshape(C).astype(jnp.int32)
    table = block_table.astype(jnp.int32)              # (C, M)
    M = table.shape[1]

    # scatter this token's K/V: flat row = table[pos // bs] * bs + pos % bs
    blk = jnp.clip(pos // bs, 0, M - 1)
    row_blk = jnp.take_along_axis(table, blk[:, None], axis=1)[:, 0]
    widx = jnp.where(pos >= 0, row_blk * bs + pos % bs, nb * bs)
    kf = kf.at[widx].set(k.astype(kf.dtype), mode="drop")
    vf = vf.at[widx].set(v.astype(vf.dtype), mode="drop")

    from ..pallas import paged_decode_attend, use_paged_pallas
    kernel = use_paged_pallas()
    if kernel:
        # Pallas kernel (docs/KERNELS.md): walks the block table inside
        # the kernel — one (bs, H, D) K/V block in VMEM at a time with
        # an online softmax, so the (C, M*bs, H, D) gathered-context
        # temp of the XLA path below never exists.  Inactive slots
        # (pos < 0) come back as exact zeros instead of the XLA path's
        # masked garbage; the engine masks both.
        o = paged_decode_attend(q, kf.reshape(k_cache.shape),
                                vf.reshape(v_cache.shape), table, pos,
                                scale=sc,
                                interpret=kernel == "interpret")
    else:
        # gather the whole addressable context per slot and mask
        # causally; padded table entries read block 0 but sit behind
        # the mask
        ctx = M * bs
        j = jnp.arange(ctx)
        ridx = table[:, j // bs] * bs + (j % bs)       # (C, ctx)
        kctx = jnp.take(kf, ridx, axis=0, mode="clip")  # (C, ctx, H, D)
        vctx = jnp.take(vf, ridx, axis=0, mode="clip")
        s = jnp.einsum("che,cjhe->chj", q, kctx) * sc
        mask = j[None, None, :] <= jnp.maximum(pos, 0)[:, None, None]
        s = jnp.where(mask, s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("chj,cjhe->che", p, vctx)
    out = jnp.einsum("che,dhe->cd", o,
                     proj_weight.reshape(d, H, D)) + proj_bias
    return (out.reshape(C, 1, d), kf.reshape(k_cache.shape),
            vf.reshape(v_cache.shape))


@register("_contrib_PagedPrefillAttention",
          aliases=("PagedPrefillAttention",), num_outputs=3)
def paged_prefill_attention(data, qkv_weight, qkv_bias, proj_weight,
                            proj_bias, k_cache, v_cache, block_table,
                            lengths, *, num_heads, scale=None):
    """Prompt-phase attention that also populates the paged KV cache.

    data (B, S, d) is the padded prompt batch; lengths (B,) the real
    prompt lengths; block_table (B, M) the destination blocks.  The
    attention itself is the same head-major causal MHA as
    FusedCausalSelfAttention (``_causal_attention_core``); additionally
    K/V rows for
    positions < length are scattered into the cache so decode can
    continue the sequence.  Outputs (hidden (B, S, d), new_k_cache,
    new_v_cache)."""
    B, S, d = data.shape
    H = int(num_heads)
    if d % H:
        raise ValueError("d_model %d not divisible by num_heads %d" % (d, H))
    D = d // H
    sc = (1.0 / D ** 0.5) if scale is None else float(scale)

    from ..pallas import paged_prefill_attend, use_paged_pallas
    kernel = use_paged_pallas()
    if kernel:
        # Pallas kernel (docs/KERNELS.md): causal attention per query
        # block with the cache scatter FUSED into the same kernel —
        # K/V rows land in their table-addressed cache blocks as they
        # are produced, so the separate (B*S)-row XLA scatter below
        # (and its index math) never runs.  Projections emit/consume
        # the kernel's seq-major (B, S, H, D) layout directly.
        Wqkv, bqkv = _paged_qkv_weights(qkv_weight, qkv_bias, d, H, D)
        q = jnp.einsum("bsd,hed->bshe", data, Wqkv[0]) + bqkv[0]
        k = jnp.einsum("bsd,hed->bshe", data, Wqkv[1]) + bqkv[1]
        v = jnp.einsum("bsd,hed->bshe", data, Wqkv[2]) + bqkv[2]
        o, kc, vc = paged_prefill_attend(
            q, k, v, k_cache, v_cache, block_table.astype(jnp.int32),
            lengths.reshape(B).astype(jnp.int32), scale=sc,
            interpret=kernel == "interpret")
        out = jnp.einsum("bshe,dhe->bsd", o,
                         proj_weight.reshape(d, H, D)) + proj_bias
        return out, kc, vc

    Wqkv = qkv_weight.reshape(3, H, D, d)
    bqkv = qkv_bias.reshape(3, H, 1, D)
    fold, attend = _causal_attention_core(S, D, data.dtype, sc)
    proj = _functools.partial(_project_heads, "bsd,hed->bhse", data)
    q = proj(Wqkv[0], bqkv[0], fold)
    k = proj(Wqkv[1], bqkv[1])
    v = proj(Wqkv[2], bqkv[2])
    o = attend(q, k, v)
    out = jnp.einsum("bhse,dhe->bsd", o,
                     proj_weight.reshape(d, H, D)) + proj_bias

    nb, bs = k_cache.shape[0], k_cache.shape[1]
    kf = k_cache.reshape(nb * bs, H, D)
    vf = v_cache.reshape(nb * bs, H, D)
    table = block_table.astype(jnp.int32)              # (B, M)
    L = lengths.reshape(B).astype(jnp.int32)
    M = table.shape[1]
    jpos = jnp.arange(S)
    blk = jnp.clip(jpos // bs, 0, M - 1)
    base = jnp.take_along_axis(table, jnp.broadcast_to(blk[None], (B, S)),
                               axis=1)
    widx = jnp.where(jpos[None, :] < L[:, None],
                     base * bs + jpos % bs, nb * bs)   # OOB sentinel
    kw = k.transpose(0, 2, 1, 3).reshape(B * S, H, D)
    vw = v.transpose(0, 2, 1, 3).reshape(B * S, H, D)
    kf = kf.at[widx.reshape(B * S)].set(kw.astype(kf.dtype), mode="drop")
    vf = vf.at[widx.reshape(B * S)].set(vw.astype(vf.dtype), mode="drop")
    return out, kf.reshape(k_cache.shape), vf.reshape(v_cache.shape)


@register("_contrib_PagedChunkPrefillAttention",
          aliases=("PagedChunkPrefillAttention",), num_outputs=3)
def paged_chunk_prefill_attention(data, qkv_weight, qkv_bias,
                                  proj_weight, proj_bias, k_cache,
                                  v_cache, block_table, start, lengths,
                                  *, num_heads, scale=None):
    """Chunked prompt-phase attention over an EXISTING cache prefix.

    The chunked-prefill variant of PagedPrefillAttention (Sarathi-Serve
    /Orca-style stall-free scheduling, docs/DECODE.md): data (B, K, d)
    holds one K-token CHUNK per row whose tokens sit at absolute
    positions ``[start[b], start[b] + lengths[b])`` of the sequence;
    earlier chunks' K/V already live in the paged cache, addressed by
    ``block_table (B, M)``.  The chunk's K/V rows are scattered first,
    then every chunk query attends causally against the FULL context so
    far (prior chunks fully visible, in-chunk keys causally).  Rows
    past ``lengths[b]`` are padding (scatter dropped, output garbage
    the engine masks); ``lengths[b] == 0`` makes row b a no-op.
    Outputs (hidden (B, K, d), new_k_cache, new_v_cache).  Weight
    names/layouts match FusedCausalSelfAttention, so the training
    checkpoint serves chunked prefill with no conversion."""
    B, K, d = data.shape
    H = int(num_heads)
    if d % H:
        raise ValueError("d_model %d not divisible by num_heads %d" % (d, H))
    D = d // H
    sc = (1.0 / D ** 0.5) if scale is None else float(scale)
    st = start.reshape(B).astype(jnp.int32)
    L = lengths.reshape(B).astype(jnp.int32)
    table = block_table.astype(jnp.int32)              # (B, M)
    M = table.shape[1]
    nb, bs = k_cache.shape[0], k_cache.shape[1]

    from ..pallas import paged_chunk_prefill_attend, use_paged_pallas
    kernel = use_paged_pallas()
    if kernel:
        # Pallas kernel (docs/KERNELS.md): streams the context cache
        # block by block with an online softmax, merging the chunk's
        # own K/V into each block in-kernel and writing it back through
        # the aliased caches — the (B, M*bs, H, D) gathered-context
        # temp of the XLA path below never exists.
        Wqkv, bqkv = _paged_qkv_weights(qkv_weight, qkv_bias, d, H, D)
        q = jnp.einsum("bsd,hed->bshe", data, Wqkv[0]) + bqkv[0]
        k = jnp.einsum("bsd,hed->bshe", data, Wqkv[1]) + bqkv[1]
        v = jnp.einsum("bsd,hed->bshe", data, Wqkv[2]) + bqkv[2]
        o, kc, vc = paged_chunk_prefill_attend(
            q, k, v, k_cache, v_cache, table, st, L, scale=sc,
            interpret=kernel == "interpret")
        out = jnp.einsum("bshe,dhe->bsd", o,
                         proj_weight.reshape(d, H, D)) + proj_bias
        return out, kc, vc

    Wqkv = qkv_weight.reshape(3, H, D, d)
    bqkv = qkv_bias.reshape(3, H, 1, D)
    q = jnp.einsum("bsd,hed->bhse", data, Wqkv[0]) + bqkv[0]
    k = jnp.einsum("bsd,hed->bhse", data, Wqkv[1]) + bqkv[1]
    v = jnp.einsum("bsd,hed->bhse", data, Wqkv[2]) + bqkv[2]

    # scatter the chunk's rows at their ABSOLUTE positions first, so
    # the gather below reads a cache that already contains them (the
    # in-chunk causal mask does the rest)
    kf = k_cache.reshape(nb * bs, H, D)
    vf = v_cache.reshape(nb * bs, H, D)
    j = jnp.arange(K)
    apos = st[:, None] + j[None, :]                    # (B, K) absolute
    blk = jnp.clip(apos // bs, 0, M - 1)
    base = jnp.take_along_axis(table, blk, axis=1)
    widx = jnp.where(j[None, :] < L[:, None],
                     base * bs + apos % bs, nb * bs)   # OOB sentinel
    kw = k.transpose(0, 2, 1, 3).reshape(B * K, H, D)
    vw = v.transpose(0, 2, 1, 3).reshape(B * K, H, D)
    kf = kf.at[widx.reshape(B * K)].set(kw.astype(kf.dtype), mode="drop")
    vf = vf.at[widx.reshape(B * K)].set(vw.astype(vf.dtype), mode="drop")

    # gather the whole addressable context per row and mask causally
    # against absolute positions; padded table entries read block 0 but
    # sit behind the mask
    ctx = M * bs
    jk = jnp.arange(ctx)
    ridx = table[:, jk // bs] * bs + (jk % bs)         # (B, ctx)
    kctx = jnp.take(kf, ridx, axis=0, mode="clip")     # (B, ctx, H, D)
    vctx = jnp.take(vf, ridx, axis=0, mode="clip")
    s = jnp.einsum("bhqe,bjhe->bhqj", q, kctx) * sc
    mask = jk[None, :] <= apos[:, :, None]             # (B, K, ctx)
    s = jnp.where(mask[:, None], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqj,bjhe->bhqe", p, vctx)
    out = jnp.einsum("bhse,dhe->bsd", o,
                     proj_weight.reshape(d, H, D)) + proj_bias
    return out, kf.reshape(k_cache.shape), vf.reshape(v_cache.shape)


@register("_contrib_GatherTimestep", aliases=("GatherTimestep",))
def gather_timestep(data, index):
    """data (B, S, d), index (B,) or (B, 1) -> (B, d): data[b, index[b]]
    with the index clipped into [0, S).  Used by the prefill graph to
    read the last REAL token's hidden state (index = length - 1) so the
    lm_head matmul runs on one row, not the whole padded sequence."""
    B, S = data.shape[0], data.shape[1]
    idx = jnp.clip(index.reshape(B).astype(jnp.int32), 0, S - 1)
    idx3 = jnp.broadcast_to(idx[:, None, None], (B, 1, data.shape[2]))
    return jnp.take_along_axis(data, idx3, axis=1)[:, 0]


@register("_contrib_SwitchMoE", aliases=("SwitchMoE",), num_outputs=2,
          num_visible_outputs=2)
def switch_moe_op(data, router_weight, expert_up_weight, expert_up_bias,
                  expert_down_weight, expert_down_bias, *, num_experts,
                  num_hidden, k=1, capacity_factor=1.25):
    """Switch/top-k Mixture-of-Experts FFN as a graph operator (new
    TPU-native capability — the reference predates MoE, SURVEY.md
    §2.3). data (..., d_model) routes per-token to ``num_experts``
    expert FFNs (router_weight (d, E); expert-stacked up (E, d, h) /
    down (E, h, d) weights with (E, h) / (E, d) biases — the _weight/
    _bias name suffixes keep the framework's init and weight-decay
    conventions). Outputs: (y, aux_loss) — aux_loss is the Switch
    load-balancing loss, typically wired through ``MakeLoss`` with a
    small coefficient. Expert parallelism: shard the leading E axis of
    the expert-stacked params over an ``ep`` mesh axis (TrainStep
    tp_rule or parallel.moe.switch_moe directly). NOTE: default Xavier
    init misreads the 3-D expert stacks' fans (it treats trailing dims
    as conv extents); the transformer builder attaches per-variable
    Normal inits sized to the per-expert fan."""
    from ..parallel.moe import switch_moe as _switch

    d = data.shape[-1]
    tokens = data.reshape(-1, d)
    params = {"router": router_weight, "w1": expert_up_weight,
              "b1": expert_up_bias, "w2": expert_down_weight,
              "b2": expert_down_bias}
    y, aux = _switch(params, tokens, k=int(k),
                     capacity_factor=float(capacity_factor))
    return y.reshape(data.shape), aux


@register("_contrib_GatedFFN", aliases=("GatedFFN",))
def gated_ffn_op(data, gate_weight, up_weight, down_weight, *, num_hidden):
    """The dense SiLU-gated feed-forward ``down(silu(gate x) * up x)`` of
    width ``num_hidden`` on (..., d) (``parallel.moe.gated_ffn``: what a
    model's dense layers run, and the shared expert of
    ``RoutedExperts``); weights (out, in), no bias."""
    from ..parallel.moe import gated_ffn
    return gated_ffn(data, gate_weight, up_weight, down_weight)


@register("_contrib_RoutedExperts", aliases=("RoutedExperts",),
          num_outputs=lambda attrs: 4 if attrs.get("router") == "sigmoid"
          else 3, num_visible_outputs=3,
          mutate_inputs=(("router_bias", 3),))
def routed_experts(data, router_in_weight=None, router_norm_gamma=None,
                   router_fc1_weight=None, router_fc2_weight=None,
                   router_out_weight=None, gate_weight=None, up_weight=None,
                   down_weight=None, router_state=None, router_carry=None,
                   router_weight=None, shared_gate_weight=None,
                   shared_up_weight=None, shared_down_weight=None,
                   shared_sg_weight=None, router_bias=None, router_data=None,
                   *, num_experts, held_first=0, held_count=None, num_hidden,
                   router_hidden=0, carry_in=True, router="zaya", top_k=1,
                   shared_hidden=0, shared_gate=True, route_scale=1.0,
                   router_stream=False, act="silu", rows_slack=1.25):
    """The dropless expert sublayer of a chip that holds ``held_count``
    of ``num_experts`` experts (``held_first`` onwards), on an already
    normalised stream (..., d).  A token's ``top_k`` experts are chosen
    among ALL experts in float32; the (token, choice) pairs whose expert
    is held here are sorted and run through three grouped matrix
    products (a gated FFN of width ``num_hidden``, ``down(act(gate x) *
    up x)`` with ``act`` ``silu`` or ``relu``; stacks (held, out, in)),
    each weighted by what the router gives it.  A pair whose
    expert lives on another chip adds 0.  No capacity, no dropped token
    (``SwitchMoE`` keeps its capacity semantics).  With ``top_k > 1`` a
    token's weighted rows are added in token order, in float32 (the
    combine; the gradient of the gather that fetched the token's rows is
    the same sum): beside the kernels of the grouped products the
    token-ordered sum of ``pallas/token_sum.py``, scope
    ``pallas.token_sum``, else XLA's ``scatter-add``.  ``rows_slack``
    (``top_k > 1``): the sorted rows' buffer holds that many times the
    pairs an even routing sends here; a step with more runs them in
    slabs (``parallel.moe._row_buckets``: five quarters, or more where
    a model's rows choose alike).

    ``router="zaya"`` (top-1 only): the state ``r = h W_in + carry *
    r_prev`` takes the previous layer's state (``router_state`` and the
    scalar ``router_carry``, both absent with ``carry_in=False``: the
    first layer), then a 3-layer GELU MLP of width ``router_hidden`` and
    a softmax; the token's weight is its expert's probability.
    ``router="linear"``: ``softmax(h W^T)`` with ``router_weight``
    (num_experts, d), the ``top_k`` best, weights normalised over all
    ``top_k`` whether held here or not; with ``router_stream=True`` it
    reads the extra input ``router_data`` (the leading shape of ``data``:
    another place of the residual stream, such as the layer's input
    before attention) while the experts read ``data``, and the router's
    gradient goes to ``router_data`` and never to ``data`` (the flag
    says the input is there: a graph tells an input's presence from the
    attributes).  ``router="sigmoid"``:
    ``sigmoid(h W^T)`` with the same ``router_weight``; the ``top_k``
    experts with the largest score PLUS ``router_bias`` (num_experts,),
    an auxiliary state that steers the choice, takes no gradient and
    leaves the step as it came; the weights are the scores without it,
    normalised over all ``top_k``, times ``route_scale``
    (``parallel.moe.sigmoid_router``).  With ``shared_hidden`` one
    shared expert (the dense gated FFN ``parallel.moe.gated_ffn``) runs over
    every token behind ``sigmoid(h w_sg)`` (``shared_sg_weight`` (1, d);
    with ``shared_gate=False`` there is no such input and no gate) and
    joins the result, whole on every chip.

    Outputs: ``y`` like ``data``; the router's second output: its state
    (..., router_hidden) float32 for the next layer (zaya), the chosen
    experts (..., top_k) int32 (linear, sigmoid); int32 (num_experts,)
    (token, choice) pairs an expert, over all experts; with the sigmoid
    router a fourth, hidden: the bias, for the executor's aux-state
    update.  Scopes: ``moe.router``,
    ``moe.dispatch``, ``moe.experts``, ``moe.combine``, ``moe.shared``."""
    from ..parallel import moe
    lead, d = data.shape[:-1], data.shape[-1]
    E, k = int(num_experts), int(top_k)
    held = E - int(held_first) if held_count is None else int(held_count)
    if gate_weight.shape[0] != held:
        raise ValueError("expert stacks hold %d experts, held_count=%d"
                         % (gate_weight.shape[0], held))
    x = data.reshape(-1, d)
    if (router_stream or act != "silu") and router != "linear":
        raise ValueError("router_stream and act belong to router='linear', "
                         "not %r" % (router,))
    if router == "zaya":
        if k != 1:
            raise ValueError("the zaya router is top-1, top_k=%d" % k)
        R = int(router_hidden)
        with jax.named_scope("moe.router"):
            r, prob = moe.zaya_router(
                x, router_state.reshape(-1, R) if carry_in else None,
                router_in_weight, router_carry, router_norm_gamma,
                router_fc1_weight, router_fc2_weight, router_out_weight)
        y, counts = moe.dropless_top1_experts(
            x, prob, gate_weight, up_weight, down_weight, int(held_first))
        second = r.reshape(lead + (R,))
    elif router in ("linear", "sigmoid"):
        with jax.named_scope("moe.router"):
            chosen, weights = moe.linear_router(
                router_data.reshape(-1, router_data.shape[-1])
                if router_stream else x, router_weight, k) \
                if router == "linear" else moe.sigmoid_router(
                    x, router_weight, router_bias, k, float(route_scale))
        y, counts = moe.dropless_topk_experts(
            x, chosen, weights, gate_weight, up_weight, down_weight, E,
            int(held_first), act=act, slack=float(rows_slack))
        second = lax.stop_gradient(chosen).reshape(lead + (k,))
    else:
        raise ValueError("router=%r (zaya, linear or sigmoid)" % (router,))
    if int(shared_hidden):
        with jax.named_scope("moe.shared"):
            shared = moe.gated_ffn(x, shared_gate_weight, shared_up_weight,
                                   shared_down_weight).astype(jnp.float32)
            if shared_gate:
                shared = jax.nn.sigmoid(jnp.einsum(
                    "nd,od->no", x, shared_sg_weight,
                    preferred_element_type=jnp.float32)) * shared
            y = (y.astype(jnp.float32) + shared).astype(data.dtype)
    outs = (y.reshape(data.shape), second, lax.stop_gradient(counts))
    if router == "sigmoid":
        outs += (lax.stop_gradient(router_bias),)
    return outs


# ----------------------------------------------------------------------
# Embedding
# ----------------------------------------------------------------------
@register("Embedding")
def embedding(data, weight, *, input_dim, output_dim, dtype="float32",
              sparse_grad=False):
    """Row gather (ref src/operator/tensor/indexing_op.cc Embedding).
    TPU: lowers to a gather HLO; one-hot matmul would also hit the MXU but
    gather wins at vocab scale. ``sparse_grad=True`` is accepted for API
    parity; inside a compiled graph the weight gradient is a dense
    scatter-add (XLA's own efficient form) — to get a row_sparse gradient
    for lazy optimizer updates, use ``nd.sparse.cast_storage(grad,
    'row_sparse')`` or Parameter(grad_stype='row_sparse') in gluon."""
    idx = data.astype("int32")
    return jnp.take(weight, idx, axis=0, mode="clip")


@register("_contrib_ShardedEmbedding")
def sharded_embedding(data, weight, *, input_dim, output_dim,
                      dtype="float32", sparse_grad=True):
    """Symbol twin of embedding.ShardedEmbedding: the same gather, but
    out-of-range ids yield zero rows via the sentinel fill instead of
    Embedding's clamp — ids >= input_dim must not silently train row
    input_dim-1. Row sharding follows the WEIGHT's placement: a
    concrete table already placed on the local mesh (place_table) keeps
    its row sharding re-asserted here; inside an executor trace the
    graph's bind-device commitment governs (the executor is a
    single-device program — forcing the mesh onto its dev0-committed
    args would not compile), and GSPMD propagates any argument sharding
    on mesh-compiled callers."""
    from ..embedding import sharding as _esh
    mesh = _esh.local_mesh()
    if (mesh is not None and weight.shape[0] % mesh.devices.size == 0
            and not isinstance(weight, jax.core.Tracer)
            and isinstance(weight, jax.Array)
            and len(weight.sharding.device_set) > 1):
        weight = jax.lax.with_sharding_constraint(
            weight, _esh.table_sharding(mesh))
    idx = data.astype("int32")
    oob = jnp.logical_or(idx < 0, idx >= int(input_dim))
    idx = jnp.where(oob, int(input_dim), idx)
    return jnp.take(weight, idx, axis=0, mode="fill", fill_value=0)


@register("Correlation")
def correlation(data1, data2, *, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    """FlowNet correlation layer (ref src/operator/correlation-inl.h):
    out[b, (dy,dx), y, x] = mean over the k×k×C patch of
    data1(center) · data2(center + (dy,dx)·stride2). The CUDA kernel's
    per-displacement loop becomes one static Python loop over the
    (2r+1)² displacements, each a shifted elementwise product + box-sum
    (reduce_window) that XLA fuses; gradients ride autodiff."""
    import numpy as _np
    B, C, H, W = data1.shape
    k = int(kernel_size)
    kr = (k - 1) // 2
    d = int(max_displacement)
    s1, s2 = int(stride1), int(stride2)
    pad = int(pad_size)
    ngr = d // s2                     # neighborhood grid radius
    gw = 2 * ngr + 1
    border = d + kr
    ph, pw = H + 2 * pad, W + 2 * pad
    top_h = max(int(_np.ceil((ph - 2 * border) / s1)), 1)
    top_w = max(int(_np.ceil((pw - 2 * border) / s1)), 1)
    sumelems = k * k * C

    p1 = jnp.pad(data1, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    # extra max_displacement halo on data2 so every shift is a slice
    p2 = jnp.pad(data2, ((0, 0), (0, 0), (pad + d, pad + d),
                         (pad + d, pad + d)))
    outs = []
    for dy in range(-ngr, ngr + 1):
        for dx in range(-ngr, ngr + 1):
            oy, ox = dy * s2, dx * s2
            p2s = lax.dynamic_slice(
                p2, (0, 0, d + oy, d + ox), (B, C, ph, pw))
            prod = (p1 * p2s) if is_multiply else jnp.abs(p1 - p2s)
            csum = prod.sum(axis=1)               # (B, ph, pw)
            patch = lax.reduce_window(
                csum, 0.0, lax.add, (1, k, k), (1, 1, 1),
                "VALID")                          # (B, ph-k+1, pw-k+1)
            # center (y,x) of output cell o: y = o*s1 + border; its
            # k×k window starts at y-kr -> patch index o*s1 + d
            patch = jnp.pad(patch, ((0, 0), (0, s1), (0, s1)))
            outs.append(patch[:, d:d + top_h * s1:s1,
                              d:d + top_w * s1:s1])
    out = jnp.stack(outs, axis=1)                 # (B, gw*gw, th, tw)
    return (out / sumelems).astype(data1.dtype)


@register("BilinearSampler")
def bilinear_sampler(data, grid):
    """Bilinear sampling (ref src/operator/bilinear_sampler.cc). grid in
    [-1,1] with shape (n, 2, h, w)."""
    n, c, hin, win = data.shape
    gx = (grid[:, 0] + 1.0) * (win - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (hin - 1) / 2.0
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(yi, xi):
        yi = jnp.clip(yi.astype("int32"), 0, hin - 1)
        xi = jnp.clip(xi.astype("int32"), 0, win - 1)
        bidx = jnp.arange(n).reshape(n, 1, 1)
        return data[bidx, :, yi, xi].transpose(0, 3, 1, 2)

    v00 = gather(y0, x0)
    v01 = gather(y0, x0 + 1)
    v10 = gather(y0 + 1, x0)
    v11 = gather(y0 + 1, x0 + 1)
    wx = wx[:, None]
    wy = wy[:, None]
    return (v00 * (1 - wx) * (1 - wy) + v01 * wx * (1 - wy)
            + v10 * (1 - wx) * wy + v11 * wx * wy)
