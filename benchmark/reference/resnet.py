"""Plain reference of the ImageNet bottleneck ResNet the repo's zoo
builds (``models.get_symbol('resnet')``: version 2, pre-activation, He
et al. 2016, by default; version 1, He et al. 2015, with ``version=1``),
channel-last.

Straightforward ``jax.numpy`` / ``lax.conv_general_dilated`` in float32
at ``highest`` precision: training-mode BatchNorm (batch statistics,
biased variance, eps 2e-5, momentum 0.9), no fusion, no kernel.  It
imports nothing of ``mxnet_tpu``; parameters come from the seed by the
names a checkpoint of the zoo's symbol uses.  Stages and, inside them,
residual units are rematerialised going back, so that 256 float32 images
take no more of the chip than the program's own step.

``precision="fp8"`` computes every convolution and the classifier the
way fp8 training does (see reference/gpt2.py): the control.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from reference.gpt2 import (fp8_round, leaf_key, mm_fp8,  # noqa: F401
                            seed_key)  # seed_key, leaf_key: the drivers' entry

EPS = 2e-5
BN_MOM = 0.9
HI = lax.Precision.HIGHEST
UNITS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
FILTERS = (64, 256, 512, 1024, 2048)


# ----------------------------------------------------------------------
# structure and parameters by name
# ----------------------------------------------------------------------
def _units(kw):
    """[(name, cin, cout, stride, dim_match)] of every residual unit."""
    out, cin = [], FILTERS[0]
    for stage, n in enumerate(UNITS[int(kw.get("num_layers", 50))]):
        cout = FILTERS[stage + 1]
        for u in range(n):
            stride = 2 if (u == 0 and stage > 0) else 1
            out.append(("stage%d_unit%d" % (stage + 1, u + 1), cin, cout,
                        stride, u > 0))
            cin = cout
    return out


def _bn_names(kw):
    """[(bn name, channels)] in graph order."""
    v2 = int(kw.get("version", 2)) == 2
    out = [("bn_data", 3), ("bn0", FILTERS[0])]
    for name, cin, cout, _, match in _units(kw):
        mid = cout // 4
        chans = (cin, mid, mid) if v2 else (mid, mid, cout)
        out += [("%s_bn%d" % (name, i + 1), c) for i, c in enumerate(chans)]
        if not v2 and not match:
            out.append((name + "_sc_bn", cout))
    if v2:
        out.append(("bn1", FILTERS[-1]))
    return out


def param_specs(kw):
    """[(name, shape)]; conv weights are (out, kh, kw, in): the zoo's
    channel-last layout."""
    specs = [("conv0_weight", (FILTERS[0], 7, 7, 3))]
    for name, cin, cout, _, match in _units(kw):
        mid = cout // 4
        specs += [(name + "_conv1_weight", (mid, 1, 1, cin)),
                  (name + "_conv2_weight", (mid, 3, 3, mid)),
                  (name + "_conv3_weight", (cout, 1, 1, mid))]
        if not match:
            specs.append((name + "_sc_weight", (cout, 1, 1, cin)))
    for bn, c in _bn_names(kw):
        specs += [(bn + "_gamma", (c,)), (bn + "_beta", (c,))]
    classes = int(kw.get("num_classes", 1000))
    return specs + [("fc1_weight", (classes, FILTERS[-1])),
                    ("fc1_bias", (classes,))]


def init_aux(kw):
    """{name: float32 array} of the BatchNorm moving statistics."""
    out = {}
    for bn, c in _bn_names(kw):
        out[bn + "_moving_mean"] = jnp.zeros((c,), jnp.float32)
        out[bn + "_moving_var"] = jnp.ones((c,), jnp.float32)
    return out


def leaf_kind(name):
    if name.endswith("_gamma"):
        return "ones"
    if name.endswith(("_bias", "_beta")):
        return "zeros"
    return "he"


def leaf_value(k, kind, shape):
    """Convolutions and the classifier normal(0, sqrt(2 / fan_in)) (the
    zoo's Xavier(gaussian, in, 2)), rounded to bfloat16, the type they
    are trained in; gains 1; shifts and the bias 0.  Float32."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    fan_in = 1
    for s in shape[1:]:
        fan_in *= s
    w = jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_in) ** 0.5
    # reduce_precision, not a cast to bfloat16 and back: inside a compiled
    # program the TPU's compiler keeps the excess precision of such a
    # pair, and the "rounded" float32 leaf then differs from its own
    # bfloat16 copy by a rounding (PERF.md, PR 23)
    return lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def init_leaf(key, name, shape):
    return leaf_value(leaf_key(key, name), leaf_kind(name), shape)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------
def _conv_f32(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "OHWI", "NHWC"), precision=HI,
        preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv_fp8(x, w, stride, pad):
    return _conv_f32(fp8_round(x), fp8_round(w), stride, pad)


def _conv_fp8_fwd(x, w, stride, pad):
    qx, qw = fp8_round(x), fp8_round(w)
    return _conv_f32(qx, qw, stride, pad), (qx, qw)


def _conv_fp8_bwd(stride, pad, res, g):
    _, vjp = jax.vjp(lambda a, b: _conv_f32(a, b, stride, pad), *res)
    return vjp(fp8_round(g, jnp.float8_e5m2, 57344.0))


_conv_fp8.defvjp(_conv_fp8_fwd, _conv_fp8_bwd)


def conv(x, w, stride, pad, precision):
    if precision == "f32":
        return _conv_f32(x, w, stride, pad)
    if precision == "fp8":
        return _conv_fp8(x, w, stride, pad)
    raise ValueError("precision %r (f32 or fp8)" % (precision,))


def batch_norm(x, p, aux, new_aux, name, fix_gamma=False):
    """Training-mode BatchNorm over (N, H, W); records the moving
    statistics' update in ``new_aux``."""
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.maximum(jnp.mean(jnp.square(x), (0, 1, 2))
                      - jnp.square(mean), 0.0)
    new_aux[name + "_moving_mean"] = lax.stop_gradient(
        aux[name + "_moving_mean"] * BN_MOM + mean * (1 - BN_MOM))
    new_aux[name + "_moving_var"] = lax.stop_gradient(
        aux[name + "_moving_var"] * BN_MOM + var * (1 - BN_MOM))
    gamma = 1.0 if fix_gamma else p[name + "_gamma"]
    return (x - mean) * lax.rsqrt(var + EPS) * gamma + p[name + "_beta"]


def unit_v2(x, p, aux, name, stride, match, precision):
    new_aux = {}
    a1 = jax.nn.relu(batch_norm(x, p, aux, new_aux, name + "_bn1"))
    c1 = conv(a1, p[name + "_conv1_weight"], 1, 0, precision)
    a2 = jax.nn.relu(batch_norm(c1, p, aux, new_aux, name + "_bn2"))
    c2 = conv(a2, p[name + "_conv2_weight"], stride, 1, precision)
    a3 = jax.nn.relu(batch_norm(c2, p, aux, new_aux, name + "_bn3"))
    c3 = conv(a3, p[name + "_conv3_weight"], 1, 0, precision)
    sc = x if match else conv(a1, p[name + "_sc_weight"], stride, 0,
                              precision)
    return c3 + sc, new_aux


def unit_v1(x, p, aux, name, stride, match, precision):
    new_aux = {}
    c1 = conv(x, p[name + "_conv1_weight"], stride, 0, precision)
    a1 = jax.nn.relu(batch_norm(c1, p, aux, new_aux, name + "_bn1"))
    c2 = conv(a1, p[name + "_conv2_weight"], 1, 1, precision)
    a2 = jax.nn.relu(batch_norm(c2, p, aux, new_aux, name + "_bn2"))
    c3 = conv(a2, p[name + "_conv3_weight"], 1, 0, precision)
    body = batch_norm(c3, p, aux, new_aux, name + "_bn3")
    if match:
        sc = x
    else:
        sc = batch_norm(conv(x, p[name + "_sc_weight"], stride, 0, precision),
                        p, aux, new_aux, name + "_sc_bn")
    return jax.nn.relu(body + sc), new_aux


def logits(params, aux, images, kw, precision="f32"):
    """(N, H, W, 3) float32 images -> ((N, classes) logits, new aux)."""
    v2 = int(kw.get("version", 2)) == 2
    unit = unit_v2 if v2 else unit_v1
    new_aux = {}
    x = batch_norm(images, params, aux, new_aux, "bn_data", fix_gamma=True)
    x = conv(x, params["conv0_weight"], 2, 3, precision)
    x = jax.nn.relu(batch_norm(x, params, aux, new_aux, "bn0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    # rematerialised twice over, a stage and then a unit at a time, so
    # that 256 float32 images need less memory than the program's step
    def run_stage(x, p, a, units):
        upds = {}
        for name, _, _, stride, match in units:
            x, upd = jax.checkpoint(
                lambda x, p, a, name=name, stride=stride, match=match: unit(
                    x, p, a, name, stride, match, precision))(x, p, a)
            upds.update(upd)
        return x, upds

    units = _units(kw)
    for stage in sorted({u[0].split("_")[0] for u in units}):
        mine = [u for u in units if u[0].startswith(stage + "_")]
        x, upd = jax.checkpoint(
            lambda x, p, a, mine=mine: run_stage(x, p, a, mine))(
                x, params, aux)
        new_aux.update(upd)
    if v2:
        x = jax.nn.relu(batch_norm(x, params, aux, new_aux, "bn1"))
    x = jnp.mean(x, (1, 2))
    w, b = params["fc1_weight"], params["fc1_bias"]
    if precision == "fp8":
        out = mm_fp8("nd,cd->nc", x, w)
    else:
        out = jnp.einsum("nd,cd->nc", x, w, precision=HI)
    return out + b, dict(aux, **new_aux)


def loss(params, aux, images, labels, kw, precision="f32"):
    """SUMMED cross-entropy over the batch: SoftmaxOutput without
    normalisation differentiates the sum, and Module's rescale_grad of
    1/batch makes it the mean."""
    out, new_aux = logits(params, aux, images, kw, precision)
    logp = jax.nn.log_softmax(out, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)
    return -jnp.sum(picked), new_aux


def loss_scale(kw, batch):
    """:func:`loss` over the mean cross-entropy per label: it sums."""
    return float(batch)


# ----------------------------------------------------------------------
# the training cells' inputs
# ----------------------------------------------------------------------
def data_shapes(kw, batch):
    c, h, w = kw["image_shape"]
    return (batch, h, w, c), (batch,)


def make_batch(rng, kw, batch):
    """Float32 images uniform in [-1, 1) and labels, as an image
    iterator hands them over (channel-last)."""
    import numpy as np
    dshape, _ = data_shapes(kw, batch)
    img = rng.random(dshape, dtype=np.float32) * 2.0 - 1.0
    lab = rng.integers(0, int(kw.get("num_classes", 1000)), (batch,))
    return img, lab.astype(np.float32)


def device_batch(data, labels):
    return jnp.asarray(data), jnp.asarray(labels).astype(jnp.int32)


# ----------------------------------------------------------------------
# what the algorithm needs, for the roofline readers (benchmark/counts.py)
# ----------------------------------------------------------------------
def train_flops_per_sample(kw):
    """FLOPs of the forward and backward passes of one image."""
    import counts
    return counts.resnet_train_flops_per_image(
        kw.get("num_layers", 50), kw["image_shape"][1],
        kw.get("num_classes", 1000))
