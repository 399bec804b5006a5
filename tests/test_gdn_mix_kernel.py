"""Gated DeltaNet's convolution, SiLU and L2 norms as Pallas kernels
(``pallas/gdn_mix.py``) in interpret mode: against the ``jax.numpy`` form
they replace on the chip (``ops/nn.py`` ``gdn_conv``), forward and in both
gradients; the rows a block takes from the block before it, in both
directions; the choice between the two paths; and the whole operator with
the choice forced either way.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import nn
from mxnet_tpu.ops.nn import gdn_conv, gdn_mix
from mxnet_tpu.pallas import gdn_mix as kernels
from mxnet_tpu.pallas.dispatch import PALLAS_FALLBACKS, PALLAS_LAUNCHES

D = 128


def _forget_builds():
    # the chunk is read when a kernel is built, and is no part of the
    # jitted calls' keys
    kernels._run_forward.clear_cache()
    kernels._run_backward.clear_cache()


@pytest.fixture
def rows(monkeypatch):
    """Grid steps of 128 rows taken 64 at a time (the chip's are larger),
    so that a short sequence is several steps of several chunks."""
    monkeypatch.setattr(kernels, "_ROWS", 128)
    monkeypatch.setattr(kernels, "_CHUNK", 64)
    _forget_builds()
    yield 128
    _forget_builds()


def _inputs(seed, S, Hk, Hv, K, dtype):
    """``[q; k; v]`` head-major, the depthwise weight, and a cotangent
    for each of q, k, v."""
    H = 2 * Hk + Hv
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    qkv = jax.random.normal(ks[0], (1, H, S, D)).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (H * D, K))).astype(dtype)
    cot = [jax.random.normal(k, (1, n, S, D))
           for k, n in zip(ks[2:], (Hk, Hk, Hv))]
    return qkv, w, cot


def _gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.isfinite(a).all() and np.linalg.norm(b) > 0
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _with_grads(mix, qkv, w, cot):
    def loss(qkv, w):
        return sum(jnp.sum(o.astype(jnp.float32) * c)
                   for o, c in zip(mix(qkv, w), cot))
    return jax.jit(mix)(qkv, w), jax.jit(jax.grad(loss, (0, 1)))(qkv, w)


CASES = {
    # S, k_heads, v_heads, conv_kernel, dtype
    "one-value-head-a-key-head": (256, 1, 1, 4, jnp.float32),
    "two-value-heads-a-key-head": (256, 1, 2, 4, jnp.float32),
    "four-value-heads-a-key-head": (128, 2, 8, 4, jnp.float32),
    "three-blocks": (384, 1, 2, 4, jnp.float32),
    "a-sequence-the-block-does-not-divide": (200, 1, 2, 4, jnp.float32),
    "shorter-than-a-chunk": (40, 1, 1, 4, jnp.float32),
    "two-taps": (256, 1, 2, 2, jnp.float32),
    "eight-taps": (256, 1, 1, 8, jnp.float32),
    "bfloat16": (256, 1, 2, 4, jnp.bfloat16),
    "bfloat16-two-taps-padded": (200, 2, 2, 2, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_the_jax_numpy_form(rows, case):
    """q, k, v and the gradients of ``qkv`` and of the weight.  The
    forward is the same float32 arithmetic in the same order, rounded
    once: float32 operands agree to rounding of the sigmoid, bfloat16
    ones to a last bit here and there.  bfloat16 operands are also held
    against the form computed in float32 from the same operands: the gap
    is the rounding of the outputs."""
    S, Hk, Hv, K, dtype = CASES[case]
    qkv, w, cot = _inputs(3, S, Hk, Hv, K, dtype)
    out, grads = _with_grads(
        lambda a, b: gdn_mix(a, b, Hk, impl="interpret"), qkv, w, cot)
    want, grads0 = _with_grads(
        lambda a, b: gdn_mix(a, b, Hk, impl=False), qkv, w, cot)
    assert [t.shape for t in out] == [(1, n, S, D) for n in (Hk, Hk, Hv)]
    assert all(t.dtype == dtype for t in out)
    assert [(t.shape, t.dtype) for t in grads] == [
        (qkv.shape, dtype), (w.shape, dtype)]
    tol = 2e-6 if dtype == jnp.float32 else 4e-3
    for got, true in zip(out + grads, want + grads0):
        assert _gap(got, true) < tol
    if dtype == jnp.bfloat16:
        f32 = lambda t: t.astype(jnp.float32)
        exact, grads32 = _with_grads(
            lambda a, b: gdn_conv(a, b, Hk), f32(qkv), f32(w), cot)
        for got, true in zip(out + grads, exact + grads32):
            assert _gap(got, true) < 6e-3


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_the_halo_crosses_a_grid_step(rows, direction):
    """384 tokens are three grid steps of 128.  Forward, the first rows
    of a block read the last rows of the block before it: a kernel that
    took zeros there would answer as the form does on the blocks one by
    one, which is far from the form on the sequence.  Backward, a
    cotangent on the first rows of a block alone reaches the last rows of
    the block before it, and the weight's gradient sums over all three."""
    S, Hk, Hv, K = 384, 1, 2, 4
    qkv, w, cot = _inputs(5, S, Hk, Hv, K, jnp.float32)
    kernel = lambda a, b: gdn_mix(a, b, Hk, impl="interpret")
    plain = lambda a, b: gdn_mix(a, b, Hk, impl=False)
    if direction == "forward":
        got, want = jax.jit(kernel)(qkv, w), jax.jit(plain)(qkv, w)
        forgetful = [jnp.concatenate(parts, 2) for parts in zip(*(
            plain(qkv[:, :, at:at + rows], w) for at in range(0, S, rows)))]
        for g, t, f in zip(got, want, forgetful):
            first = (slice(None), slice(None), slice(rows, rows + K - 1))
            assert _gap(f[first], t[first]) > 0.05
            assert _gap(g[first], t[first]) < 2e-6
            assert _gap(g, t) < 2e-6
    else:
        # only rows 128 .. 130 and 256 .. 258 of v receive a cotangent
        mask = jnp.zeros((1, 1, S, 1)).at[:, :, rows:rows + K - 1].set(1.0) \
            .at[:, :, 2 * rows:2 * rows + K - 1].set(1.0)
        cot = [jnp.zeros_like(cot[0]), jnp.zeros_like(cot[1]),
               cot[2] * mask]
        (_, (dx, dw)), (_, (dx0, dw0)) = (
            _with_grads(f, qkv, w, cot) for f in (kernel, plain))
        before = (slice(None), slice(2 * Hk, None),
                  slice(rows - K + 1, rows))
        assert float(jnp.abs(dx0[before]).max()) > 1e-3
        assert _gap(dx[before], dx0[before]) < 2e-6
        assert float(jnp.abs(dx[:, :2 * Hk]).max()) == 0.0
        assert _gap(dx, dx0) < 2e-6 and _gap(dw, dw0) < 2e-6


def test_a_head_is_treated_by_its_index():
    """q heads are unit vectors scaled by ``D ** -0.5``, k heads unit
    vectors, value heads neither: from the head's index against
    ``k_heads`` alone (the same ``qkv`` with another ``k_heads`` gives
    other sections)."""
    qkv, w, _ = _inputs(7, 64, 2, 2, 4, jnp.float32)
    norm = lambda t: np.asarray(jnp.linalg.norm(t, axis=-1))
    q, k, v = gdn_mix(qkv, w, 2, impl="interpret")
    assert q.shape[1] == 2 and k.shape[1] == 2 and v.shape[1] == 2
    np.testing.assert_allclose(norm(q), D ** -0.5, rtol=1e-4)
    np.testing.assert_allclose(norm(k), 1.0, rtol=1e-4)
    assert np.abs(norm(v) - 1.0).min() > 0.05
    q1, k1, v1 = gdn_mix(qkv, w, 1, impl="interpret")
    assert q1.shape[1] == 1 and k1.shape[1] == 1 and v1.shape[1] == 4
    np.testing.assert_allclose(np.asarray(k1[:, 0]) * D ** -0.5,
                               np.asarray(q[:, 1]), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(v1[:, 2:]), np.asarray(v),
                               rtol=1e-6)


def test_the_backward_keeps_qkv_and_the_weight_alone():
    """The ``custom_vjp`` hands its backward the two operands and nothing
    else (what ``jax.checkpoint`` keeps of the ``jax.numpy`` form), and
    three layers of one geometry share one build of each kernel."""
    qkv, w, cot = _inputs(8, 128, 1, 2, 4, jnp.float32)
    out, res = kernels._mix_fwd(qkv, w, 1, True)
    assert len(res) == 2 and res[0] is qkv and res[1] is w
    _forget_builds()
    built = PALLAS_LAUNCHES.labels(kernel="gdn_mix").value

    def three(qkv, w):
        total = 0.0
        for _ in range(3):
            q, k, v = gdn_mix(qkv, w, 1, impl="interpret")
            total = total + jnp.sum(q) + jnp.sum(k) + jnp.sum(v * v)
            qkv = qkv + 0.5
        return total
    jax.jit(jax.grad(three, (0, 1)))(qkv, w)
    assert PALLAS_LAUNCHES.labels(kernel="gdn_mix").value == built + 2


def test_shapes_the_kernels_refuse():
    from mxnet_tpu.pallas.gdn_mix import gdn_mix as run, supported
    qkv, w, _ = _inputs(1, 64, 1, 2, 4, jnp.float32)
    assert supported(qkv, 1, 4)[0] and supported(qkv, 1, 8)[0]
    assert not supported(qkv[..., :64], 1, 4)[0]            # 64 wide
    assert not supported(qkv, 1, 9)[0]                      # nine taps
    assert not supported(qkv, 2, 4)[0]                      # no value head
    assert not supported(qkv.astype(jnp.float16), 1, 4)[0]
    with pytest.raises(ValueError, match="conv_kernel"):
        run(qkv[..., :64], w[:4 * 64], 1, interpret=True)


def test_the_choice_is_counted_and_has_no_knob(monkeypatch):
    """On the CPU ``auto`` is the ``jax.numpy`` form and books
    ``pallas_fallbacks{reason="backend"}``; ``impl="interpret"`` books a
    launch of kernel ``gdn_mix``; in a TPU program partitioned over a
    selected mesh the reason is ``mesh``; shapes the kernels refuse are
    ``gdn-mix-geometry``.  Nothing reads the environment."""
    import mxnet_tpu as mx
    _forget_builds()
    qkv, w, _ = _inputs(2, 64, 1, 2, 4, jnp.float32)
    count = lambda reason: PALLAS_FALLBACKS.labels(reason=reason).value
    launches = PALLAS_LAUNCHES.labels(kernel="gdn_mix")

    before, built = count("backend"), launches.value
    environ = dict(os.environ)
    out = gdn_mix(qkv, w, 1)
    assert count("backend") == before + 1 and launches.value == built
    for got, want in zip(out, gdn_conv(qkv, w, 1)):
        assert _gap(got, want) < 1e-6
    for got, want in zip(gdn_mix(qkv, w, 1, impl="interpret"), out):
        assert _gap(got, want) < 2e-6
    assert launches.value == built + 1 and count("backend") == before + 1
    assert dict(os.environ) == environ

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert nn._gdn_mix_impl(qkv, 1, 4) == "compiled"
    before = count("gdn-mix-geometry")
    assert nn._gdn_mix_impl(qkv[..., :64], 1, 4) is False
    assert nn._gdn_mix_impl(qkv, 1, 9) is False
    assert count("gdn-mix-geometry") == before + 2
    before = count("mesh")
    mx.sharding.set_mesh({"dp": 4, "mp": 2})
    try:
        assert nn._gdn_mix_impl(qkv, 1, 4) is False
    finally:
        mx.sharding.set_mesh(None)
    assert count("mesh") == before + 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_operator_agrees_with_the_choice_forced_either_way(
        monkeypatch, dtype):
    """``_contrib_GatedDeltaNet`` end to end (projections, the mix, the
    scan in ``jax.numpy``, the gated norm, the output projection): the
    same loss and the same seven gradients whether the mix runs as
    kernels or as ``jax.numpy``."""
    B, S, d, Hk, Hv, K = 1, 96, 64, 1, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    normal = lambda k, shape, scale: (
        scale * jax.random.normal(k, shape)).astype(dtype)
    args = (normal(ks[0], (B, S, d), 1.0),
            normal(ks[1], ((2 * Hk + 2 * Hv) * D, d), d ** -0.5),
            normal(ks[2], (2 * Hv, d), d ** -0.5),
            normal(ks[3], ((2 * Hk + Hv) * D, K), 0.5),
            jnp.log(jax.random.uniform(ks[4], (Hv,), minval=1.0,
                                       maxval=4.0)),
            jnp.ones((Hv,)), jnp.ones((D,), dtype),
            normal(ks[5], (d, Hv * D), (Hv * D) ** -0.5))
    weight = jax.random.normal(ks[6], (B, S, d))

    def loss(*a):
        y = nn.gated_delta_net(*a, k_heads=Hk, v_heads=Hv, k_dim=D,
                               v_dim=D, conv_kernel=K)
        return jnp.sum(y.astype(jnp.float32) * weight)

    def run(impl):
        monkeypatch.setattr(nn, "_gdn_mix_impl", lambda *a: impl)
        return jax.jit(jax.value_and_grad(loss, tuple(range(8))))(*args)

    built = PALLAS_LAUNCHES.labels(kernel="gdn_mix").value
    (l0, g0) = run(False)
    assert PALLAS_LAUNCHES.labels(kernel="gdn_mix").value == built
    (l1, g1) = run("interpret")
    assert PALLAS_LAUNCHES.labels(kernel="gdn_mix").value > built
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert abs(float(l1) - float(l0)) <= tol * abs(float(l0))
    for got, want in zip(g1, g0):
        assert got.dtype == want.dtype and _gap(got, want) < tol


def test_the_kernels_run_under_gdn_conv_and_not_under_gdn_scan(monkeypatch):
    """Both kernels carry ``pallas.gdn_mix`` inside the operator's
    ``gdn.conv``, forward and backward: ``pallas_ms.train`` counts them,
    and ``gdn_scan_roofline_share.train``, which reads ``gdn.scan`` and
    ``pallas.gated_delta_rule``, keeps reading the scan alone."""
    import re
    B, S, d, Hk, Hv, K = 1, 64, 32, 1, 1, 4
    shapes = [(B, S, d), ((2 * Hk + 2 * Hv) * D, d), (2 * Hv, d),
              ((2 * Hk + Hv) * D, K), (Hv,), (Hv,), (D,), (d, Hv * D)]
    args = [jnp.ones(s, jnp.float32) for s in shapes]
    monkeypatch.setattr(nn, "_gdn_mix_impl", lambda *a: "interpret")

    def loss(*a):
        return jnp.sum(nn.gated_delta_net(
            *a, k_heads=Hk, v_heads=Hv, k_dim=D, v_dim=D, conv_kernel=K))

    text = jax.jit(jax.grad(loss, (0, 3))).lower(*args).as_text(
        debug_info=True)
    names = set(re.findall(r'"(jit\(loss\)/[^"]*pallas\.[^"/]*)', text))
    assert names == {"jit(loss)/jvp(gdn.conv)/pallas.gdn_mix",
                     "jit(loss)/transpose(jvp(gdn.conv))/pallas.gdn_mix"}
