"""The chunked gated delta rule's Pallas kernels (``pallas/delta_rule.py``)
in interpret mode: against the ``jax.numpy`` chunks they replace on the
chip and against the benchmark reference's token-by-token rule, forward
and in all five gradients; and the choice between the two paths.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops.delta_rule import (chunk_gated_delta_rule,
                                      gated_delta_rule)
from mxnet_tpu.pallas.dispatch import PALLAS_FALLBACKS, PALLAS_LAUNCHES

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
D = 128
RUN_TOKENS = 512        # a grid step: 8 chunks of 64


@pytest.fixture
def ref(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    for m in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, m)
    from reference import qwen3_next
    return qwen3_next


def _inputs(seed, S, Hk, Hv, dtype, decay=(0.9, 1.0), repeat=False):
    """Head-major operands as the mixer makes them: q, k unit vectors (q
    scaled), g = log of a decay in ``decay``, beta in (0, 1).  With
    ``repeat`` every token of a run of 48 has the same key (the triangle
    is all ones times beta there: the solve's hard case)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, Hk, S, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (1, Hk, S, D)))
    if repeat:
        k = k.at[:, :, 8:56].set(k[:, :, 8:9])
    v = jax.random.normal(ks[2], (1, Hv, S, D))
    g = jnp.log(jax.random.uniform(ks[3], (1, Hv, S), minval=decay[0],
                                   maxval=decay[1]))
    beta = jax.random.uniform(ks[4], (1, Hv, S))
    if repeat:
        beta = beta.at[:, :, 8:56].set(0.99)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.isfinite(a).all() and np.linalg.norm(b) > 0
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _with_grads(rule, args, weight):
    loss = lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * weight)
    return jax.jit(rule)(*args), jax.jit(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)


def _token_rule(ref):
    def rule(q, k, v, g, beta):
        rep = v.shape[1] // q.shape[1]
        seq = lambda t: jnp.moveaxis(t, 1, 2)
        o = ref.delta_rule(seq(jnp.repeat(q, rep, 1)),
                           seq(jnp.repeat(k, rep, 1)), seq(v), seq(g),
                           seq(beta))
        return jnp.moveaxis(o, 2, 1)
    return rule


KERNEL = lambda *a: gated_delta_rule(*a, impl="interpret")
CHUNKS = lambda *a: gated_delta_rule(*a, impl=False)

CASES = {
    # S, Hk, Hv, dtype, decay, repeated keys
    "two-heads-a-key-head": (128, 1, 2, jnp.float32, (0.9, 1.0), False),
    "one-head-a-key-head": (128, 2, 2, jnp.float32, (0.9, 1.0), False),
    "padded-sequence": (200, 1, 2, jnp.float32, (0.9, 1.0), False),
    "padded-one-head": (72, 1, 1, jnp.float32, (0.5, 1.0), False),
    "g-near-0": (128, 1, 2, jnp.float32, (0.999, 1.0), False),
    "g-strongly-negative": (128, 1, 2, jnp.float32, (1e-4, 0.05), False),
    "repeated-keys": (128, 1, 2, jnp.float32, (0.9, 1.0), True),
    "repeated-keys-one-head": (64, 1, 1, jnp.float32, (0.9, 1.0), True),
    "two-grid-steps": (640, 1, 2, jnp.float32, (0.97, 1.0), False),
    "bfloat16": (192, 1, 2, jnp.bfloat16, (0.9, 1.0), False),
    "bfloat16-one-head": (128, 1, 1, jnp.bfloat16, (0.5, 1.0), False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_the_chunks_and_the_token_rule(ref, case):
    """Forward and the gradients with respect to q, k, v, g and beta.
    float32 operands: equal to the ``jax.numpy`` chunks and to the
    token-by-token rule to float32 rounding.  bfloat16 operands: equal to
    the chunks (which round the same products at other places) to
    bfloat16 rounding."""
    S, Hk, Hv, dtype, decay, repeat = CASES[case]
    args = _inputs(3, S, Hk, Hv, dtype, decay, repeat)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, Hv, S, D))
    o, grads = _with_grads(KERNEL, args, weight)
    assert o.dtype == dtype and o.shape == (1, Hv, S, D)
    assert [t.dtype for t in grads] == [a.dtype for a in args]
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    o0, grads0 = _with_grads(CHUNKS, args, weight)
    assert _gap(o, o0) < tol
    for got, want in zip(grads, grads0):
        assert _gap(got, want) < tol
    if dtype == jnp.float32:
        o1, grads1 = _with_grads(_token_rule(ref), args, weight)
        assert _gap(o, o1) < tol
        for got, want in zip(grads, grads1):
            assert _gap(got, want) < 5 * tol


def test_the_state_crosses_a_grid_step(ref):
    """640 tokens are two grid steps of 512 (the second padded).  A
    kernel that forgot the state between them would compute the second
    run of chunks from a zero state: that answer is far from the rule's,
    and the kernels' is not."""
    args = _inputs(5, 640, 1, 2, jnp.float32, (0.97, 1.0))
    o = jax.jit(KERNEL)(*args)
    want = jax.jit(_token_rule(ref))(*args)
    forgetful = jnp.concatenate([
        jax.jit(chunk_gated_delta_rule)(*(
            t[:, :, part] for t in args))
        for part in (slice(0, RUN_TOKENS), slice(RUN_TOKENS, 640))], 2)
    assert _gap(forgetful[:, :, RUN_TOKENS:], want[:, :, RUN_TOKENS:]) > 0.05
    assert _gap(o[:, :, RUN_TOKENS:], want[:, :, RUN_TOKENS:]) < 2e-5
    # and backward: what the first run's values receive from the second
    weight = jnp.zeros_like(want).at[:, :, RUN_TOKENS:].set(1.0)
    dv = lambda rule: jax.jit(jax.grad(
        lambda *a: jnp.sum(rule(*a) * weight), argnums=2))(*args)
    got, true = dv(KERNEL), dv(_token_rule(ref))
    assert float(jnp.abs(true[:, :, :RUN_TOKENS]).max()) > 1e-3
    assert _gap(got[:, :, :RUN_TOKENS], true[:, :, :RUN_TOKENS]) < 1e-4


def test_the_forward_keeps_run_starts_and_the_backward_kernel_alone():
    """``_run_forward``: o as the rule's, and the
    float32 state each run of 8 chunks starts from: zero before the
    first run, and a second run's that does not depend on what follows
    it.  ``_run_backward`` from those is the rule's gradient."""
    from mxnet_tpu.pallas import delta_rule as dr
    args = _inputs(6, 600, 1, 2, jnp.float32, (0.97, 1.0))
    laid, run = dr._layout(*args)
    assert run == 8 and laid[0].shape[2] == 2 * RUN_TOKENS
    o, starts = dr._run_forward(laid, run, True)
    assert _gap(o[:, :, :600], KERNEL(*args)) < 1e-6
    assert starts.shape == (1, 2, 2, D, D) and starts.dtype == jnp.float32
    assert float(jnp.abs(starts[:, :, 0]).max()) == 0.0
    short, _ = dr._layout(*(t[:, :, :RUN_TOKENS + 64] for t in args))
    assert _gap(dr._run_forward(short, run, True)[1][:, :, 1],
                starts[:, :, 1]) < 1e-6
    do = jax.random.normal(jax.random.PRNGKey(1), o.shape)
    dq, dk, dv, _, _ = dr._run_backward(laid, starts, do, run, True)
    want = jax.grad(lambda q, k, v: jnp.sum(
        CHUNKS(q, k, v, *args[3:]) * do[:, :, :600]),
        argnums=(0, 1, 2))(*args[:3])
    for got, true in zip((dq, dk, dv), want):
        assert _gap(got[:, :, :600], true) < 2e-5


def test_shapes_the_kernels_refuse():
    from mxnet_tpu.pallas.delta_rule import gated_delta_rule as kernels
    from mxnet_tpu.pallas.delta_rule import supported
    q, k, v, g, beta = _inputs(1, 64, 1, 3, jnp.float32)
    assert not supported(q, k, v)[0]                # three heads a key head
    with pytest.raises(ValueError, match="value heads"):
        kernels(q, k, v, g, beta, interpret=True)
    q, k, v, g, beta = _inputs(1, 64, 1, 2, jnp.float32)
    assert supported(q, k, v)[0]
    assert not supported(q[..., :64], k[..., :64], v)[0]      # Dk 64
    assert not supported(q, k, v.astype(jnp.float16))[0]


def test_the_choice_is_counted_and_has_no_knob(monkeypatch):
    """On the CPU ``auto`` is the ``jax.numpy`` path and books
    ``pallas_fallbacks{reason="backend"}``; ``impl="interpret"`` books a
    launch of kernel ``gated_delta_rule``; in a TPU program partitioned
    over a selected mesh the reason is ``mesh``; shapes the kernels
    refuse are ``delta-rule-geometry``.  Nothing reads the environment."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import delta_rule
    from mxnet_tpu.pallas import delta_rule as kernels
    # the kernel calls are jitted (a model's layers share one trace), and
    # a geometry this process has traced is not instantiated again
    kernels._run_forward.clear_cache()
    args = _inputs(2, 64, 1, 2, jnp.float32)
    count = lambda reason: PALLAS_FALLBACKS.labels(reason=reason).value
    launches = PALLAS_LAUNCHES.labels(kernel="gated_delta_rule")

    before, built = count("backend"), launches.value
    environ = dict(os.environ)
    o = gated_delta_rule(*args)
    assert count("backend") == before + 1 and launches.value == built
    assert _gap(o, chunk_gated_delta_rule(*args)) < 1e-6
    assert _gap(gated_delta_rule(*args, impl="interpret"), o) < 2e-5
    assert launches.value == built + 1 and count("backend") == before + 1
    assert dict(os.environ) == environ

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta_rule._delta_rule_impl(*args[:3]) == "compiled"
    narrow = tuple(t[..., :64] for t in args[:3])
    before = count("delta-rule-geometry")
    assert delta_rule._delta_rule_impl(*narrow) is False
    assert count("delta-rule-geometry") == before + 1
    before = count("mesh")
    mx.sharding.set_mesh({"dp": 4, "mp": 2})
    try:
        assert delta_rule._delta_rule_impl(*args[:3]) is False
    finally:
        mx.sharding.set_mesh(None)
    assert count("mesh") == before + 1
