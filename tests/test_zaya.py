"""ZAYA1 on the CPU at tiny widths, float32: each new operator against
the plain reference (benchmark/reference/zaya.py), forward and
gradients; the share test of the ``model-configs`` guide, section 4
(experts 0-7 plus experts 8-15 add up to the uncut layer, the router
counted once); the dropless layer under the worst imbalance; what
happens at t = 0; three ``Module.fit_step`` steps of
``models.get_symbol('zaya')`` against the reference's first steps.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

KW = dict(num_classes=96, num_layers=2, d_model=32, q_heads=4, kv_heads=2,
          head_dim=8, expert_dim=48, num_experts=8, experts_held=[2, 4],
          router_hidden=16, conv_k0=2, conv_k1=2, rotary_frac=0.5,
          rope_theta=5e6, seq_len=24, dtype="float32")
B, S = 2, KW["seq_len"]


@pytest.fixture
def ref(monkeypatch):
    """The benchmark's reference modules, importable as run.py makes
    them."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    for m in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, m)
    from reference import zaya, train
    zaya.train = train
    return zaya


def _params(ref, kw=KW, seed=7):
    key = ref.seed_key(seed)
    return key, {n: ref.init_leaf(key, n, s) for n, s in ref.param_specs(kw)}


def _stream(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, \
        (float(np.abs(a - b).max()), scale)


# ----------------------------------------------------------------------
# operators against the reference, forward and gradients
# ----------------------------------------------------------------------
def test_rms_norm_matches_reference(ref):
    from mxnet_tpu.ops.nn import rms_norm
    x, g = _stream(1, (B, S, 32)), 1.0 + 0.1 * _stream(2, (32,))
    w = _stream(3, (B, S, 32))
    _close(rms_norm(x, g), ref.rms_norm(x, g))
    got = jax.grad(lambda x, g: jnp.sum(rms_norm(x, g) * w), (0, 1))(x, g)
    want = jax.grad(lambda x, g: jnp.sum(ref.rms_norm(x, g) * w),
                    (0, 1))(x, g)
    for a, b in zip(got, want):
        _close(a, b)


CCA_NAMES = ["attn_q_weight", "attn_k_weight", "attn_v_weight",
             "attn_conv0_weight", "attn_conv1_weight", "attn_temp",
             "attn_o_weight"]


def _cca_op(h, ws, kw=KW):
    from mxnet_tpu.ops.nn import compressed_conv_attention
    return compressed_conv_attention(
        h, *ws, q_heads=kw["q_heads"], kv_heads=kw["kv_heads"],
        head_dim=kw["head_dim"], conv_k0=kw["conv_k0"],
        conv_k1=kw["conv_k1"], rotary_frac=kw["rotary_frac"],
        rope_theta=kw["rope_theta"])


def _cca_ref(ref, h, ws, kw=KW):
    p = {"L_" + n: w for n, w in zip(CCA_NAMES, ws)}
    return ref.cca(h, p, "L_", ref.dims(kw), "f32")


def _cca_weights(ref, seed=7):
    _, p = _params(ref, seed=seed)
    ws = [p["layer1_" + n] for n in CCA_NAMES]
    ws[5] = ws[5] * jnp.asarray([0.8, 1.3])     # temperatures off 1
    return ws


def test_cca_forward_matches_reference(ref):
    h, ws = _stream(4, (B, S, 32)), _cca_weights(ref)
    _close(_cca_op(h, ws), _cca_ref(ref, h, ws))


def test_cca_gradients_match_reference(ref):
    h, ws = _stream(4, (B, S, 32)), _cca_weights(ref)
    w = _stream(5, (B, S, 32))
    got = jax.grad(lambda h, ws: jnp.sum(_cca_op(h, ws) * w), (0, 1))(h, ws)
    want = jax.grad(lambda h, ws: jnp.sum(_cca_ref(ref, h, ws) * w),
                    (0, 1))(h, ws)
    _close(got[0], want[0])
    for name, a, b in zip(CCA_NAMES, got[1], want[1]):
        assert float(jnp.abs(b).max()) > 0, name
        _close(a, b)


MOE_NAMES = ["moe_router_in_weight", "moe_router_norm_gamma",
             "moe_router_fc1_weight", "moe_router_fc2_weight",
             "moe_router_out_weight", "moe_gate_weight", "moe_up_weight",
             "moe_down_weight"]


def _moe_weights(ref, kw=KW, seed=7, scale=20.0):
    """Layer 1's expert sublayer; the router's matrices scaled up so
    that its choices are far from ties and spread over the experts."""
    _, p = _params(ref, kw, seed)
    ws = [p["layer1_" + n] for n in MOE_NAMES]
    for i in (0, 2, 3, 4):
        ws[i] = ws[i] * scale
    return ws, p["layer1_moe_router_carry"] * 1.5


def _moe_op(h, r_prev, ws, carry, kw=KW, held=None):
    from mxnet_tpu.ops.nn import routed_experts
    first, count = held or kw["experts_held"]
    return routed_experts(
        h, *ws, r_prev, carry, num_experts=kw["num_experts"],
        held_first=first, held_count=count, num_hidden=kw["expert_dim"],
        router_hidden=kw["router_hidden"])


def _moe_ref(ref, h, r_prev, ws, carry, kw=KW, held=None):
    p = {"L_" + n: w for n, w in zip(MOE_NAMES, ws)}
    p["L_moe_router_carry"] = carry
    z = ref.dims(dict(kw, experts_held=list(held or kw["experts_held"])))
    d = h.shape[-1]
    y, r, e = ref.experts(h.reshape(-1, d), r_prev.reshape(-1, z["R"]), p,
                          "L_", z, "f32")
    return y.reshape(h.shape), r.reshape(h.shape[:-1] + (z["R"],)), e


def test_routed_experts_forward_matches_reference(ref):
    h, r0 = _stream(6, (B, S, 32)), _stream(7, (B, S, 16))
    ws, carry = _moe_weights(ref)
    y, r, counts = _moe_op(h, r0, ws, carry)
    y_ref, r_ref, e = _moe_ref(ref, h, r0, ws, carry)
    _close(y, y_ref)
    _close(r, r_ref)
    want = np.bincount(np.asarray(e), minlength=KW["num_experts"])
    assert counts.dtype == jnp.int32
    assert np.array_equal(np.asarray(counts), want)
    first, n = KW["experts_held"]
    assert 0 < want[first:first + n].sum() < B * S    # some here, some away
    assert (want > 0).sum() >= 4                      # and spread


def test_routed_experts_gradients_match_reference(ref):
    h, r0 = _stream(6, (B, S, 32)), _stream(7, (B, S, 16))
    ws, carry = _moe_weights(ref)
    wy, wr = _stream(8, (B, S, 32)), _stream(9, (B, S, 16))

    def loss(fn):
        def f(h, r0, ws, carry):
            y, r = fn(h, r0, ws, carry)[:2]
            return jnp.sum(y * wy) + jnp.sum(r * wr)
        return jax.grad(f, (0, 1, 2, 3))(h, r0, ws, carry)

    got = loss(_moe_op)
    want = loss(lambda *a: _moe_ref(ref, *a))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(b).max()) > 0
        _close(a, b, tol=5e-5)


def test_first_layer_has_no_state_and_no_carry(ref):
    """``carry_in=False``: the router state is ``h W_in`` alone."""
    from mxnet_tpu.ops.nn import routed_experts
    h = _stream(6, (B, S, 32))
    ws, _ = _moe_weights(ref)
    first, n = KW["experts_held"]
    y, r, _ = routed_experts(
        h, *ws, num_experts=8, held_first=first, held_count=n,
        num_hidden=48, router_hidden=16, carry_in=False)
    y0, r0, _ = _moe_op(h, jnp.zeros((B, S, 16)), ws, jnp.ones((1,)))
    _close(y, y0)
    _close(r, r0)


# ----------------------------------------------------------------------
# the share adds up to the layer
# ----------------------------------------------------------------------
def test_the_two_shares_add_up_to_the_uncut_layer(ref):
    """Experts 0-3 on one chip and 4-7 on its partner: the two parts of
    ``y`` add up to what the uncut reference gives for the whole layer;
    router state and token counts are what every chip computes alike,
    counted once."""
    h, r0 = _stream(10, (B, S, 32)), _stream(11, (B, S, 16))
    kw = dict(KW, experts_held=[0, 8])
    ws, carry = _moe_weights(ref, kw)
    whole, r_whole, e = _moe_ref(ref, h, r0, ws, carry, kw, held=(0, 8))
    parts = []
    for first in (0, 4):
        part = [w[first:first + 4] if n.endswith(("gate_weight", "up_weight",
                                                  "down_weight")) else w
                for n, w in zip(MOE_NAMES, ws)]
        y, r, counts = _moe_op(h, r0, part, carry, kw, held=(first, 4))
        _close(r, r_whole)
        assert np.array_equal(np.asarray(counts),
                              np.bincount(np.asarray(e), minlength=8))
        parts.append(y)
        assert float(jnp.abs(y).max()) > 0
    _close(parts[0] + parts[1], whole)
    # and no token is in both parts
    both = (jnp.abs(parts[0]).sum(-1) > 0) & (jnp.abs(parts[1]).sum(-1) > 0)
    assert not bool(both.any())


# ----------------------------------------------------------------------
# dropless under the worst imbalance
# ----------------------------------------------------------------------
def _ffn(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd.T


@pytest.mark.parametrize("impl", [False, "interpret"])
@pytest.mark.parametrize("target,held_here", [(5, True), (0, False),
                                              (7, False)])
def test_every_token_to_one_expert(target, held_here, impl):
    """All N tokens choose one expert.  Held here: every one of them
    gets that expert's FFN (nothing dropped: N is 4x an even share).
    Held elsewhere: y is 0, and so is every gradient.  With XLA's
    ragged product and with the Pallas grouped matmul (interpreted)."""
    from mxnet_tpu.parallel.moe import dropless_top1_experts
    N, d, F, E, first, held = 64, 16, 24, 8, 2, 4
    x = _stream(20, (N, d))
    wg, wu = 0.3 * _stream(21, (held, F, d)), 0.3 * _stream(22, (held, F, d))
    wd = 0.3 * _stream(23, (held, d, F))
    logits = 0.1 * _stream(24, (N, E))
    logits = logits.at[:, target].add(3.0)

    def f(x, logits, wg, wu, wd):
        prob = jax.nn.softmax(logits, -1)
        return dropless_top1_experts(x, prob, wg, wu, wd, first, impl=impl)

    y, counts = f(x, logits, wg, wu, wd)
    assert np.asarray(counts).tolist() == [N if e == target else 0
                                           for e in range(E)]
    grads = jax.grad(lambda *a: jnp.sum(f(*a)[0] ** 2), (0, 1, 2, 3, 4))(
        x, logits, wg, wu, wd)
    if held_here:
        j = target - first
        pe = jax.nn.softmax(logits, -1)[:, target]
        _close(y, pe[:, None] * _ffn(x, wg[j], wu[j], wd[j]))
        want = jax.grad(lambda x, l, g, u, dn: jnp.sum(
            (jax.nn.softmax(l, -1)[:, target, None]
             * _ffn(x, g[j], u[j], dn[j])) ** 2), (0, 1, 2, 3, 4))(
            x, logits, wg, wu, wd)
        for a, b in zip(grads, want):
            _close(a, b)
    else:
        assert float(jnp.abs(y).max()) == 0.0
        for g in grads:
            assert float(jnp.abs(g).max()) == 0.0


def test_pallas_grouped_matmul_matches_the_ragged_product():
    """The kernel (interpreted) against XLA's ragged product inside the
    whole layer, uneven groups and one empty, forward and gradients;
    and the kernel's launches are counted under its name."""
    from mxnet_tpu.parallel.moe import dropless_top1_experts
    from mxnet_tpu.pallas.dispatch import PALLAS_LAUNCHES
    N, d, F, E, first, held = 96, 16, 24, 8, 1, 5
    x = _stream(40, (N, d))
    wg, wu = 0.3 * _stream(41, (held, F, d)), 0.3 * _stream(42, (held, F, d))
    wd = 0.3 * _stream(43, (held, d, F))
    logits = 2.0 * _stream(44, (N, E))
    logits = logits.at[:, 3].add(-100.0)            # expert 3: no token

    def run(impl):
        def f(x, logits, wg, wu, wd):
            y, c = dropless_top1_experts(x, jax.nn.softmax(logits, -1), wg,
                                         wu, wd, first, impl=impl)
            return jnp.sum(y ** 2), (y, c)
        return jax.value_and_grad(f, (0, 1, 2, 3, 4), has_aux=True)(
            x, logits, wg, wu, wd)

    before = PALLAS_LAUNCHES.labels(kernel="grouped_matmul").value
    (_, (y_k, c_k)), g_k = run("interpret")
    assert PALLAS_LAUNCHES.labels(kernel="grouped_matmul").value > before
    (_, (y_x, c_x)), g_x = run(False)
    assert np.array_equal(np.asarray(c_k), np.asarray(c_x))
    assert int(c_x[3]) == 0 and 0 < int(c_x[first:first + held].sum()) < N
    _close(y_k, y_x)
    for a, b in zip(g_k, g_x):
        _close(a, b)


def test_ties_go_to_the_lower_index():
    from mxnet_tpu.parallel.moe import dropless_top1_experts
    N, d, F = 8, 4, 6
    prob = jnp.full((N, 4), 0.25)
    w = jnp.ones((4, F, d))
    _, counts = dropless_top1_experts(jnp.ones((N, d)), prob, w, w,
                                      jnp.ones((4, d, F)))
    assert np.asarray(counts).tolist() == [N, 0, 0, 0]


# ----------------------------------------------------------------------
# t = 0: nothing comes from before the sequence
# ----------------------------------------------------------------------
def test_first_token_sees_no_previous_token(ref):
    """The causal convolutions and the value shift read zeros before
    position 0: the first token's output is what a sequence of that one
    token gives (whose attention can only return its own value head 0;
    head 1, the previous token's, is zero)."""
    h, ws = _stream(4, (B, S, 32)), _cca_weights(ref)
    _close(_cca_op(h, ws)[:, :1], _cca_op(h[:, :1], ws))


@pytest.mark.parametrize("t", [1, 7, S - 1])
def test_cca_is_causal(ref, t):
    """Changing position t changes nothing before it, and something at
    t and (through both convolutions and the value shift) at t + 1."""
    h, ws = _stream(4, (B, S, 32)), _cca_weights(ref)
    moved = h.at[:, t].add(1.0)
    a, b = _cca_op(h, ws), _cca_op(moved, ws)
    assert float(jnp.abs(a[:, :t] - b[:, :t]).max()) == 0.0
    assert float(jnp.abs(a[:, t] - b[:, t]).max()) > 1e-4
    if t + 1 < S:
        assert float(jnp.abs(a[:, t + 1] - b[:, t + 1]).max()) > 1e-4


def test_rotary_turns_half_of_each_head_and_not_position_zero(ref):
    from mxnet_tpu.ops.nn import _rotary_half
    x = _stream(30, (1, 3, 6, 8))                       # (B, H, S, D)
    y = _rotary_half(x, 4, 5e6)
    assert np.array_equal(np.asarray(y[..., 4:]), np.asarray(x[..., 4:]))
    assert np.array_equal(np.asarray(y[:, :, 0]), np.asarray(x[:, :, 0]))
    assert float(jnp.abs(y[:, :, 1:, :4] - x[:, :, 1:, :4]).max()) > 1e-3
    # the pairing of halves, against the reference's (B, S, H, D) form
    _close(y.transpose(0, 2, 1, 3), ref.rotary(x.transpose(0, 2, 1, 3),
                                               4, 5e6))
    # a rotation: lengths are kept
    _close(jnp.sum(y * y, -1), jnp.sum(x * x, -1))


def test_value_shift_hands_head_one_the_previous_token(ref):
    """With every query equal (zero q and k weights give no direction:
    uniform causal attention is not reached that way, so read the
    values directly): an impulse in the value projection's second half
    shows up one position later."""
    from mxnet_tpu.ops.nn import _shift_right
    v = _stream(31, (B, S, 8))
    s = _shift_right(v, 1, 1)
    assert float(jnp.abs(s[:, 0]).max()) == 0.0
    assert np.array_equal(np.asarray(s[:, 1:]), np.asarray(v[:, :-1]))
    _close(s, ref.shift_right(v, 1, 1))


# ----------------------------------------------------------------------
# the model through Module.fit_step
# ----------------------------------------------------------------------
def test_symbol_parameters_are_the_references(ref):
    import mxnet_tpu as mx
    sym = mx.models.get_symbol("zaya", **KW)
    assert sym.list_outputs() == ["softmax_output",
                                  "moe_expert_tokens_output"]
    arg_shapes, out_shapes, _ = sym.infer_shape(
        data=(B, S), softmax_label=(B * S,))
    got = dict(zip(sym.list_arguments(), arg_shapes))
    for name, shape in ref.param_specs(KW):
        assert tuple(got.pop(name)) == tuple(shape), name
    assert set(got) == {"data", "softmax_label"}
    assert [tuple(s) for s in out_shapes] == [(B * S, 96), (2, 8)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fit_steps_match_the_reference(ref, dtype):
    """``Module.fit_step`` with kvstore='tpu' and Adam, as the
    benchmark's driver drives it: fused, one dispatch a step, losses
    and every leaf's change against the reference's first steps; in
    bfloat16 (multi_precision) within bfloat16's reach."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler, telemetry
    kw = dict(KW, dtype=dtype)
    low = dtype != "float32"
    key, weights = _params(ref, kw, seed=3)
    mod = mx.Module(mx.models.get_symbol("zaya", **kw), context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (B, S))],
             label_shapes=[("softmax_label", (B * S,))])

    class Seeded(mx.init.Initializer):
        def __call__(self, desc, arr):
            arr[:] = mx.nd.NDArray(weights[str(desc)].astype(arr.dtype),
                                   arr.context)

    mod.init_params(Seeded())
    opt = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
           "epsilon": 1e-8, "wd": 0.1}
    mod.init_optimizer(kvstore="tpu", optimizer="adam",
                       optimizer_params=dict(opt, multi_precision=low))
    rng = np.random.default_rng(0)
    pool = [ref.make_batch(rng, kw, B) for _ in range(3)]
    metric = mx.metric.create("ce")
    losses, d0 = [], int(profiler.DEVICE_DISPATCHES.value)
    for d, l in pool:
        batch = mx.io.DataBatch(data=[mx.nd.array(d)],
                                label=[mx.nd.array(l)])
        assert mod.fit_step(batch, metric)
        mod.update_metric(metric, batch.label)
        losses.append(float(metric.get()[1]))
        metric.reset()
    assert int(profiler.DEVICE_DISPATCHES.value) - d0 == 3
    want = ref.train.first_steps(
        ref, kw, "adam", opt, 1.0 / B, key,
        [ref.device_batch(d, l) for d, l in pool])
    np.testing.assert_allclose(losses, want["losses"],
                               rtol=2e-3 if low else 1e-5)
    exe = mod._exec_group._exec
    for name, shape in ref.param_specs(kw):
        w = exe.arg_dict[name]._data
        if low and w.dtype != jnp.float32:      # the float32 master
            upd = mod._kvstore._updater
            w = upd.states[name][1]._data
        got = float(ref.train.delta_norm(key, name, tuple(shape), w, ref))
        assert got == pytest.approx(want["delta_norms"][name],
                                    rel=0.2 if low else 1e-3, abs=1e-7), name
    # the counts rode the step; the gauges are filled when read
    counts = mod.get_outputs()[1].asnumpy()
    assert counts.shape == (2, 8) and counts.dtype == np.int32
    assert (counts.sum(axis=1) == B * S).all()
    load = telemetry.moe.publish()
    first, n = kw["experts_held"]
    here = counts[:, first:first + n]
    reg = telemetry.REGISTRY
    assert reg.get("moe_expert_load_max_over_mean").value == pytest.approx(
        here.max() / here.mean())
    assert reg.get("moe_expert_tokens").labels(
        layer=1, expert=3).value == counts[1, 3]
    assert reg.get("moe_tokens_away").labels(layer=0).value \
        == B * S - here[0].sum()
    assert np.array_equal(load["counts"], counts)
    # the counts outlive the module (a reader may come after it)
    import gc
    del mod, exe
    gc.collect()
    assert np.array_equal(telemetry.moe.publish()["counts"], counts)
