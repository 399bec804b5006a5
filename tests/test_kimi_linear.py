"""Kimi-Linear on the CPU at tiny widths, float32, against the plain
reference (benchmark/reference/kimi_linear.py, whose KDA state advances
token by token): the channel-gated chunk routine against the recurrence
in o and all five gradients, under decays of e^-20 inside one chunk
beside channels that do not decay and with a key repeated inside a
chunk; a gate constant over a head's channels against the scalar
routine; the KDA mixer and latent attention without rotation as
operators; the share test of the ``model-configs`` guide, section 4 (32
shares of 8 experts and the shared expert once are the uncut layer);
loss, every leaf's gradient and three ``Module.fit_step`` steps of
``models.get_symbol('kimi_linear')`` at a preset of five layers in the
cut's order, a strict part of the experts held and a sequence that is
no whole number of chunks.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

with open(os.path.join(ROOT, "benchmark", "configs",
                       "kimi_linear_48b_train.json")) as _f:
    CONFIG = json.load(_f)
KW = dict(CONFIG["rehearse"]["kwargs"])     # the cell's rehearsal sizes
B, S = 2, KW["seq_len"]


@pytest.fixture
def ref(monkeypatch):
    """The benchmark's reference modules, importable as run.py makes
    them."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    for m in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, m)
    from reference import kimi_linear, train
    kimi_linear.train = train
    return kimi_linear


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


def _grads_close(got, want, tol=5e-5):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(b).max()) > 0
        _close(a, b, tol)


# ----------------------------------------------------------------------
# the chunk routine against the token-by-token recurrence
# ----------------------------------------------------------------------
def _scan_operands(S_=150, H=2, D=32, seed=0):
    """Head-major operands whose gates hold, side by side in one head,
    channels that decay by e^-20 and more inside one chunk of 64 (a rate
    of 0.4 a token: where a divided decay overflows), channels that
    hardly decay and channels that do not at all; a key repeated inside
    a chunk; a sequence of two chunks and a padded one."""
    rng = np.random.RandomState(seed)
    n = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k, v = unit(n(1, H, S_, D)) * D ** -0.5, unit(n(1, H, S_, D)), \
        n(1, H, S_, D)
    k = k.at[:, :, 70].set(k[:, :, 66])
    rate = jnp.asarray(rng.choice([0.0, 0.01, 0.4, 3.0], size=(1, H, 1, D)),
                       jnp.float32)
    g = -rate * jnp.asarray(0.5 + 0.5 * rng.rand(1, H, S_, D), jnp.float32)
    return (q, k, v, g, jax.nn.sigmoid(n(1, H, S_))), n(1, H, S_, D)


def _head_major_rule(ref):
    seq = lambda t: jnp.moveaxis(t, 1, 2)
    return lambda q, k, v, g, beta: seq(ref.kda_rule(
        seq(q), seq(k), seq(v), seq(g), seq(beta)))


def test_chunk_routine_matches_the_recurrence_under_fast_and_absent_decays(
        ref):
    from mxnet_tpu.ops.delta_rule import chunk_kda_delta_rule
    args, do = _scan_operands()
    g = args[3]
    total = jnp.sum(g[:, :, :64], axis=2)
    assert float(total.min()) < -20.0 and float(total.max()) == 0.0
    both = [jax.jit(jax.value_and_grad(
        lambda *a, f=f: jnp.sum(f(*a) * do), argnums=(0, 1, 2, 3, 4)))(*args)
        for f in (chunk_kda_delta_rule, _head_major_rule(ref))]
    _close(chunk_kda_delta_rule(*args), _head_major_rule(ref)(*args),
           tol=5e-6)
    for got, want in zip(both[0][1], both[1][1]):
        assert got.shape == want.shape
        assert float(jnp.linalg.norm(got - want)) \
            <= 5e-6 * float(jnp.linalg.norm(want))
    assert both[0][1][3].shape == g.shape           # dg is (S, Dk) a head


def test_a_gate_constant_over_the_channels_is_the_scalar_rule():
    """Not bit for bit: the scalar routine multiplies a chunk's Gram
    matrix ``k k^T`` by one decay a pair AFTER the contraction, the
    channel routine sums ``k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` with the
    decay inside it (per pair on the diagonal blocks, by a reference row
    below them), so the two round differently; they agree to float32's
    rounding."""
    from mxnet_tpu.ops.delta_rule import (chunk_gated_delta_rule,
                                          chunk_kda_delta_rule)
    (q, k, v, g, beta), do = _scan_operands(seed=1)
    gs = g[..., 0]
    wide = lambda gs: jnp.broadcast_to(gs[..., None], g.shape)
    _close(chunk_kda_delta_rule(q, k, v, wide(gs), beta),
           chunk_gated_delta_rule(q, k, v, gs, beta), tol=2e-6)


def test_the_chooser_picks_by_the_gates_rank_and_counts_a_refusal(
        monkeypatch):
    from mxnet_tpu.ops import delta_rule
    from mxnet_tpu.pallas import dispatch
    from mxnet_tpu.telemetry import REGISTRY
    monkeypatch.setattr(dispatch, "_compiles_here", lambda: (True, "", None))
    t = lambda *s, d=jnp.bfloat16: jax.ShapeDtypeStruct(s, d)
    q = t(1, 32, 8192, 128)
    g4, g3 = t(1, 32, 8192, 128, d=jnp.float32), t(1, 32, 8192, d=jnp.float32)
    assert delta_rule._delta_rule_impl(q, q, q, g4) == "compiled"
    assert delta_rule._delta_rule_impl(q, q, q, g3) == "compiled"
    fallbacks = REGISTRY.get("pallas_fallbacks")
    before = fallbacks.labels(reason="kda-geometry").value
    narrow = t(1, 32, 8192, 64)
    assert delta_rule._delta_rule_impl(narrow, narrow, narrow,
                                       t(1, 32, 8192, 64)) is False
    # two value heads a key head: the scalar pair's geometry, not this one's
    assert delta_rule._delta_rule_impl(t(1, 16, 8192, 128),
                                       t(1, 16, 8192, 128), q, g4) is False
    assert fallbacks.labels(reason="kda-geometry").value == before + 2
    with pytest.raises(ValueError):
        delta_rule.chunk_kda_delta_rule(
            *(jnp.zeros(s.shape, s.dtype) for s in
              (t(1, 2, 64, 16), t(1, 2, 64, 16), t(1, 4, 64, 16),
               t(1, 2, 64, 16), t(1, 2, 64))))


# ----------------------------------------------------------------------
# the mixers against the reference, forward and gradients
# ----------------------------------------------------------------------
KDA_NAMES = ["kda_q_weight", "kda_k_weight", "kda_v_weight",
             "kda_conv_weight", "kda_fa_weight", "kda_fb_weight", "kda_A_log",
             "kda_dt_bias", "kda_b_weight", "kda_ga_weight", "kda_gb_weight",
             "kda_gb_bias", "kda_norm_gamma", "kda_o_weight"]
MLA_NAMES = ["attn_q_weight", "attn_kva_weight", "attn_kv_norm_gamma",
             "attn_kvb_weight", "attn_o_weight"]


def _mixer_weights(ref, layer, names, scale):
    """A layer's mixer weights; matrices scaled up from normal(0, 0.02)
    so that gates and softmax are far from flat, gains and the output
    gate's bias moved off their seeded 1 and 0."""
    _, p = _params(ref)
    ws = []
    for i, n in enumerate(names):
        w = p["layer%d_%s" % (layer, n)]
        if n.endswith(("_gamma", "_bias")):
            w = w + 0.1 * _stream(40 + i, w.shape)
        elif n.endswith("_weight"):
            w = w * scale
        ws.append(w)
    return ws


def test_kimi_delta_attention_matches_reference(ref):
    """Forward, with gates far from flat, and causality; every leaf's
    gradient is held at the model's level below."""
    from mxnet_tpu.ops.nn import kimi_delta_attention
    kw = dict(heads=KW["heads"], head_dim=KW["head_dim"],
              conv_kernel=KW["conv_kernel"])
    op = jax.jit(lambda h, ws: kimi_delta_attention(h, *ws, **kw))
    want = lambda h, ws: ref.kimi_delta_attention(
        h, {"L_" + n: w for n, w in zip(KDA_NAMES, ws)}, "L_", ref.dims(KW),
        "f32")
    h, ws = _stream(4, (B, S, KW["d_model"])), \
        _mixer_weights(ref, 0, KDA_NAMES, 10.0)
    _close(op(h, ws), want(h, ws))
    # causal: position t changes nothing before it
    a, b = op(h, ws), op(h.at[:, 7].add(1.0), ws)
    assert float(jnp.abs(a[:, :7] - b[:, :7]).max()) == 0.0
    assert float(jnp.abs(a[:, 7] - b[:, 7]).max()) > 1e-6


def test_latent_attention_without_rotation_matches_reference(ref):
    """``rotary=False`` against the reference's positionless layer; the
    attribute at its default builds what it built (the jaxpr with
    ``rotary=True`` said is the default's string for string, and turns
    the 64 shared channels: another result)."""
    from mxnet_tpu.ops.nn import latent_attention
    kw = dict(heads=KW["heads"], nope_dim=KW["nope_dim"],
              rope_dim=KW["rope_dim"], v_dim=KW["v_dim"],
              kv_rank=KW["kv_rank"], eps=1e-5)
    op = jax.jit(lambda h, ws: latent_attention(h, *ws, rotary=False, **kw))
    want = lambda h, ws: ref.latent_attention(
        h, {"L_" + n: w for n, w in zip(MLA_NAMES, ws)}, "L_", ref.dims(KW),
        "f32")
    h, ws = _stream(4, (B, S, KW["d_model"])), \
        _mixer_weights(ref, 3, MLA_NAMES, 10.0)
    w = _stream(5, (B, S, KW["d_model"]))
    _close(op(h, ws), want(h, ws))
    _grads_close(
        jax.jit(jax.grad(lambda h, ws: jnp.sum(op(h, ws) * w), (0, 1)))(h, ws),
        jax.jit(jax.grad(lambda h, ws: jnp.sum(want(h, ws) * w),
                         (0, 1)))(h, ws))
    default = lambda h, ws: latent_attention(h, *ws, **kw)
    turned = lambda h, ws: latent_attention(h, *ws, rotary=True, **kw)
    assert str(jax.make_jaxpr(default)(h, ws)) \
        == str(jax.make_jaxpr(turned)(h, ws))
    assert "cos" in str(jax.make_jaxpr(default)(h, ws))
    assert "cos" not in str(jax.make_jaxpr(
        lambda h, ws: latent_attention(h, *ws, rotary=False, **kw))(h, ws))
    assert np.array_equal(np.asarray(default(h, ws)),
                          np.asarray(turned(h, ws)))
    assert float(jnp.abs(default(h, ws) - op(h, ws)).max()) > 1e-4


# ----------------------------------------------------------------------
# the share: 32 chips of 8 experts
# ----------------------------------------------------------------------
def test_32_shares_of_8_experts_and_the_shared_expert_once_are_the_layer(
        ref):
    """Thirty-two chips hold eight experts each of a layer's 256 (the
    cell's deployment, at a small width): the routed parts of the 32,
    and the shared expert that every chip computes alike counted once,
    add up to what the uncut reference gives for the whole layer."""
    from mxnet_tpu.ops.nn import routed_experts
    d, F, E, k, N = 16, 8, 256, 8, 48
    kw = dict(KW, d_model=d, expert_dim=F, shared_dim=F, num_experts=E,
              top_k=k, experts_held=[0, E])
    names = ["gate_weight", "up_weight", "down_weight", "router_weight",
             "shared_gate_weight", "shared_up_weight", "shared_down_weight"]
    shapes = [(E, F, d), (E, F, d), (E, d, F), (E, d), (F, d), (F, d), (d, F)]
    ws = [_stream(60 + i, s) * 0.5 for i, s in enumerate(shapes)]
    bias = 0.05 * _stream(70, (E,))
    h = _stream(10, (1, N, d))
    p = {"L_moe_" + n: w for n, w in zip(names, ws)}
    y_whole, s_whole, e = ref.experts(h.reshape(N, d), p, bias, "L_",
                                      ref.dims(kw), "f32")

    def share(first, ws):
        part = [w[first:first + 8] if i < 3 else w for i, w in enumerate(ws)]
        return routed_experts(
            h, **dict(zip(names, part)), router_bias=bias, router="sigmoid",
            top_k=k, route_scale=kw["route_scale"], num_experts=E,
            held_first=first, held_count=8, num_hidden=F, shared_hidden=F,
            shared_gate=False)

    no_shared = [jnp.zeros_like(w) if "shared" in n else w
                 for n, w in zip(names, ws)]

    @jax.jit
    def all_shares(ws):
        total = jnp.zeros_like(h)
        for first in range(0, E, 8):
            y, chosen, counts, _ = share(first, ws)
            total = total + y
        return total, chosen, counts

    total, chosen, counts = all_shares(no_shared)
    assert np.array_equal(np.asarray(chosen).reshape(N, k), np.asarray(e))
    assert int(counts.sum()) == N * k
    shared = share(0, ws)[0] - share(0, no_shared)[0]
    _close(shared.reshape(N, d), s_whole, tol=5e-5)
    _close((total + shared).reshape(N, d), y_whole + s_whole, tol=5e-5)


# ----------------------------------------------------------------------
# the model through Module.fit_step
# ----------------------------------------------------------------------
def test_the_preset_is_the_cuts_five_layers_and_a_strict_share():
    from mxnet_tpu.models import kimi_linear
    src = CONFIG["source_config"]["linear_attn_config"]
    assert tuple(src["kda_layers"]) == kimi_linear.KDA_LAYERS
    assert tuple(src["full_attn_layers"]) == kimi_linear.FULL_ATTN_LAYERS
    assert kimi_linear.layer_kinds(5) == ["kda", "kda", "kda", "full", "kda"]
    kinds = kimi_linear.layer_kinds(27)
    assert (kinds.count("kda"), kinds.count("full")) == (20, 7)
    assert kinds[26] == "full"
    with pytest.raises(ValueError):
        kimi_linear.layer_kinds(3, kda_layers=[1, 3], full_attn_layers=[])
    assert KW["num_layers"] == 5 and KW["dense_layers"] == 1
    first, held = KW["experts_held"]
    assert 0 < first and first + held < KW["num_experts"]
    assert KW["seq_len"] % 64 and KW["seq_len"] > 64


def test_symbol_parameters_and_aux_states_are_the_references(ref):
    import mxnet_tpu as mx
    sym = mx.models.get_symbol("kimi_linear", **KW)
    assert sym.list_outputs() == ["softmax_output",
                                  "moe_expert_tokens_output"]
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(
        data=(B, S), softmax_label=(B * S,))
    got = dict(zip(sym.list_arguments(), arg_shapes))
    for name, shape in ref.param_specs(KW):
        assert tuple(got.pop(name)) == tuple(shape), name
    assert set(got) == {"data", "softmax_label"}
    aux = ref.init_aux(KW)
    assert sym.list_auxiliary_states() == sorted(aux) and len(aux) == 4
    assert [tuple(s) for s in out_shapes] == [(B * S, KW["num_classes"]),
                                             (4, KW["num_experts"])]
    args = sym.list_arguments()
    # layer 0: a KDA mixer over the dense FFN; layer 3: latent attention
    assert "layer0_kda_q_weight" in args and "layer0_ffn_gate_weight" in args
    assert "layer0_moe_gate_weight" not in args
    assert "layer3_attn_kva_weight" in args
    assert "layer3_kda_q_weight" not in args
    assert "layer4_kda_fb_weight" in args and "layer4_moe_gate_weight" in args
    nodes = {n["name"]: n for n in json.loads(sym.tojson())["nodes"]}
    assert nodes["layer3_attn"]["attrs"]["rotary"] == "False"
    again = mx.sym.load_json(sym.tojson())
    assert again.list_arguments() == args
    assert again.list_auxiliary_states() == sym.list_auxiliary_states()


def test_loss_gradients_and_three_fit_steps_match_the_reference(ref):
    """``Module.fit_step`` with kvstore='tpu' and Adam as the
    benchmark's driver drives it: fused, one dispatch a step; the three
    losses, every leaf's first gradient (its norm, worked out of Adam's
    state after one step, which holds its scale) and every leaf's change
    after three steps against the reference's first steps; the bias the
    same after the steps as before them."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from drivers import train_fit
    key, weights = _params(ref, seed=3)
    aux0 = ref.init_aux(KW)
    bias0 = {n: np.asarray(b) for n, b in aux0.items()}
    rng = np.random.default_rng(0)
    pool = [ref.make_batch(rng, KW, B) for _ in range(3)]
    sym = mx.models.get_symbol("kimi_linear", **KW)
    values = dict(weights, **aux0)

    class Seeded(mx.init.Initializer):
        def __call__(self, desc, arr):
            arr[:] = mx.nd.NDArray(values[str(desc)].astype(arr.dtype),
                                   arr.context)

    mod = mx.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (B, S))],
             label_shapes=[("softmax_label", (B * S,))])
    mod.init_params(Seeded())
    opt = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
           "epsilon": 1e-8, "wd": 0.1}
    mod.init_optimizer(kvstore="tpu", optimizer="adam",
                       optimizer_params=dict(opt, multi_precision=False))
    metric = mx.metric.create("ce")
    names = [n for n, _ in ref.param_specs(KW)]
    losses, d0 = [], int(profiler.DEVICE_DISPATCHES.value)
    for d, l in pool:
        batch = mx.io.DataBatch(data=[mx.nd.array(d)],
                                label=[mx.nd.array(l)])
        assert mod.fit_step(batch, metric)
        mod.update_metric(metric, batch.label)
        losses.append(float(metric.get()[1]))
        metric.reset()
        if len(losses) == 1:
            # every leaf's first gradient, out of Adam's state after
            # ONE step (the benchmark's arithmetic)
            got_g = train_fit.first_gradient_norms(mod, names, "adam", opt)
    assert int(profiler.DEVICE_DISPATCHES.value) - d0 == 3
    want = ref.train.first_steps(
        ref, KW, "adam", opt, 1.0 / B, key,
        [ref.device_batch(d, l) for d, l in pool])
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-5)
    gaps = ref.train.leaf_gaps(got_g, want["grad_norms"])
    assert set(gaps) == set(names)
    assert ref.train.worst_gap(gaps)[0] < 1e-3, ref.train.worst_gap(gaps)
    exe = mod._exec_group._exec
    states = mod._kvstore._updater.states
    for name, shape in ref.param_specs(KW):
        got = float(ref.train.delta_norm(key, name, tuple(shape),
                                         exe.arg_dict[name]._data, ref))
        assert got == pytest.approx(want["delta_norms"][name], rel=1e-3,
                                    abs=1e-7), name
    for name, b in bias0.items():
        assert np.array_equal(exe.aux_dict[name].asnumpy(), b)
        assert name not in states
    assert set(states) == {n for n, _ in ref.param_specs(KW)}
    counts = mod.get_outputs()[1].asnumpy()
    assert counts.shape == (4, KW["num_experts"])
    assert (counts.sum(axis=1) == B * S * KW["top_k"]).all()
