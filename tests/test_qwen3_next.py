"""Qwen3-Next on the CPU at tiny widths, float32: each new operator
against the plain reference (benchmark/reference/qwen3_next.py), forward
and gradients; the chunked gated delta rule against the token-by-token
rule under slow and under fast decay, on sequences that are and are not
whole chunks; the top-k dropless layer against the masked dense form
under even, collapsed and empty routing; the share test of the
``model-configs`` guide, section 4 (the routed parts of all 16 shares
plus the shared expert once are the uncut layer); three
``Module.fit_step`` steps of ``models.get_symbol('qwen3_next')`` against
the reference's first steps.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

KW = dict(num_classes=96, num_layers=2, d_model=32,
          full_attention_interval=2, q_heads=4, kv_heads=2, head_dim=8,
          rotary_frac=0.25, rope_theta=1e7, gdn_k_heads=2, gdn_v_heads=4,
          gdn_k_dim=8, gdn_v_dim=8, conv_kernel=4, expert_dim=16,
          num_experts=16, experts_held=[4, 4], top_k=3, shared_dim=16,
          seq_len=80, dtype="float32")
B, S = 2, KW["seq_len"]         # 80 tokens: a chunk of 64 and a padded one


@pytest.fixture
def ref(monkeypatch):
    """The benchmark's reference modules, importable as run.py makes
    them."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    for m in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, m)
    from reference import qwen3_next, train
    qwen3_next.train = train
    return qwen3_next


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
# the norm in its zero-centred form
# ----------------------------------------------------------------------
def test_zero_centred_rms_norm_matches_reference(ref):
    from mxnet_tpu.ops.nn import rms_norm
    x, g = _stream(1, (B, S, 32)), 0.1 * _stream(2, (32,))
    w = _stream(3, (B, S, 32))
    op = lambda x, g: rms_norm(x, g, eps=1e-6, zero_centered=True)
    _close(op(x, g), ref.rms_norm(x, g))
    _close(op(x, jnp.zeros(32)), rms_norm(x, jnp.ones(32), eps=1e-6))
    _grads_close(jax.grad(lambda x, g: jnp.sum(op(x, g) * w), (0, 1))(x, g),
                 jax.grad(lambda x, g: jnp.sum(ref.rms_norm(x, g) * w),
                          (0, 1))(x, g))


# ----------------------------------------------------------------------
# the chunked gated delta rule against the token-by-token rule
# ----------------------------------------------------------------------
def _rule_inputs(ref, seed, S, slow):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    Hk, Hv, Dk, Dv = 2, 4, 16, 8
    q = ref.l2_norm(jax.random.normal(ks[0], (B, S, Hk, Dk))) * Dk ** -0.5
    k = ref.l2_norm(jax.random.normal(ks[1], (B, S, Hk, Dk)))
    v = jax.random.normal(ks[2], (B, S, Hv, Dv))
    lo, hi = (0.9, 1.0) if slow else (0.01, 0.5)
    g = jnp.log(jax.random.uniform(ks[3], (B, S, Hv), minval=lo, maxval=hi))
    return q, k, v, g, jax.random.uniform(ks[4], (B, S, Hv))


def _token_rule(ref, q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    return ref.delta_rule(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v, g,
                          beta)


@jax.jit
def _chunk_rule(q, k, v, g, beta):
    from mxnet_tpu.ops.delta_rule import chunk_gated_delta_rule
    heads_first = lambda t: jnp.moveaxis(t, 1, 2)
    return jnp.moveaxis(chunk_gated_delta_rule(
        *(heads_first(t) for t in (q, k, v, g, beta))), 1, 2)


@pytest.mark.parametrize("slow,S_", [(True, 192), (False, 192), (True, 200),
                                     (False, 200)],
                         ids=["slow-whole", "fast-whole", "slow-padded",
                              "fast-padded"])
def test_chunked_rule_matches_token_by_token(ref, slow, S_):
    """Three chunks of 64, or three and 8 tokens of a fourth.  Slow decay
    (``exp(g)`` >= 0.9): the state carries across every chunk, so a
    fault in the hand-over shows.  Fast (<= 0.5): the differences of the
    running sums are large, so a fault in the masked exponentials
    shows."""
    args = _rule_inputs(ref, 3, S_, slow)
    w = _stream(9, (B, S_, 4, 8))
    _close(_chunk_rule(*args), _token_rule(ref, *args))
    loss = lambda fn: jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                       argnums=(0, 1, 2, 3, 4)))(*args)
    _grads_close(loss(_chunk_rule),
                 loss(lambda *a: _token_rule(ref, *a)), tol=2e-5)


def test_chunked_rule_carries_its_state_across_chunks(ref):
    """With no decay and one value written, in the first chunk (beta 1
    at token 5 and 0 elsewhere), the same key as a query in the third
    chunk reads it back whole: ``o = (k . q) v``."""
    q, k, v, g, _ = _rule_inputs(ref, 4, 192, True)
    beta = jnp.zeros_like(g).at[:, 5].set(1.0)
    q = q.at[:, 150].set(k[:, 5] * 0.25)
    o = _chunk_rule(q, k, jnp.ones_like(v), jnp.zeros_like(g), beta)
    _close(o[:, 150], jnp.full_like(o[:, 150], 0.25))
    assert float(jnp.abs(o[:, :5]).max()) == 0.0


def test_unit_lower_inverse_where_keys_repeat():
    """Identical keys make the triangle all ones below the diagonal,
    where a Neumann product loses every digit: the substitution does
    not.  The gradient is the closed form's."""
    from mxnet_tpu.ops.delta_rule import unit_lower_inverse
    a = jnp.tril(jnp.ones((2, 64, 64)), -1) * 0.99
    eye = jnp.eye(64)
    assert float(jnp.abs((eye + a) @ jax.jit(unit_lower_inverse)(a)
                         - eye).max()) < 1e-5
    a = jnp.tril(_stream(5, (3, 64, 64)), -1) * 0.2
    w = _stream(6, (3, 64, 64))
    got = jax.jit(jax.grad(lambda a: jnp.sum(unit_lower_inverse(a) * w)))(a)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye + a) * w))(a)
    _close(got, jnp.tril(want, -1), tol=1e-4)


# ----------------------------------------------------------------------
# the two mixers against the reference, forward and gradients
# ----------------------------------------------------------------------
GDN_NAMES = ["gdn_qkvz_weight", "gdn_ba_weight", "gdn_conv_weight",
             "gdn_A_log", "gdn_dt_bias", "gdn_norm_gamma", "gdn_out_weight"]
ATTN_NAMES = ["attn_q_weight", "attn_k_weight", "attn_v_weight",
              "attn_q_norm_gamma", "attn_k_norm_gamma", "attn_o_weight"]


def _layer_weights(ref, names, layer, scale=10.0):
    """A layer's mixer weights; matrices scaled up from normal(0, 0.02)
    so that gates, decays and softmax are far from their flat middle,
    gains moved off their identity."""
    _, p = _params(ref)
    ws = []
    for i, n in enumerate(names):
        w = p["layer%d_%s" % (layer, n)]
        if n.endswith("_gamma"):
            w = w + 0.1 * _stream(40 + i, w.shape)
        elif n.endswith("_weight"):
            w = w * scale
        ws.append(w)
    return ws


@jax.jit
def _gdn_op(h, ws):
    from mxnet_tpu.ops.nn import gated_delta_net
    return gated_delta_net(h, *ws, k_heads=2, v_heads=4, k_dim=8, v_dim=8,
                           conv_kernel=4)


def _gdn_ref(ref, h, ws):
    p = {"L_" + n: w for n, w in zip(GDN_NAMES, ws)}
    return ref.gated_delta_net(h, p, "L_", ref.dims(KW), "f32")


def test_gated_delta_net_matches_reference(ref):
    h, ws = _stream(4, (B, S, 32)), _layer_weights(ref, GDN_NAMES, 0)
    w = _stream(5, (B, S, 32))
    _close(_gdn_op(h, ws), _gdn_ref(ref, h, ws))
    _grads_close(
        jax.jit(jax.grad(lambda h, ws: jnp.sum(_gdn_op(h, ws) * w),
                         (0, 1)))(h, ws),
        jax.jit(jax.grad(lambda h, ws: jnp.sum(_gdn_ref(ref, h, ws) * w),
                         (0, 1)))(h, ws))


@jax.jit
def _attn_op(h, ws):
    from mxnet_tpu.ops.nn import gated_causal_self_attention
    return gated_causal_self_attention(
        h, *ws, q_heads=4, kv_heads=2, head_dim=8, rotary_frac=0.25,
        rope_theta=1e7)


def _attn_ref(ref, h, ws):
    p = {"L_" + n: w for n, w in zip(ATTN_NAMES, ws)}
    return ref.gated_attention(h, p, "L_", ref.dims(KW), "f32")


def test_gated_attention_matches_reference(ref):
    h, ws = _stream(4, (B, S, 32)), _layer_weights(ref, ATTN_NAMES, 1)
    w = _stream(5, (B, S, 32))
    _close(_attn_op(h, ws), _attn_ref(ref, h, ws))
    _grads_close(
        jax.jit(jax.grad(lambda h, ws: jnp.sum(_attn_op(h, ws) * w),
                         (0, 1)))(h, ws),
        jax.jit(jax.grad(lambda h, ws: jnp.sum(_attn_ref(ref, h, ws) * w),
                         (0, 1)))(h, ws))


@pytest.mark.parametrize("op,names,layer,t", [
    (_gdn_op, GDN_NAMES, 0, 1), (_gdn_op, GDN_NAMES, 0, 70),
    (_attn_op, ATTN_NAMES, 1, 7)], ids=["gdn-1", "gdn-70", "attn-7"])
def test_mixers_are_causal(ref, op, names, layer, t):
    """Changing position t changes nothing before it and something at
    it; t = 70 lies in the padded second chunk."""
    h, ws = _stream(4, (B, S, 32)), _layer_weights(ref, names, layer)
    a, b = op(h, ws), op(h.at[:, t].add(1.0), ws)
    assert float(jnp.abs(a[:, :t] - b[:, :t]).max()) == 0.0
    assert float(jnp.abs(a[:, t] - b[:, t]).max()) > 1e-5


# ----------------------------------------------------------------------
# the expert sublayer: linear router, top-k, shared expert
# ----------------------------------------------------------------------
MOE_NAMES = ["moe_gate_weight", "moe_up_weight", "moe_down_weight",
             "moe_router_weight", "moe_shared_gate_weight",
             "moe_shared_up_weight", "moe_shared_down_weight",
             "moe_shared_sg_weight"]
MOE_INPUTS = ["gate_weight", "up_weight", "down_weight", "router_weight",
              "shared_gate_weight", "shared_up_weight", "shared_down_weight",
              "shared_sg_weight"]


def _moe_weights(ref, kw=KW, scale=20.0):
    _, p = _params(ref, kw)
    return [p["layer1_" + n] * scale for n in MOE_NAMES]


def _moe_op(h, ws, kw=KW, held=None):
    from mxnet_tpu.ops.nn import routed_experts
    first, count = held or kw["experts_held"]
    return routed_experts(
        h, **dict(zip(MOE_INPUTS, ws)), router="linear", top_k=kw["top_k"],
        num_experts=kw["num_experts"], held_first=first, held_count=count,
        num_hidden=kw["expert_dim"], shared_hidden=kw["shared_dim"])


def _moe_ref(ref, h, ws, kw=KW, held=None):
    p = {"L_" + n: w for n, w in zip(MOE_NAMES, ws)}
    z = ref.dims(dict(kw, experts_held=list(held or kw["experts_held"])))
    y, s, e = ref.experts(h.reshape(-1, h.shape[-1]), p, "L_", z, "f32")
    return y.reshape(h.shape), s.reshape(h.shape), e


def test_routed_experts_forward_matches_reference(ref):
    h, ws = _stream(6, (B, S, 32)), _moe_weights(ref)
    y, chosen, counts = _moe_op(h, ws)
    y_ref, s_ref, e = _moe_ref(ref, h, ws)
    _close(y, y_ref + s_ref)
    assert chosen.dtype == jnp.int32 and chosen.shape == (B, S, 3)
    assert np.array_equal(np.asarray(chosen).reshape(-1, 3), np.asarray(e))
    want = np.bincount(np.asarray(e).ravel(), minlength=16)
    assert counts.dtype == jnp.int32
    assert np.array_equal(np.asarray(counts), want)
    assert want.sum() == B * S * 3                  # pairs, not tokens
    first, n = KW["experts_held"]
    assert 0 < want[first:first + n].sum() < B * S * 3
    assert (want > 0).sum() >= 8                    # and spread


def test_routed_experts_gradients_match_reference(ref):
    h, ws = _stream(6, (B, S, 32)), _moe_weights(ref)
    w = _stream(8, (B, S, 32))
    got = jax.grad(lambda h, ws: jnp.sum(_moe_op(h, ws)[0] * w),
                   (0, 1))(h, ws)

    def whole(h, ws):
        y, s, _ = _moe_ref(ref, h, ws)
        return jnp.sum((y + s) * w)

    _grads_close(got, jax.grad(whole, (0, 1))(h, ws))


def _dense_topk(x, e, w, wg, wu, wd, first):
    """Every held expert over every token, a mask keeping its pairs."""
    y = jnp.zeros_like(x)
    for i in range(wg.shape[0]):
        mine = jnp.sum(jnp.where(e == first + i, w, 0.0), -1, keepdims=True)
        y = y + mine * ((jax.nn.silu(x @ wg[i].T) * (x @ wu[i].T)) @ wd[i].T)
    return y


@pytest.mark.parametrize("impl", [False, "interpret"])
@pytest.mark.parametrize("routing", ["even", "collapsed", "empty"])
def test_topk_dispatch_matches_the_masked_dense_form(routing, impl):
    """128 tokens, 3 choices of 16 experts, 4 held (4-7): the sorted
    rows' buffer has 128 or 384 rows.  Even routing fills the small one;
    collapsed, every token chooses three held experts and all 384 rows
    are real (nothing dropped); empty, no held expert is chosen and the
    layer returns 0 and no gradient.  The Pallas grouped matmul
    (interpreted) and XLA's ragged product alike."""
    from mxnet_tpu.parallel.moe import _row_buckets, dropless_topk_experts
    N, d, F, E, k, first, held = 128, 16, 8, 16, 3, 4, 4
    assert _row_buckets(N, k, held, E) == [128, 384]
    rng = np.random.default_rng(1)
    pick = {"even": np.arange(E), "collapsed": np.arange(4, 7),
            "empty": np.r_[0:4, 8:16]}[routing]
    e = jnp.asarray(np.stack([rng.permutation(pick)[:k] for _ in range(N)]),
                    jnp.int32)
    w = jax.nn.softmax(_stream(2, (N, k)), -1)
    x = _stream(3, (N, d))
    wg, wu = _stream(4, (held, F, d)), _stream(5, (held, F, d))
    wd = _stream(6, (held, d, F))
    cot = _stream(7, (N, d))

    def layer(x, w, wg, wu, wd):
        return dropless_topk_experts(x, e, w, wg, wu, wd, E, first,
                                     impl=impl)

    y, counts = layer(x, w, wg, wu, wd)
    assert np.array_equal(np.asarray(counts),
                          np.bincount(np.asarray(e).ravel(), minlength=E))
    here = int(counts[first:first + held].sum())
    assert here == {"collapsed": N * k, "empty": 0}.get(routing, here)
    if routing == "even":
        assert 0 < here <= 128
    _close(y, _dense_topk(x, e, w, wg, wu, wd, first), tol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(layer(*a)[0] * cot),
                   (0, 1, 2, 3, 4))(x, w, wg, wu, wd)
    want = jax.grad(lambda *a: jnp.sum(_dense_topk(a[0], e, *a[1:], first)
                                       * cot), (0, 1, 2, 3, 4))(x, w, wg, wu, wd)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        if routing == "empty":
            assert float(jnp.abs(a).max()) == 0.0
        _close(a, b, tol=5e-5)


def test_top1_is_the_k_equal_one_case():
    """``dropless_top1_experts`` is the merged routine at k = 1: one
    buffer size, one row a token."""
    from mxnet_tpu.parallel import moe
    N, d, F, E = 32, 8, 6, 4
    assert moe._row_buckets(N, 1, 2, E) == [N]
    prob = jax.nn.softmax(3.0 * _stream(1, (N, E)), -1)
    x = _stream(2, (N, d))
    wg, wu, wd = _stream(3, (2, F, d)), _stream(4, (2, F, d)), \
        _stream(5, (2, d, F))
    y1, c1 = moe.dropless_top1_experts(x, prob, wg, wu, wd, 1, impl=False)
    e = jnp.argmax(prob, -1).astype(jnp.int32)[:, None]
    yk, ck = moe.dropless_topk_experts(
        x, e, jnp.take_along_axis(prob, e, -1), wg, wu, wd, E, 1, impl=False)
    assert np.array_equal(np.asarray(y1), np.asarray(yk))
    assert np.array_equal(np.asarray(c1), np.asarray(ck))
    _close(y1, _dense_topk(x, e, jnp.take_along_axis(prob, e, -1), wg, wu,
                           wd, 1))


def test_grouped_matmul_tiles_follow_the_shapes():
    """One rule for both cells: ZAYA's 8192 rows over 8 groups of width
    2048 keep (512, 1024, 1024); 32 groups of width 512 get smaller row
    tiles and the widths they have."""
    from mxnet_tpu.parallel.moe import _gmm_tiling
    assert _gmm_tiling(8192, 8, 2048, 2048) == (512, 1024, 1024)
    assert _gmm_tiling(8192, 32, 2048, 512) == (256, 1024, 512)
    assert _gmm_tiling(8192, 32, 512, 2048) == (256, 512, 1024)
    assert _gmm_tiling(81920, 32, 2048, 512)[0] == 512
    assert _gmm_tiling(64, 32, 16, 16) == (64, 16, 16)


# ----------------------------------------------------------------------
# the shares add up to the layer
# ----------------------------------------------------------------------
def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer(ref):
    """Sixteen chips hold one expert each of this layer's 16 (the cell's
    sixteen hold 32 of 512 each): the routed parts of the sixteen, and
    the shared expert that every chip computes alike counted once, add
    up to what the uncut reference gives for the whole layer.  Chosen
    experts and counts are what every chip computes alike."""
    h, ws = _stream(10, (B, S, 32)), _moe_weights(ref)
    kw = dict(KW, experts_held=[0, 16])
    _, p = _params(ref, kw)
    ws[:3] = [p["layer1_" + n] * 20.0 for n in MOE_NAMES[:3]]
    y_whole, s_whole, e = _moe_ref(ref, h, ws, kw, held=(0, 16))
    no_shared = [jnp.zeros_like(w) if "shared" in n else w
                 for n, w in zip(MOE_NAMES, ws)]
    total = jnp.zeros_like(h)
    for first in range(16):
        part = [w[first:first + 1] if i < 3 else w
                for i, w in enumerate(no_shared)]
        y, chosen, counts = _moe_op(h, part, kw, held=(first, 1))
        assert np.array_equal(np.asarray(chosen).reshape(-1, 3),
                              np.asarray(e))
        assert np.array_equal(np.asarray(counts),
                              np.bincount(np.asarray(e).ravel(),
                                          minlength=16))
        total = total + y
    # the shared expert, from any one chip: its result less its routed part
    with_shared = [w[:1] if i < 3 else w for i, w in enumerate(ws)]
    one = [w[:1] if i < 3 else w for i, w in enumerate(no_shared)]
    shared = _moe_op(h, with_shared, kw, held=(0, 1))[0] \
        - _moe_op(h, one, kw, held=(0, 1))[0]
    _close(shared, s_whole, tol=5e-5)
    _close(total + shared, y_whole + s_whole, tol=5e-5)


# ----------------------------------------------------------------------
# the dense gated FFN (the shared expert's function)
# ----------------------------------------------------------------------
def test_gated_ffn_is_the_references(ref):
    from mxnet_tpu.parallel.moe import gated_ffn
    x = _stream(1, (B, S, 32))
    wg, wu, wd = _stream(2, (16, 32)), _stream(3, (16, 32)), _stream(4, (32, 16))
    _close(gated_ffn(x, wg, wu, wd),
           ref.gated_ffn(x.reshape(-1, 32), wg, wu, wd, "f32")
           .reshape(B, S, 32))


# ----------------------------------------------------------------------
# the model through Module.fit_step
# ----------------------------------------------------------------------
def test_layer_kinds_follow_the_published_period():
    from mxnet_tpu.models.qwen3_next import layer_kinds
    assert layer_kinds(8) == ["linear"] * 3 + ["full"] + ["linear"] * 3 \
        + ["full"]
    assert layer_kinds(2, 2) == ["linear", "full"]
    with pytest.raises(ValueError):
        layer_kinds(3, 0)


def test_symbol_parameters_are_the_references(ref):
    import mxnet_tpu as mx
    sym = mx.models.get_symbol("qwen3_next", **KW)
    assert sym.list_outputs() == ["softmax_output",
                                  "moe_expert_tokens_output"]
    arg_shapes, out_shapes, _ = sym.infer_shape(
        data=(B, S), softmax_label=(B * S,))
    got = dict(zip(sym.list_arguments(), arg_shapes))
    for name, shape in ref.param_specs(KW):
        assert tuple(got.pop(name)) == tuple(shape), name
    assert set(got) == {"data", "softmax_label"}
    assert [tuple(s) for s in out_shapes] == [(B * S, 96), (2, 16)]
    # an absent input stands before a present one in RoutedExperts'
    # declaration (the zaya router's): saved and loaded, the inputs keep
    # their names
    again = mx.sym.load_json(sym.tojson())
    assert again.list_arguments() == sym.list_arguments()
    assert again.infer_shape(data=(B, S), softmax_label=(B * S,))[0] \
        == arg_shapes
    # the default period: three linear layers, then a full one
    four = mx.models.get_symbol("qwen3_next", **dict(
        KW, num_layers=4, full_attention_interval=4)).list_arguments()
    assert "layer2_gdn_A_log" in four and "layer3_attn_q_weight" in four


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fit_steps_match_the_reference(ref, dtype):
    """``Module.fit_step`` with kvstore='tpu' and Adam, as the
    benchmark's driver drives it: fused, one dispatch a step, losses and
    every leaf's change against the reference's first steps; in bfloat16
    (multi_precision) within bfloat16's reach."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler, telemetry
    kw = dict(KW, dtype=dtype)
    low = dtype != "float32"
    key, weights = _params(ref, kw, seed=3)
    mod = mx.Module(mx.models.get_symbol("qwen3_next", **kw),
                    context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (B, S))],
             label_shapes=[("softmax_label", (B * S,))])

    class Seeded(mx.init.Initializer):
        def __call__(self, desc, arr):
            arr[:] = mx.nd.NDArray(weights[str(desc)].astype(arr.dtype),
                                   arr.context)

    mod.init_params(Seeded())
    exe = mod._exec_group._exec
    f32 = {n for n, _ in ref.param_specs(kw)
           if n.endswith(("router_weight", "A_log", "dt_bias"))
           or n == "tok_embed_weight"}
    assert {n for n, _ in ref.param_specs(kw)
            if str(exe.arg_dict[n].dtype) == "float32"} \
        == (f32 if low else {n for n, _ in ref.param_specs(kw)})
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
                               rtol=5e-3 if low else 1e-5)
    for name, shape in ref.param_specs(kw):
        w = exe.arg_dict[name]._data
        if low and w.dtype != jnp.float32:      # the float32 master
            w = mod._kvstore._updater.states[name][1]._data
        got = float(ref.train.delta_norm(key, name, tuple(shape), w, ref))
        assert got == pytest.approx(want["delta_norms"][name],
                                    rel=0.2 if low else 1e-3, abs=1e-7), name
    # the counts rode the step: (token, choice) pairs an expert
    counts = mod.get_outputs()[1].asnumpy()
    assert counts.shape == (2, 16) and counts.dtype == np.int32
    assert (counts.sum(axis=1) == B * S * kw["top_k"]).all()
    load = telemetry.moe.publish()
    first, n = kw["experts_held"]
    here = counts[:, first:first + n]
    reg = telemetry.REGISTRY
    assert reg.get("moe_expert_load_max_over_mean").value == pytest.approx(
        here.max() / here.mean())
    assert reg.get("moe_tokens_away").labels(layer=0).value \
        == B * S * kw["top_k"] - here[0].sum()
    assert np.array_equal(load["counts"], counts)
    # which arm each layer took, from the node's own top_k and rows_slack
    from mxnet_tpu.parallel.moe import _row_buckets
    size = _row_buckets(B * S, kw["top_k"], n, 16)[0]
    assert reg.get("moe_layers_over_size").value \
        == (here.sum(axis=1) > size).sum()


@pytest.mark.parametrize("held_pairs, k, slack, over", [
    ((0, 100, 128), 3, 1.25, 0),            # at the small size is inside it
    ((129, 40, 384), 3, 1.25, 2),
    ((129, 200, 384), 3, 1.6, 2),           # a buffer of 154 rows
    ((129, 200, 384), 3, 3.0, 1),           # ... of 288
    ((128, 128), 1, 1.25, 0)])              # top-1 has one size
def test_layers_over_size_counts_the_layers_that_ran_slabs(
        monkeypatch, held_pairs, k, slack, over):
    """``moe_layers_over_size`` from hand-made counts: 128 tokens, ``k``
    choices of 16 experts, 4 held (4-7), so a buffer of 128 rows at five
    quarters.  A layer whose held pairs fit reads 0; one pair more and
    the layer ran its pairs a slab at a time."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel.moe import _row_buckets
    N, E, first, held = 128, 16, 4, 4
    assert _row_buckets(N, 3, held, E) == [128, 384]
    assert _row_buckets(N, 3, held, E, 1.6) == [154, 384]
    assert _row_buckets(N, 3, held, E, 3.0) == [288, 384]
    counts = np.zeros((len(held_pairs), E), np.int32)
    for row, n in zip(counts, held_pairs):
        row[first:first + held] = [n - 3 * (n // 4)] + 3 * [n // 4]
        row[0] = N * k - n              # the other pairs are away
    monkeypatch.setattr(telemetry.moe, "_last", None)
    assert telemetry.moe.publish() is None
    telemetry.moe.note(counts, first, held, k, slack)
    load = telemetry.moe.publish()
    assert np.array_equal(load["counts"], counts)
    assert telemetry.REGISTRY.get("moe_layers_over_size").value == over
