"""SmallThinker's language model on the CPU at tiny widths, float32: the
grouped-query attention operator against the plain reference
(benchmark/reference/smallthinker.py), window and full, with and without
position, forward and every gradient; the band in the flash pair, the
kernels interpreted, against the XLA path (forward, dq, dk, dv, a
partial left block, whole skipped blocks, a query block before the band
is full, two key/value segments), and ``window=None`` bit-equal to the
kernels put together by hand as they were; the router's own stream
(``router_data``) and its gradient's path; ReLU gating, the slab arm
included; the share test of the ``model-configs`` guide, section 4 (four
shares of 16 experts add up to the uncut layer); a planted tie in the
router's logits; three ``Module.fit_step`` steps of
``models.get_symbol('smallthinker')`` against the reference's first
steps.
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
                       "smallthinker_21b_train.json")) as _f:
    REHEARSE = json.load(_f)["rehearse"]
KW = dict(REHEARSE["kwargs"])           # the cell's rehearsal sizes
B, S = 2, KW["seq_len"]
ATTN = ["attn_q_weight", "attn_k_weight", "attn_v_weight", "attn_o_weight"]
MOE = ["moe_gate_weight", "moe_up_weight", "moe_down_weight",
       "moe_router_weight"]


@pytest.fixture
def ref(monkeypatch):
    """The benchmark's reference modules, importable as run.py makes
    them."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    for m in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, m)
    from reference import smallthinker, train
    smallthinker.train = train
    return smallthinker


def _params(ref, kw=KW, seed=7):
    key = ref.seed_key(seed)
    return key, {n: ref.init_leaf(key, n, s) for n, s in ref.param_specs(kw)}


def _stream(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, \
        (float(np.abs(a - b).max()), scale)


def test_the_preset_has_both_kinds_of_layer_and_a_band_that_bites():
    assert KW["window_layout"] == KW["rope_layout"] == [0, 1, 1, 1]
    assert 0 < KW["window"] < S
    assert KW["q_heads"] == 7 * KW["kv_heads"]
    assert 0 < KW["experts_held"][1] < KW["num_experts"]


# ----------------------------------------------------------------------
# the attention operator
# ----------------------------------------------------------------------
@pytest.mark.parametrize("layer", [0, 1], ids=["full_no_position",
                                               "window_rotary"])
def test_operator_matches_reference_forward_and_every_gradient(ref, layer):
    """``GroupedQueryAttention`` with the attributes the model gives a
    layer of each kind, against the reference's sublayer: the result and
    the gradient of every input, the matrices scaled up so that the
    softmax is far from flat."""
    from mxnet_tpu.ops.nn import grouped_query_attention
    _, p = _params(ref)
    ws = [p["layer%d_%s" % (layer, n)] * 10.0 for n in ATTN]
    h = _stream(5, (B, S, KW["d_model"]))
    w = _stream(6, (B, S, KW["d_model"]))
    z = ref.dims(KW)

    def op(h, *ws):
        return grouped_query_attention(
            h, *ws, q_heads=KW["q_heads"], kv_heads=KW["kv_heads"],
            head_dim=KW["head_dim"],
            window=KW["window"] if z["windowed"][layer] else 0,
            rotary=bool(z["turned"][layer]), rope_theta=KW["rope_theta"])

    def plain(h, *ws):
        return ref.attention_sublayer(
            h, {"L_" + n: x for n, x in zip(ATTN, ws)}, "L_", layer, z,
            "f32", blk=8)

    run = lambda f: jax.value_and_grad(
        lambda *a: (f(*a) * w).sum(), argnums=tuple(range(5)))(h, *ws)
    _close(op(h, *ws), plain(h, *ws))
    for a, b in zip(jax.tree_util.tree_leaves(run(op)),
                    jax.tree_util.tree_leaves(run(plain))):
        _close(a, b, tol=5e-5)


def test_a_window_layer_forgets_what_a_full_layer_remembers(ref):
    """Moving the first token changes a full layer's last position and
    leaves a window layer's alone (the band has passed it); no layer
    sees a later token."""
    from mxnet_tpu.ops.nn import grouped_query_attention
    _, p = _params(ref)
    ws = [p["layer1_" + n] * 10.0 for n in ATTN]
    attrs = dict(q_heads=KW["q_heads"], kv_heads=KW["kv_heads"],
                 head_dim=KW["head_dim"], rope_theta=KW["rope_theta"])
    h = _stream(8, (1, S, KW["d_model"]))
    first = h.at[0, 0].add(1.0)
    last = h.at[0, -1].add(1.0)
    for window, moved in ((KW["window"], False), (0, True)):
        y = lambda x: grouped_query_attention(x, *ws, window=window, **attrs)
        base = y(h)
        assert (float(jnp.abs(y(first)[0, -1] - base[0, -1]).max()) > 0) \
            == moved
        assert float(jnp.abs(y(first)[0, KW["window"] - 1]
                             - base[0, KW["window"] - 1]).max()) > 0
        assert float(jnp.abs(y(last)[0, :-1] - base[0, :-1]).max()) == 0.0


# ----------------------------------------------------------------------
# the band in the flash pair (kernels interpreted)
# ----------------------------------------------------------------------
def _flash_operands(Hq, Hk, S_, dtype=jnp.float32, D=128):
    ks = jax.random.split(jax.random.PRNGKey(Hq * S_), 4)
    q = (jax.random.normal(ks[0], (1, Hq, S_, D)) * D ** -0.5).astype(dtype)
    k = jax.random.normal(ks[1], (1, Hk, S_, D)).astype(dtype)
    v = jax.random.normal(ks[2], (1, Hk, S_, D)).astype(dtype)
    return q, k, v, jax.random.normal(ks[3], (1, Hq, S_, D))


def _value_and_grads(fn, q, k, v, w):
    return jax.jit(jax.value_and_grad(
        lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(),
        argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("Hq,Hk,S_,window", [
    (2, 1, 2048, 512), (2, 1, 2048, 1024), (7, 1, 1024, 512)],
    ids=["w512_s2048", "w1024_s2048", "w512_s1024_7to1"])
def test_banded_flash_pair_matches_the_xla_path(Hq, Hk, S_, window):
    """Forward, dq, dk, dv of the banded kernels against the XLA path
    under the band's mask: four (or two) query blocks of 512, so the
    leftmost block of a band is masked in part, the blocks left of it
    are skipped whole, and the first query blocks have not filled their
    band yet; SmallThinker's seven query heads to a key/value head."""
    from mxnet_tpu.ops import nn
    q, k, v, w = _flash_operands(Hq, Hk, S_)
    got = _value_and_grads(lambda q, k, v: nn._flash_attention(
        q, k, v, window=window, interpret=True), q, k, v, w)
    want = _value_and_grads(lambda q, k, v: nn._grouped_causal_attention(
        q, k, v, 1.0, window), q, k, v, w)
    causal = _value_and_grads(lambda q, k, v: nn._grouped_causal_attention(
        q, k, v, 1.0), q, k, v, w)
    assert got[1][1].shape == k.shape and got[1][2].shape == v.shape
    for a, b, c in zip(jax.tree_util.tree_leaves(got),
                       jax.tree_util.tree_leaves(want),
                       jax.tree_util.tree_leaves(causal)):
        _close(a, b)
        # the band bites: plain causal attention gives something else
        assert float(np.abs(np.asarray(b) - np.asarray(c)).max()) \
            > 1e-3 * float(np.abs(np.asarray(b)).max())


def test_window_none_is_bit_equal_to_the_kernels_as_they_were():
    """``_flash_attention`` without a window, and with one no shorter
    than the sequence, against the pair put together by hand the way it
    was before there was a band: jax's splash forward under a
    ``CausalMask`` and ``flash_attention_backward`` with no window.
    Value and gradients are the same bits."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        CausalMask, MultiHeadMask, make_splash_mha)
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas.flash_backward import flash_attention_backward
    Hq, Hk, S_ = 2, 1, 1024
    q, k, v, w = _flash_operands(Hq, Hk, S_, jnp.bfloat16)
    kernel = make_splash_mha(
        MultiHeadMask([CausalMask((S_, S_))] * Hq), head_shards=1,
        q_seq_shards=1, interpret=True, save_residuals=True,
        block_sizes=nn._flash_block_sizes(S_))

    @jax.jit
    def by_hand(q, k, v):
        o, (lse,) = jax.vmap(kernel)(q, k, v)
        do = w.astype(o.dtype)
        return (o,) + tuple(flash_attention_backward(
            q, k, v, o, lse, do, interpret=True))

    want = by_hand(q, k, v)
    for window in (None, S_, 2 * S_):
        @jax.jit
        def pair(q, k, v):
            o, vjp = jax.vjp(lambda q, k, v: nn._flash_attention(
                q, k, v, window=window, interpret=True), q, k, v)
            return (o,) + tuple(vjp(w.astype(o.dtype)))

        for a, b in zip(pair(q, k, v), want):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))


def test_banded_backward_in_two_segments_is_the_unsegmented(monkeypatch):
    """The band's walk over key/value segments: with the budget cut so
    that a head's rows come in two passes, dq, dk, dv are those of one
    pass (a band's leftmost block may lie in the earlier segment)."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas import flash_backward as fb
    q, k, v, w = _flash_operands(2, 1, 2048)
    o, (lse,) = nn._flash_forward(q, k, v, True, True, 512)
    whole = fb.flash_attention_backward(q, k, v, o, lse, w, window=512,
                                        interpret=True)
    z = fb.plan(2048, 128, 128, jnp.float32)
    assert z.segments == 1
    monkeypatch.setattr(fb, "plan", lambda *a, **kw: fb.Plan(
        2, 1024, z.transposed, z.vmem_limit_bytes))
    fb._run_pass.clear_cache()
    halves = fb.flash_attention_backward(q, k, v, o, lse, w, window=512,
                                         interpret=True)
    fb._run_pass.clear_cache()
    for a, b in zip(halves, whole):
        _close(a, b, tol=1e-6)


def test_a_window_that_is_not_whole_blocks_is_refused_and_counted(
        monkeypatch):
    """The kernels raise on a band that is not whole blocks of 512; the
    gate sends such a layer to the XLA path and books it under
    ``pallas_fallbacks{reason="flash-window"}``, and only it."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas import dispatch
    from mxnet_tpu.pallas.flash_backward import flash_attention_backward
    q, k, v, w = _flash_operands(2, 1, 1024)
    with pytest.raises(ValueError, match="multiple of 512"):
        nn._flash_attention(q, k, v, window=300, interpret=True)
    with pytest.raises(ValueError, match="whole blocks"):
        flash_attention_backward(q, k, v, q, q[..., 0], w, window=300,
                                 interpret=True)
    monkeypatch.setattr(dispatch, "_compiles_here", lambda: (True, "", None))
    band = dispatch.PALLAS_FALLBACKS.labels(reason="flash-window")
    shape = dispatch.PALLAS_FALLBACKS.labels(reason="flash-geometry")
    before = band.value, shape.value
    assert nn._use_flash_attention(1024, 128, jnp.bfloat16, window=512)
    assert nn._use_flash_attention(1024, 128, jnp.bfloat16)
    assert (band.value, shape.value) == before
    assert nn._use_flash_attention(1024, 128, jnp.bfloat16,
                                   window=300) is False
    assert (band.value, shape.value) == (before[0] + 1, before[1])
    assert nn._use_flash_attention(1000, 128, jnp.bfloat16,
                                   window=300) is False
    assert (band.value, shape.value) == (before[0] + 1, before[1] + 1)


def test_block_counters_at_the_cells_geometry():
    """What the band saves at 16 384 rows and a window of 4096: the
    backward walks 252 of the 528 causal blocks of 512 a head, the
    forward's tables keep 70 of the 136 blocks of 1024; the trace-time
    counters book both, by kernel, in blocks of 512."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas import dispatch
    from mxnet_tpu.pallas.flash_backward import blocks_walked
    assert blocks_walked(16384) == 528 and blocks_walked(16384, 4096) == 252
    assert blocks_walked(8192) == 136 and blocks_walked(2048, 512) == 7
    walked = {n: dispatch.FLASH_BLOCKS_WALKED.labels(kernel=n)
              for n in ("flash_attention_window",
                        "flash_attention_window_bwd")}
    causal = {n: dispatch.FLASH_BLOCKS_CAUSAL.labels(kernel=n)
              for n in walked}
    before = {n: (walked[n].value, causal[n].value) for n in walked}
    nn._count_flash_blocks(1, 28, 16384, 4096, False)
    grew = {n: (walked[n].value - before[n][0],
                causal[n].value - before[n][1]) for n in walked}
    assert grew == {"flash_attention_window": (28 * 70 * 4, 28 * 136 * 4),
                    "flash_attention_window_bwd": (28 * 252, 28 * 528)}
    share = sum(g[0] for g in grew.values()) / sum(g[1] for g in grew.values())
    assert 0.49 < share < 0.50
    # a kernel a band built is kept apart from its causal twin
    assert nn._flash_kernel(4, 1024, True, True, 512) \
        is nn._flash_kernel(4, 1024, True, True, 512)
    assert nn._flash_kernel(4, 1024, True, True, 512) \
        is not nn._flash_kernel(4, 1024, True, True)


def test_flash_branch_of_the_operator_matches_its_xla_branch(monkeypatch):
    """The operator's two branches on a window layer with rotary and on
    a full layer without: the branch is steered as the chip would answer
    and the kernels interpreted; the result and every gradient agree
    (q carries the softmax scale on the flash branch only)."""
    from mxnet_tpu.ops import nn
    rng = np.random.RandomState(40)
    S_, D, Hq, Hk, d = 1024, 128, 2, 1, 64
    args = [jnp.asarray(rng.randn(1, S_, d), jnp.float32)] + [
        jnp.asarray(rng.randn(*s) * 0.2, jnp.float32)
        for s in ((Hq * D, d), (Hk * D, d), (Hk * D, d), (d, Hq * D))]
    w = jnp.asarray(rng.randn(1, S_, d), jnp.float32)
    for window, rotary in ((512, True), (0, False)):
        def run():
            return jax.value_and_grad(
                lambda *a: (nn.grouped_query_attention(
                    *a, q_heads=Hq, kv_heads=Hk, head_dim=D, window=window,
                    rotary=rotary) * w).sum(),
                argnums=tuple(range(5)))(*args)

        with monkeypatch.context() as m:
            want = run()
            m.setattr(nn, "_use_flash_attention", lambda *a, **k: "compiled")
            kernel = nn._flash_attention
            m.setattr(nn, "_flash_attention", lambda q, k, v, window=None:
                      kernel(q, k, v, window=window, interpret=True))
            got = run()
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            _close(a, b, tol=5e-5)


# ----------------------------------------------------------------------
# the expert layer: the router's own stream, ReLU, the shares, a tie
# ----------------------------------------------------------------------
def _moe_weights(ref, kw, scale=5.0, router=50.0):
    _, p = _params(ref, kw)
    return [p["layer1_" + n] * (router if n.endswith("router_weight")
                                else scale) for n in MOE]


def _routed(kw, first=None, count=None, act="relu"):
    """The model's expert layer as a function of (experts' stream,
    router's stream, [gate, up, down, router])."""
    from mxnet_tpu.ops.nn import routed_experts
    first = kw["experts_held"][0] if first is None else first
    count = kw["experts_held"][1] if count is None else count

    def layer(h, x, ws):
        return routed_experts(
            h, gate_weight=ws[0], up_weight=ws[1], down_weight=ws[2],
            router_weight=ws[3], router_data=x, router_stream=True,
            router="linear", act=act, top_k=kw["top_k"],
            num_experts=kw["num_experts"], held_first=first,
            held_count=count, num_hidden=kw["expert_dim"])
    return layer


def test_relu_experts_behind_the_routers_own_stream_match_the_reference(ref):
    """``act="relu"`` and ``router_data``: the result and the gradient
    of both streams and every weight against the reference's layer."""
    ws = _moe_weights(ref, KW)
    N, d = B * S, KW["d_model"]
    h, x, w = (_stream(s, (N, d)) for s in (11, 12, 13))
    z = ref.dims(KW)

    def plain(h, x, ws):
        return ref.experts(h, x, {"L_" + n: a for n, a in zip(MOE, ws)},
                           "L_", z, "f32")[0]

    layer = _routed(KW)
    run = lambda f: jax.value_and_grad(
        lambda h, x, ws: (f(h, x, ws) * w).sum(), argnums=(0, 1, 2))(h, x, ws)
    got = run(lambda h, x, ws: layer(h, x, ws)[0])
    want = run(plain)
    assert float(jnp.abs(want[1][1]).max()) > 0     # the router's path
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, tol=5e-5)
    # silu is another layer
    other = _routed(KW, act="silu")(h, x, ws)[0]
    assert float(jnp.abs(other - layer(h, x, ws)[0]).max()) > 1e-3


def test_relu_reaches_the_slab_arm(ref):
    """Every token sent to experts held here passes the smaller buffer,
    so the step runs its pairs a slab at a time, forward and backward:
    ReLU gates there too."""
    from mxnet_tpu.parallel import moe
    kw = dict(KW, num_experts=16, experts_held=[0, 4], top_k=3)
    ws = _moe_weights(ref, kw)
    N, d = 64, kw["d_model"]
    # the router strongly prefers experts 0..3: nearly every choice is held
    router = ws[3].at[:4].add(100.0 * jnp.ones((4, d)))
    h, w = _stream(21, (N, d)), _stream(22, (N, d))
    x = jnp.abs(_stream(23, (N, d)))
    buckets = moe._row_buckets(N, 3, 4, 16)
    assert len(buckets) == 2
    layer = _routed(kw)
    z = ref.dims(kw)
    ws = ws[:3] + [router]
    y, chosen, counts = layer(h, x, ws)
    assert int(counts[:4].sum()) > buckets[0]       # the worst-case arm
    run = lambda f: jax.value_and_grad(
        lambda h, x, ws: (f(h, x, ws) * w).sum(), argnums=(0, 1, 2))(h, x, ws)
    got = run(lambda h, x, ws: layer(h, x, ws)[0])
    want = run(lambda h, x, ws: ref.experts(
        h, x, {"L_" + n: a for n, a in zip(MOE, ws)}, "L_", z, "f32")[0])
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, tol=5e-5)


def test_the_routers_gradient_takes_its_own_stream_and_no_other(ref):
    """With ``router_data`` the router's weights get their gradient and
    hand one on to ``router_data``; the experts' stream gets none from
    the router: frozen experts' weights aside, d/d data with the router
    reading elsewhere is d/d data with the choice and weights held
    fixed.  Without ``router_data`` the operator is what it was."""
    from mxnet_tpu.ops.nn import routed_experts
    from mxnet_tpu.parallel import moe
    ws = _moe_weights(ref, KW)
    N, d = B * S, KW["d_model"]
    h, x, w = (_stream(s, (N, d)) for s in (31, 32, 33))
    first, held = KW["experts_held"]
    layer = _routed(KW)
    g_h, g_x, g_r = jax.grad(
        lambda h, x, r: (layer(h, x, ws[:3] + [r])[0] * w).sum(),
        argnums=(0, 1, 2))(h, x, ws[3])
    assert float(jnp.abs(g_x).max()) > 0 and float(jnp.abs(g_r).max()) > 0
    # the experts' stream: the gradient with the routing held constant
    chosen, weights = moe.linear_router(x, ws[3], KW["top_k"])
    fixed = jax.grad(lambda h: (moe.dropless_topk_experts(
        h, chosen, weights, *ws[:3], KW["num_experts"], first, act="relu")[0]
        * w).sum())(h)
    _close(g_h, fixed, tol=1e-6)
    # the router's stream: through the weights alone
    via = jax.grad(lambda x: (moe.dropless_topk_experts(
        h, chosen, moe.linear_router(x, ws[3], KW["top_k"])[1], *ws[:3],
        KW["num_experts"], first, act="relu")[0] * w).sum())(x)
    _close(g_x, via, tol=1e-6)
    # absent: the router reads data, as it did
    plain = dict(gate_weight=ws[0], up_weight=ws[1], down_weight=ws[2],
                 router_weight=ws[3], router="linear", top_k=KW["top_k"],
                 num_experts=KW["num_experts"], held_first=first,
                 held_count=held, num_hidden=KW["expert_dim"])
    was = routed_experts(h, **plain)
    same = routed_experts(h, router_data=h, router_stream=True, **plain)
    for a, b in zip(was, same):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    chosen_h, weights_h = moe.linear_router(h, ws[3], KW["top_k"])
    by_hand = moe.dropless_topk_experts(h, chosen_h, weights_h, *ws[:3],
                                        KW["num_experts"], first)
    assert np.array_equal(np.asarray(was[0]), np.asarray(by_hand[0]))
    with pytest.raises(ValueError, match="router='linear'"):
        routed_experts(h, **dict(plain, router="sigmoid", act="relu",
                                 router_bias=jnp.zeros(KW["num_experts"])))


def test_the_four_shares_add_up_to_the_uncut_expert_layer(ref):
    """The guide's share test: the parts that the four shares of a
    layer's 64 experts give (16 held each, the router scoring all 64 and
    the weights normalised over all ``top_k``) add up to the uncut
    reference layer's result."""
    kw = dict(KW, num_experts=64, top_k=6, experts_held=[0, 64])
    ws = _moe_weights(ref, kw)
    N, d = B * S, kw["d_model"]
    h, x = _stream(6, (N, d)), _stream(7, (N, d))
    whole, _ = ref.experts(h, x, {"L_" + n: a for n, a in zip(MOE, ws)},
                           "L_", ref.dims(kw), "f32")
    assert float(jnp.abs(whole).max()) > 0
    total = 0.0
    for first in range(0, 64, 16):
        part_ws = [a[first:first + 16] for a in ws[:3]] + [ws[3]]
        y = _routed(kw, first, 16)(h, x, part_ws)[0]
        part, _ = ref.experts(
            h, x, {"L_" + n: a for n, a in zip(MOE, part_ws)}, "L_",
            ref.dims(dict(kw, experts_held=[first, 16])), "f32")
        _close(y, part, tol=5e-5)
        total = total + y
    _close(total, whole, tol=5e-5)


def test_a_planted_tie_goes_to_the_lower_index(ref):
    """Two experts' router rows made equal: every token's logits tie
    there, and both program and reference give the pair's lower index
    the place when only one of them fits."""
    kw = dict(KW, experts_held=[0, KW["num_experts"]])
    ws = _moe_weights(ref, kw)
    lo, hi = 5, 9
    router = ws[3].at[hi].set(ws[3][lo])
    N, d = B * S, kw["d_model"]
    h, x = _stream(41, (N, d)), _stream(42, (N, d))
    ws = ws[:3] + [router]
    y, chosen, _ = _routed(kw)(h, x, ws)
    want, e = ref.experts(h, x, {"L_" + n: a for n, a in zip(MOE, ws)},
                          "L_", ref.dims(kw), "f32")
    chosen, e = np.asarray(chosen), np.asarray(e)
    assert np.array_equal(chosen, e)
    has_lo, has_hi = (chosen == lo).any(-1), (chosen == hi).any(-1)
    assert (has_lo & ~has_hi).any()         # the tie at the k-th place
    assert not (has_hi & ~has_lo).any()     # never the higher alone
    both = has_lo & has_hi
    at = lambda i: np.argmax(chosen == i, -1)
    assert (at(lo)[both] < at(hi)[both]).all()
    _close(y, want, tol=5e-5)


# ----------------------------------------------------------------------
# the model through Module.fit_step
# ----------------------------------------------------------------------
def test_symbol_parameters_and_outputs_are_the_references(ref):
    import mxnet_tpu as mx
    sym = mx.models.get_symbol("smallthinker", **KW)
    assert sym.list_outputs() == ["softmax_output",
                                  "moe_expert_tokens_output"]
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(
        data=(B, S), softmax_label=(B * S,))
    got = dict(zip(sym.list_arguments(), arg_shapes))
    for name, shape in ref.param_specs(KW):
        assert tuple(got.pop(name)) == tuple(shape), name
    assert set(got) == {"data", "softmax_label"}
    assert not sym.list_auxiliary_states() and not aux_shapes
    assert [tuple(s) for s in out_shapes] == [
        (B * S, KW["num_classes"]), (KW["num_layers"], KW["num_experts"])]
    again = mx.sym.load_json(sym.tojson())
    assert again.list_arguments() == sym.list_arguments()
    assert again.infer_shape(data=(B, S), softmax_label=(B * S,))[1] \
        == out_shapes
    # each layer carries its own kind
    nodes = {n["name"]: n for n in json.loads(sym.tojson())["nodes"]}
    kinds = [(nodes["layer%d_attn" % i]["attrs"]["window"],
              nodes["layer%d_attn" % i]["attrs"]["rotary"])
             for i in range(KW["num_layers"])]
    assert kinds == [("0", "False")] + [(str(KW["window"]), "True")] * 3
    assert nodes["layer2_moe"]["attrs"]["act"] == "relu"
    with pytest.raises(ValueError):
        mx.models.get_symbol("smallthinker", **dict(KW, experts_held=[12, 8]))
    with pytest.raises(ValueError):
        mx.models.get_symbol("smallthinker",
                             **dict(KW, window_layout=[0, 1, 1]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fit_steps_match_the_reference(ref, dtype):
    """``Module.fit_step`` with kvstore='tpu' and Adam at the cell's
    rehearsal sizes, as the benchmark's driver drives it: fused, one
    dispatch a step, losses and every leaf's first gradient and change
    against the reference's first steps; in bfloat16 (multi_precision)
    within bfloat16's reach."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    kw = dict(KW, dtype=dtype)
    low = dtype != "float32"
    key, weights = _params(ref, kw, seed=3)
    mod = mx.Module(mx.models.get_symbol("smallthinker", **kw),
                    context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (B, S))],
             label_shapes=[("softmax_label", (B * S,))])

    class Seeded(mx.init.Initializer):
        def __call__(self, desc, arr):
            arr[:] = mx.nd.NDArray(weights[str(desc)].astype(arr.dtype),
                                   arr.context)

    mod.init_params(Seeded())
    exe = mod._exec_group._exec
    names = [n for n, _ in ref.param_specs(kw)]
    f32 = {n for n in names
           if n.endswith("router_weight") or n == "tok_embed_weight"}
    assert {n for n in names if str(exe.arg_dict[n].dtype) == "float32"} \
        == (f32 if low else set(names))
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
    states = mod._kvstore._updater.states
    got_delta = {}
    for name, shape in ref.param_specs(kw):
        st = states[name]
        w = exe.arg_dict[name]._data
        if low and str(w.dtype) != "float32":
            st, master = st
            w = master._data
        got_delta[name] = float(ref.train.delta_norm(key, name, tuple(shape),
                                                     w, ref))
    gaps = ref.train.leaf_gaps(got_delta, want["delta_norms"])
    worst, at = ref.train.worst_gap(gaps)
    assert worst <= (5e-2 if low else 1e-3), (worst, at)
    from mxnet_tpu.telemetry import moe as moe_counts
    load = moe_counts.publish()
    assert load["counts"].shape == (kw["num_layers"], kw["num_experts"])
    assert int(load["counts"].sum()) == kw["num_layers"] * B * S * kw["top_k"]
