"""Kanana-2 on the CPU at tiny widths, float32: each new operator against
the plain reference (benchmark/reference/kanana2.py), forward and
gradients: latent attention on the XLA path and with the flash kernel
interpreted at key width 192 / value width 128; the sigmoid router with
its selection bias; the expert sublayer with an ungated shared expert
against the masked dense form; the share test of the ``model-configs``
guide, section 4 (the routed parts of all eight shares plus the shared
expert once are the uncut layer); the dense gated FFN; three
``Module.fit_step`` steps of ``models.get_symbol('kanana2')`` against
the reference's first steps, the bias an auxiliary state that no step
moves and no optimizer knows; the flash kernel's geometries.
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
                       "kanana2_30b_train.json")) as _f:
    REHEARSE = json.load(_f)["rehearse"]
KW = dict(REHEARSE["kwargs"])           # the cell's rehearsal sizes
B, S = 2, KW["seq_len"]


@pytest.fixture
def ref(monkeypatch):
    """The benchmark's reference modules, importable as run.py makes
    them."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    for m in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, m)
    from reference import kanana2, train
    kanana2.train = train
    return kanana2


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
# latent attention against the reference, forward and gradients
# ----------------------------------------------------------------------
MLA_NAMES = ["attn_q_weight", "attn_kva_weight", "attn_kv_norm_gamma",
             "attn_kvb_weight", "attn_o_weight"]


def _mla_kw(kw):
    return dict(heads=kw["heads"], nope_dim=kw["nope_dim"],
                rope_dim=kw["rope_dim"], v_dim=kw["v_dim"],
                kv_rank=kw["kv_rank"], rope_theta=kw["rope_theta"])


def _mla_weights(ref, kw=KW, scale=10.0):
    """A layer's mixer weights; matrices scaled up from normal(0, 0.02)
    so that the softmax is far from flat, the gain moved off 1."""
    _, p = _params(ref, kw)
    ws = []
    for i, n in enumerate(MLA_NAMES):
        w = p["layer1_" + n]
        ws.append(w + 0.1 * _stream(40 + i, w.shape)
                  if n.endswith("_gamma") else w * scale)
    return ws


def _mla_ref(ref, h, ws, kw=KW):
    p = {"L_" + n: w for n, w in zip(MLA_NAMES, ws)}
    return ref.latent_attention(h, p, "L_", ref.dims(kw), "f32")


def test_latent_attention_matches_reference(ref):
    from mxnet_tpu.ops.nn import latent_attention
    op = jax.jit(lambda h, ws: latent_attention(h, *ws, **_mla_kw(KW)))
    h, ws = _stream(4, (B, S, KW["d_model"])), _mla_weights(ref)
    w = _stream(5, (B, S, KW["d_model"]))
    _close(op(h, ws), _mla_ref(ref, h, ws))
    _grads_close(
        jax.jit(jax.grad(lambda h, ws: jnp.sum(op(h, ws) * w), (0, 1)))(h, ws),
        jax.jit(jax.grad(lambda h, ws: jnp.sum(_mla_ref(ref, h, ws) * w),
                         (0, 1)))(h, ws))


def test_latent_attention_with_the_flash_kernel_at_192_and_128(
        ref, monkeypatch):
    """The cell's head geometry (queries and keys 128 + 64 = 192 wide,
    values 128) on 2 heads and 512 tokens, the flash kernel interpreted:
    forward and every gradient against the reference's layer."""
    from mxnet_tpu.ops import nn
    kw = dict(KW, d_model=64, heads=2, nope_dim=128, rope_dim=64, v_dim=128,
              kv_rank=32, seq_len=512)
    monkeypatch.setattr(nn, "_use_flash_attention",
                        lambda *a, **k: "interpret")
    flash = nn._flash_attention
    monkeypatch.setattr(nn, "_flash_attention", lambda q, k, v, window=None:
                        flash(q, k, v, window=window, interpret=True))
    seen = []
    kernel = nn._flash_kernel
    monkeypatch.setattr(nn, "_flash_kernel",
                        lambda *a: seen.append(a) or kernel(*a))
    op = lambda h, ws: nn.latent_attention(h, *ws, **_mla_kw(kw))
    h, ws = _stream(4, (1, 512, 64)), _mla_weights(ref, kw, scale=4.0)
    w = _stream(5, (1, 512, 64))
    _close(op(h, ws), _mla_ref(ref, h, ws, kw), tol=1e-4)
    # the value alone, then under the gradient with the rows' log-sum-exp
    assert set(seen) == {(2, 512, True, False)}
    got = jax.grad(lambda h, ws: jnp.sum(op(h, ws) * w), (0, 1))(h, ws)
    assert set(seen) == {(2, 512, True, False), (2, 512, True, True)}
    _grads_close(
        got,
        jax.jit(jax.grad(lambda h, ws: jnp.sum(_mla_ref(ref, h, ws, kw) * w),
                         (0, 1)))(h, ws), tol=2e-4)


def test_latent_attention_is_causal(ref):
    """Changing position t changes nothing before it and something at
    it."""
    from mxnet_tpu.ops.nn import latent_attention
    op = jax.jit(lambda h, ws: latent_attention(h, *ws, **_mla_kw(KW)))
    h, ws = _stream(4, (B, S, KW["d_model"])), _mla_weights(ref)
    a, b = op(h, ws), op(h.at[:, 7].add(1.0), ws)
    assert float(jnp.abs(a[:, :7] - b[:, :7]).max()) == 0.0
    assert float(jnp.abs(a[:, 7] - b[:, 7]).max()) > 1e-5


def test_interleaved_rotary_pairs_neighbours(ref):
    from mxnet_tpu.ops.nn import _rotary_interleaved
    x = _stream(3, (B, 2, S, 8))
    got = _rotary_interleaved(x, 1e6)
    want = ref.rotary_interleaved(jnp.moveaxis(x, 1, 2), 1e6)
    _close(got, jnp.moveaxis(want, 2, 1), tol=1e-6)
    # position 0 is the identity, and a pair keeps its length
    _close(got[:, :, 0], x[:, :, 0], tol=1e-7)
    pair = lambda t: jnp.square(t.reshape(t.shape[:-1] + (4, 2))).sum(-1)
    _close(pair(got), pair(x), tol=1e-5)


# ----------------------------------------------------------------------
# the sigmoid router with a selection bias
# ----------------------------------------------------------------------
def test_sigmoid_router_matches_reference_route(ref):
    from mxnet_tpu.parallel.moe import sigmoid_router
    z = ref.dims(KW)
    h = _stream(1, (B * S, KW["d_model"]))
    w = _stream(2, (z["E"], KW["d_model"])) * 0.3
    bias = 0.05 * _stream(3, (z["E"],))
    e, wt = sigmoid_router(h, w, bias, z["k"], z["scale"])
    e_ref, w_ref = ref.route(h, {"L_moe_router_weight": w}, bias, "L_", z)
    assert e.dtype == jnp.int32
    assert np.array_equal(np.asarray(e), np.asarray(e_ref))
    _close(wt, w_ref, tol=1e-6)
    _close(wt.sum(-1), jnp.full((B * S,), z["scale"]), tol=1e-5)


def test_bias_flips_choices_and_leaves_the_weights_formula(ref):
    """With the bias some tokens choose other experts than without; the
    weights of whatever is chosen are the plain scores there, normalised
    and scaled: the bias is in no weight.  No gradient reaches it, and
    the router's weight gets one."""
    from mxnet_tpu.parallel.moe import sigmoid_router
    z = ref.dims(KW)
    h = _stream(1, (B * S, KW["d_model"]))
    w = _stream(2, (z["E"], KW["d_model"])) * 0.3
    bias = 0.05 * _stream(3, (z["E"],))
    e0, _ = sigmoid_router(h, w, jnp.zeros_like(bias), z["k"], z["scale"])
    e1, w1 = sigmoid_router(h, w, bias, z["k"], z["scale"])
    flipped = np.asarray((jnp.sort(e0, -1) != jnp.sort(e1, -1)).any(-1))
    assert 0 < flipped.mean() < 1
    score = jax.nn.sigmoid(jnp.einsum("nd,ed->ne", h, w, precision="highest"))
    at = jnp.take_along_axis(score, e1, -1)
    _close(w1, at / at.sum(-1, keepdims=True) * z["scale"], tol=1e-6)
    cot = _stream(4, w1.shape)
    gw, gb = jax.grad(lambda w, b: jnp.sum(
        sigmoid_router(h, w, b, z["k"], z["scale"])[1] * cot), (0, 1))(w, bias)
    assert float(jnp.abs(gb).max()) == 0.0
    assert float(jnp.abs(gw).max()) > 0.0
    # ties go to the lower index: equal scores, no bias
    e, _ = sigmoid_router(jnp.zeros((3, 8)), jnp.zeros((5, 8)),
                          jnp.zeros((5,)), 2, 1.0)
    assert np.array_equal(np.asarray(e), [[0, 1]] * 3)


# ----------------------------------------------------------------------
# the expert sublayer: sigmoid router, top-k, ungated shared expert
# ----------------------------------------------------------------------
MOE_NAMES = ["moe_gate_weight", "moe_up_weight", "moe_down_weight",
             "moe_router_weight", "moe_shared_gate_weight",
             "moe_shared_up_weight", "moe_shared_down_weight"]
MOE_INPUTS = ["gate_weight", "up_weight", "down_weight", "router_weight",
              "shared_gate_weight", "shared_up_weight", "shared_down_weight"]


def _moe_weights(ref, kw=KW, scale=20.0):
    _, p = _params(ref, kw)
    return [p["layer1_" + n] * scale for n in MOE_NAMES]


def _bias(ref, kw=KW, scale=10.0):
    return ref.init_aux(kw)["layer1_moe_router_bias"] * scale


def _moe_op(h, ws, bias, kw=KW, held=None):
    from mxnet_tpu.ops.nn import routed_experts
    first, count = held or kw["experts_held"]
    return routed_experts(
        h, **dict(zip(MOE_INPUTS, ws)), router_bias=bias, router="sigmoid",
        top_k=kw["top_k"], route_scale=kw["route_scale"],
        num_experts=kw["num_experts"], held_first=first, held_count=count,
        num_hidden=kw["expert_dim"], shared_hidden=kw["shared_dim"],
        shared_gate=False)


def _moe_ref(ref, h, ws, bias, kw=KW, held=None):
    p = {"L_" + n: w for n, w in zip(MOE_NAMES, ws)}
    z = ref.dims(dict(kw, experts_held=list(held or kw["experts_held"])))
    y, s, e = ref.experts(h.reshape(-1, h.shape[-1]), p, bias, "L_", z, "f32")
    return y.reshape(h.shape), s.reshape(h.shape), e


def test_routed_experts_with_the_sigmoid_router_matches_reference(ref):
    """Forward (the masked dense form: every held expert over every
    token), the chosen experts, the counts, and every gradient; the
    fourth output is the bias as it came."""
    h, ws = _stream(6, (B, S, KW["d_model"])), _moe_weights(ref)
    bias = _bias(ref)
    y, chosen, counts, kept = _moe_op(h, ws, bias)
    y_ref, s_ref, e = _moe_ref(ref, h, ws, bias)
    _close(y, y_ref + s_ref)
    k, E = KW["top_k"], KW["num_experts"]
    assert chosen.dtype == jnp.int32 and chosen.shape == (B, S, k)
    assert np.array_equal(np.asarray(chosen).reshape(-1, k), np.asarray(e))
    want = np.bincount(np.asarray(e).ravel(), minlength=E)
    assert np.array_equal(np.asarray(counts), want)
    assert want.sum() == B * S * k                  # pairs, not tokens
    first, n = KW["experts_held"]
    assert 0 < want[first:first + n].sum() < B * S * k
    assert np.array_equal(np.asarray(kept), np.asarray(bias))
    # the bias changed somebody's choice (or the test says nothing of it)
    e0 = _moe_ref(ref, h, ws, jnp.zeros_like(bias))[2]
    assert (np.sort(np.asarray(e0), -1) != np.sort(np.asarray(e), -1)).any()
    w = _stream(8, h.shape)
    got = jax.grad(lambda h, ws, b: jnp.sum(_moe_op(h, ws, b)[0] * w),
                   (0, 1, 2))(h, ws, bias)

    def whole(h, ws):
        y, s, _ = _moe_ref(ref, h, ws, bias)
        return jnp.sum((y + s) * w)

    _grads_close(got[:2], jax.grad(whole, (0, 1))(h, ws))
    assert float(jnp.abs(got[2]).max()) == 0.0


def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(ref):
    """Eight chips hold two experts each of this layer's 16 (the cell's
    eight hold 16 of 128 each): the routed parts of the eight, and the
    shared expert that every chip computes alike counted once, add up
    to what the uncut reference gives for the whole layer.  Chosen
    experts and counts are what every chip computes alike."""
    kw = dict(KW, experts_held=[0, 16])
    assert kw["num_experts"] == 16
    h, ws = _stream(10, (B, S, kw["d_model"])), _moe_weights(ref, kw)
    bias, k = _bias(ref, kw), kw["top_k"]
    y_whole, s_whole, e = _moe_ref(ref, h, ws, bias, kw, held=(0, 16))
    no_shared = [jnp.zeros_like(w) if "shared" in n else w
                 for n, w in zip(MOE_NAMES, ws)]
    total = jnp.zeros_like(h)
    for first in range(0, 16, 2):
        part = [w[first:first + 2] if i < 3 else w
                for i, w in enumerate(no_shared)]
        y, chosen, counts, _ = _moe_op(h, part, bias, kw, held=(first, 2))
        assert np.array_equal(np.asarray(chosen).reshape(-1, k),
                              np.asarray(e))
        assert np.array_equal(np.asarray(counts),
                              np.bincount(np.asarray(e).ravel(),
                                          minlength=16))
        total = total + y
    # the shared expert, from any one chip: its result less its routed part
    with_shared = [w[:2] if i < 3 else w for i, w in enumerate(ws)]
    one = [w[:2] if i < 3 else w for i, w in enumerate(no_shared)]
    shared = _moe_op(h, with_shared, bias, kw, held=(0, 2))[0] \
        - _moe_op(h, one, bias, kw, held=(0, 2))[0]
    _close(shared, s_whole, tol=5e-5)
    _close(total + shared, y_whole + s_whole, tol=5e-5)


# ----------------------------------------------------------------------
# the dense gated FFN as a Symbol operator
# ----------------------------------------------------------------------
def test_gated_ffn_operator_is_gated_ffn(ref):
    import mxnet_tpu as mx
    from mxnet_tpu.ops.nn import gated_ffn_op
    from mxnet_tpu.parallel.moe import gated_ffn
    d, F = KW["d_model"], KW["dense_dim"]
    x = _stream(1, (B, S, d))
    wg, wu, wd = _stream(2, (F, d)), _stream(3, (F, d)), _stream(4, (d, F))
    want = gated_ffn(x, wg, wu, wd)
    assert np.array_equal(np.asarray(gated_ffn_op(x, wg, wu, wd,
                                                  num_hidden=F)),
                          np.asarray(want))
    _close(want, ref.gated_ffn(x, wg, wu, wd, "f32"))
    net = mx.sym.contrib.GatedFFN(mx.sym.Variable("data"), num_hidden=F,
                                  name="ffn")
    assert net.list_arguments() == ["data", "ffn_gate_weight",
                                    "ffn_up_weight", "ffn_down_weight"]
    shapes, out, _ = net.infer_shape(data=(B, S, d))
    assert [tuple(s) for s in shapes[1:]] == [(F, d), (F, d), (d, F)]
    assert tuple(out[0]) == (B, S, d)
    eager = mx.nd.contrib.GatedFFN(mx.nd.NDArray(x), mx.nd.NDArray(wg),
                                   mx.nd.NDArray(wu), mx.nd.NDArray(wd),
                                   num_hidden=F)
    _close(eager.asnumpy(), want, tol=1e-6)


# ----------------------------------------------------------------------
# the flash kernel's geometries
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell,S_,D,Dv,Hq,Hk,held", [
    ("cgpt13b_train_s2048", 2048, 128, None, 16, 16, 6.3e6),
    ("zaya1_8b_train_ep2", 8192, 128, None, 8, 2, 25.2e6),
    ("qwen3next_80b_train_ep16", 8192, 256, None, 16, 2, 50.3e6),
    ("kanana2_30b_train_ep8", 8192, 192, 128, 32, 32, 38.8e6),
])
def test_flash_geometries_of_the_cells(monkeypatch, cell, S_, D, Dv, Hq, Hk,
                                       held):
    """The tiles, the backward's plan and the gate's answer for the
    geometries the four language-model cells use: the forward's tiles are
    what they were, the backward holds a key/value head's whole sequence
    in one segment whatever the head counts (a width of 192 takes the
    lanes of 256 and has dq and dk summed transposed), and its VMEM
    limit is what those rows take beside the working room; the program
    of a gradient at the cell's shapes calls the repo's backward."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas import dispatch, flash_backward as fb
    bs = nn._flash_block_sizes(S_)
    assert (bs.block_q, bs.block_kv, bs.block_kv_compute) == (1024, 1024, 512)
    assert not bs.has_backward_blocks
    z = fb.plan(S_, D, Dv or D, jnp.bfloat16)
    assert (z.segments, z.rows, z.transposed) == (1, S_, D == 192)
    assert abs(z.vmem_limit_bytes - fb._WORKING - held) < 0.05e6
    assert z.vmem_limit_bytes < 100 << 20      # of the v5e's 128 MiB
    # float32 operands at the widest geometry pass the budget: two passes
    assert fb.plan(8192, 256, 256, jnp.float32)[:2] == (2, 4096)
    q = jax.ShapeDtypeStruct((1, Hq, S_, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, Hk, S_, D), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, Hk, S_, Dv or D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: nn._flash_attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    assert "flash_attention_backward" in str(jaxpr)
    assert "splash_mha_dkv" not in str(jaxpr)
    # the gate, asked as a one-device TPU program would be
    monkeypatch.setattr(dispatch, "_compiles_here",
                        lambda: (True, "", None))
    gate = lambda *a: nn._use_flash_attention(*a)
    assert gate(S_, D, jnp.bfloat16, *(() if Dv is None else (Dv,)))
    assert not gate(S_, 96, jnp.bfloat16)           # under a lane tile
    assert not gate(S_, 192, jnp.bfloat16, 64)      # values fill lane tiles
    assert not gate(S_ + 256, D, jnp.bfloat16)


# ----------------------------------------------------------------------
# the model through Module.fit_step
# ----------------------------------------------------------------------
def test_symbol_parameters_and_aux_states_are_the_references(ref):
    import mxnet_tpu as mx
    sym = mx.models.get_symbol("kanana2", **KW)
    assert sym.list_outputs() == ["softmax_output",
                                  "moe_expert_tokens_output"]
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(
        data=(B, S), softmax_label=(B * S,))
    got = dict(zip(sym.list_arguments(), arg_shapes))
    for name, shape in ref.param_specs(KW):
        assert tuple(got.pop(name)) == tuple(shape), name
    assert set(got) == {"data", "softmax_label"}
    aux = ref.init_aux(KW)
    assert sym.list_auxiliary_states() == sorted(aux)
    assert [tuple(s) for s in aux_shapes] == [(KW["num_experts"],)] * len(aux)
    n_moe = KW["num_layers"] - KW["dense_layers"]
    assert len(aux) == n_moe
    assert [tuple(s) for s in out_shapes] == [(B * S, KW["num_classes"]),
                                             (n_moe, KW["num_experts"])]
    # layer 0 is dense, the others are not
    args = sym.list_arguments()
    assert "layer0_ffn_gate_weight" in args
    assert "layer0_moe_gate_weight" not in args
    assert "layer1_moe_gate_weight" in args
    assert "layer1_ffn_gate_weight" not in args
    assert not [a for a in args if "shared_sg" in a]
    # saved and loaded, inputs and aux states keep their names
    again = mx.sym.load_json(sym.tojson())
    assert again.list_arguments() == args
    assert again.list_auxiliary_states() == sym.list_auxiliary_states()
    with pytest.raises(ValueError):
        mx.models.get_symbol("kanana2", **dict(KW, dense_layers=KW[
            "num_layers"]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fit_steps_match_the_reference(ref, dtype):
    """``Module.fit_step`` with kvstore='tpu' and Adam at the cell's
    rehearsal sizes, as the benchmark's driver drives it: fused, one
    dispatch a step, losses and every leaf's change against the
    reference's first steps; in bfloat16 (multi_precision) within
    bfloat16's reach.  The bias is the same after the steps as before
    them, and the optimizer holds nothing for it."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler, telemetry
    kw = dict(KW, dtype=dtype)
    low = dtype != "float32"
    key, weights = _params(ref, kw, seed=3)
    aux0 = ref.init_aux(kw)
    # the fused step donates what the module holds, and on the CPU the
    # module may hold these very buffers: the values, on the host
    bias0 = {n: np.asarray(b) for n, b in aux0.items()}
    mod = mx.Module(mx.models.get_symbol("kanana2", **kw),
                    context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (B, S))],
             label_shapes=[("softmax_label", (B * S,))])
    values = dict(weights, **aux0)

    class Seeded(mx.init.Initializer):
        def __call__(self, desc, arr):
            arr[:] = mx.nd.NDArray(values[str(desc)].astype(arr.dtype),
                                   arr.context)

    mod.init_params(Seeded())
    exe = mod._exec_group._exec
    f32 = {n for n, _ in ref.param_specs(kw)
           if n.endswith("router_weight") or n == "tok_embed_weight"}
    assert {n for n, _ in ref.param_specs(kw)
            if str(exe.arg_dict[n].dtype) == "float32"} \
        == (f32 if low else {n for n, _ in ref.param_specs(kw)})
    assert sorted(exe.aux_dict) == sorted(aux0)
    assert all(str(a.dtype) == "float32" for a in exe.aux_dict.values())
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
    for name, shape in ref.param_specs(kw):
        w = exe.arg_dict[name]._data
        if low and w.dtype != jnp.float32:      # the float32 master
            w = states[name][1]._data
        got = float(ref.train.delta_norm(key, name, tuple(shape), w, ref))
        assert got == pytest.approx(want["delta_norms"][name],
                                    rel=0.2 if low else 1e-3, abs=1e-7), name
    # the bias: carried through three steps unchanged, unknown to Adam
    for name, b in bias0.items():
        assert float(np.abs(b).max()) > 0
        assert np.array_equal(exe.aux_dict[name].asnumpy(), b)
        assert name not in states
        assert name not in mod._exec_group._exec.grad_dict
    assert set(states) == {n for n, _ in ref.param_specs(kw)}
    # the counts rode the step: (token, choice) pairs an expert
    n_moe = kw["num_layers"] - kw["dense_layers"]
    counts = mod.get_outputs()[1].asnumpy()
    assert counts.shape == (n_moe, kw["num_experts"])
    assert counts.dtype == np.int32
    assert (counts.sum(axis=1) == B * S * kw["top_k"]).all()
    load = telemetry.moe.publish()
    first, n = kw["experts_held"]
    here = counts[:, first:first + n]
    reg = telemetry.REGISTRY
    assert reg.get("moe_expert_load_max_over_mean").value == pytest.approx(
        here.max() / here.mean())
    assert np.array_equal(load["counts"], counts)
