"""Keye-VL-2.0's language model on the CPU at tiny widths, float32: the
sparse indexed attention against the plain reference
(benchmark/reference/keye_vl2.py), forward, the index loss and every
gradient, over several query blocks and at a padded length, with the
heads' cores and the scorer as the XLA loops and once more as the Pallas
kernels in interpret mode (``cores``); the choice against a sort on the host, planted ties included; with ``topk >= S`` the
operator is dense grouped causal attention; the two objectives keep to
their own leaves EXACTLY (the cross-entropy gives the scorer's leaves
zero, the index loss gives every other leaf zero); the share test of the
``model-configs`` guide, section 4 (the expert layer's parts from all
eight shares add up to the uncut layer); three ``Module.fit_step`` steps
of ``models.get_symbol('keye_vl2')`` against the reference's first
steps, one fit program a step with two loss heads.
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
                       "keye_vl2_30b_train.json")) as _f:
    REHEARSE = json.load(_f)["rehearse"]
KW = dict(REHEARSE["kwargs"])           # the cell's rehearsal sizes
B, S = 2, KW["seq_len"]

ATTN_NAMES = ["attn_q_weight", "attn_k_weight", "attn_v_weight",
              "attn_q_norm_gamma", "attn_k_norm_gamma", "attn_o_weight",
              "attn_idx_q_weight", "attn_idx_k_weight", "attn_idx_w_weight",
              "attn_idx_k_norm_gamma", "attn_idx_k_norm_beta"]
SCORER = [i for i, n in enumerate(ATTN_NAMES) if "_idx_" in n]
MAIN = [i for i, n in enumerate(ATTN_NAMES) if "_idx_" not in n]


@pytest.fixture
def ref(monkeypatch):
    """The benchmark's reference modules, importable as run.py makes
    them."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    for m in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, m)
    from reference import keye_vl2, train
    keye_vl2.train = train
    return keye_vl2


@pytest.fixture(params=["xla", "kernels"])
def cores(request, monkeypatch):
    """The S x S work as the CPU chooses it (the XLA loops), and as the
    kernels of ``pallas/sparse_attention.py`` (the heads' cores) and
    ``pallas/index_scorer.py`` (the scorer) in interpret mode, through
    the operator's whole ``custom_vjp``."""
    if request.param == "kernels":
        from mxnet_tpu.ops import sparse_attention
        monkeypatch.setattr(sparse_attention, "_cores_impl",
                            lambda *a: "interpret")
    return request.param


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


def _attn_kw(kw):
    return {k: kw[k] for k in ("q_heads", "kv_heads", "head_dim", "idx_heads",
                               "idx_dim", "topk", "rope_theta", "q_chunk",
                               "kv_chunk")}


def _attn_weights(ref, kw=KW, scale=10.0):
    """A layer's attention weights; matrices scaled up from normal(0,
    0.02) so that the softmax and the scorer are far from flat, the
    gains moved off 1 and the shift off 0."""
    _, p = _params(ref, kw)
    ws = []
    for i, n in enumerate(ATTN_NAMES):
        w = p["layer1_" + n]
        ws.append(w + 0.1 * _stream(40 + i, w.shape)
                  if n.endswith(("_gamma", "_beta")) else w * scale)
    return ws


def _op(kw):
    from mxnet_tpu.ops.nn import sparse_indexed_attention
    return jax.jit(lambda h, ws: sparse_indexed_attention(
        h, *ws, **_attn_kw(kw))[:2])


def _ref_layer(ref, h, ws, kw=KW, want_chosen=False):
    p = {"L_" + n: w for n, w in zip(ATTN_NAMES, ws)}
    y, kl, chosen = ref.attention_sublayer(h, p, "L_", ref.dims(kw), "f32",
                                           want_chosen=want_chosen)
    return (y, kl / (h.shape[0] * h.shape[1])) \
        + ((chosen,) if want_chosen else ())


# ----------------------------------------------------------------------
# the operator against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seq,topk", [(40, 12), (37, 9), (40, 40)])
def test_operator_matches_reference_forward_and_every_gradient(ref, cores, seq,
                                                               topk):
    """Five query blocks of 8 (and a padded length, 37): the result,
    the index loss, and the gradient of ``sum(y * w) + 3 L`` to the
    stream and to all eleven leaves."""
    kw = dict(KW, seq_len=seq, topk=topk)
    h, ws = _stream(4, (B, seq, kw["d_model"])), _attn_weights(ref, kw)
    w = _stream(5, (B, seq, kw["d_model"]))
    y, li = _op(kw)(h, ws)
    y_ref, li_ref = _ref_layer(ref, h, ws, kw)
    _close(y, y_ref)
    assert float(li_ref) > 1e-3
    assert float(li[0]) == pytest.approx(float(li_ref), rel=2e-5)
    got = jax.jit(jax.grad(lambda h, ws: jnp.sum(_op(kw)(h, ws)[0] * w)
                           + 3.0 * _op(kw)(h, ws)[1][0], (0, 1)))(h, ws)

    def want_fn(h, ws):
        y, li = _ref_layer(ref, h, ws, kw)
        return jnp.sum(y * w) + 3.0 * li

    want = jax.jit(jax.grad(want_fn, (0, 1)))(h, ws)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(b).max()) > 0
        _close(a, b, 5e-5)


def _host_choice(scores, topk):
    """Per row t the ``topk`` columns s <= t with the largest score, a
    tie to the lower column: a stable sort on the host."""
    out = np.zeros(scores.shape, bool)
    for idx in np.ndindex(scores.shape[:-1]):
        t = idx[-1]
        row = np.asarray(scores[idx][:t + 1], np.float64)
        keep = np.argsort(-row, kind="stable")[:topk]
        out[idx + (keep,)] = True
    return out


def test_choice_is_exact_with_planted_ties(ref):
    """Scores drawn from five values, so that nearly every row's
    ``topk``-th largest is shared by several keys, and zeros of both
    signs: the program's bisection, the reference's ``top_k`` and a
    stable sort on the host pick the same pairs."""
    from mxnet_tpu.ops import sparse_attention as sa
    rng = np.random.default_rng(0)
    n, topk = 48, 7
    scores = rng.choice(np.array([-1.5, -0.0, 0.0, 0.25, 3.0], np.float32),
                        size=(n, n))
    scores[5] = 0.25                            # a whole row of one value
    scores[9, :10] = np.arange(10, dtype=np.float32)    # and one of none
    causal = np.tril(np.ones((n, n), bool))
    want = _host_choice(scores[None], topk)[0]
    assert (want.sum(-1) == np.minimum(np.arange(n) + 1, topk)).all()
    got = jax.jit(lambda s: sa.choose(s, jnp.asarray(causal), topk))(
        jnp.asarray(scores))
    assert np.array_equal(np.asarray(got), want)
    assert np.array_equal(
        np.asarray(ref.choose(jnp.asarray(scores), jnp.asarray(causal),
                              topk)), want)
    # bits and back
    packed = sa._pack(jnp.asarray(want), 16)
    assert packed.shape == (n, n // 8) and packed.dtype == jnp.uint8
    back = jnp.concatenate([sa._unpack(packed[:, c * 2:c * 2 + 2])
                            for c in range(n // 16)], axis=1)
    assert np.array_equal(np.asarray(back), want)


def test_live_tiles_are_the_tiles_of_the_references_choice(ref):
    """At the rehearsal sizes the reference's chosen mask holds
    ``min(t + 1, topk)`` keys a query, none above the diagonal, and the
    operator's count of live tiles is the count of that mask's tiles:
    the two choices fall in the same tiles (that they are the same pairs
    is what the results' agreement above shows)."""
    from mxnet_tpu.ops.nn import sparse_indexed_attention
    kw = dict(KW, topk=1)                   # few enough to leave tiles dead
    h, ws = _stream(4, (B, S, kw["d_model"])), _attn_weights(ref, kw)
    _, _, chosen = _ref_layer(ref, h, ws, kw, want_chosen=True)
    chosen = np.asarray(chosen)
    assert (chosen.sum(-1) == np.minimum(np.arange(S) + 1, kw["topk"])).all()
    assert not chosen[:, np.triu_indices(S, 1)[0],
                      np.triu_indices(S, 1)[1]].any()
    tiles = np.asarray(sparse_indexed_attention(h, *ws, **_attn_kw(kw))[2])
    t = kw["kv_chunk"]
    live = sum(int(chosen[b, i * t:(i + 1) * t, j * t:(j + 1) * t].any())
               for b in range(B) for i in range(S // t) for j in range(S // t))
    assert tiles.tolist() == [live, B * (S // t) * (S // t + 1) // 2]
    assert 0 < live < tiles[1]


def test_with_topk_at_least_the_length_it_is_dense_causal_attention(ref, cores):
    """``topk >= S``: every causal key is chosen, whatever the scorer
    says, and the result is ``_grouped_causal_attention`` of the same
    projections."""
    from mxnet_tpu.ops import nn
    kw = dict(KW, topk=S + 5)
    h, ws = _stream(4, (B, S, kw["d_model"])), _attn_weights(ref, kw)
    y = _op(kw)(h, ws)[0]
    d, Hq, Hk, D = kw["d_model"], kw["q_heads"], kw["kv_heads"], kw["head_dim"]

    def norm(t, gain):
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True) + 1e-6)
        return nn._rotary_half(t * inv * gain, D, kw["rope_theta"])

    q = norm(jnp.einsum("bsd,hed->bhse", h, ws[0].reshape(Hq, D, d)), ws[3])
    k = norm(jnp.einsum("bsd,hed->bhse", h, ws[1].reshape(Hk, D, d)), ws[4])
    v = jnp.einsum("bsd,hed->bhse", h, ws[2].reshape(Hk, D, d))
    o = nn._grouped_causal_attention(q, k, v, D ** -0.5)
    _close(y, jnp.einsum("bhse,dhe->bsd", o, ws[5].reshape(d, Hq, D)))


def test_each_objective_reaches_its_own_leaves_and_no_other(ref, cores):
    """EXACT zeros: the gradient of the result to the scorer's five
    leaves, and of the index loss to the stream and the main attention's
    six; in the program and in the reference."""
    h, ws = _stream(4, (B, S, KW["d_model"])), _attn_weights(ref)
    w = _stream(5, (B, S, KW["d_model"]))
    for layer in (lambda h, ws: _op(KW)(h, ws),
                  lambda h, ws: _ref_layer(ref, h, ws)):
        of_y = jax.grad(lambda h, ws: jnp.sum(layer(h, ws)[0] * w),
                        (0, 1))(h, ws)
        of_l = jax.grad(lambda h, ws: jnp.sum(layer(h, ws)[1]), (0, 1))(h, ws)
        for i in SCORER:
            assert float(jnp.abs(of_y[1][i]).max()) == 0.0, ATTN_NAMES[i]
            assert float(jnp.abs(of_l[1][i]).max()) > 0.0, ATTN_NAMES[i]
        for i in MAIN:
            assert float(jnp.abs(of_l[1][i]).max()) == 0.0, ATTN_NAMES[i]
            assert float(jnp.abs(of_y[1][i]).max()) > 0.0, ATTN_NAMES[i]
        assert float(jnp.abs(of_l[0]).max()) == 0.0
        assert float(jnp.abs(of_y[0]).max()) > 0.0


def test_operator_is_causal(ref, cores):
    """Changing position t changes nothing before it."""
    h, ws = _stream(4, (1, S, KW["d_model"])), _attn_weights(ref)
    t = 23
    y0 = _op(KW)(h, ws)[0]
    y1 = _op(KW)(h.at[0, t].add(1.0), ws)[0]
    assert float(jnp.abs(y1[0, :t] - y0[0, :t]).max()) == 0.0
    assert float(jnp.abs(y1[0, t:] - y0[0, t:]).max()) > 0.0


@pytest.mark.parametrize("scores,seq,topk", [
    ("distinct", 40, 12), ("distinct", 37, 9), ("tied", 40, 12),
    ("tied", 48, 20)])
def test_kernels_and_loops_agree_and_keep_the_same_bits(scores, seq, topk):
    """``ops/sparse_attention.py`` end to end with ``impl="interpret"``
    (the scorer's, the choice's and the cores' kernels) against
    ``impl=False`` (the XLA loops and ``choose``): the result, the index
    loss, the live tiles, the six gradients, and the SAME bits kept for
    the backward pass.  ``tied``: the scorer's keys are drawn from three
    vectors, so a row's scores tie in bulk and nearly every row's ties
    overflow its room (the tie rule runs inside the choice's kernel)."""
    from mxnet_tpu.ops import sparse_attention as sa
    Hq, Hk, D, Hi, Di = 4, 2, 8, 2, 8
    n = lambda i, *shape: _stream(20 + i, (B,) + shape)
    ki = n(4, seq, Di)
    if scores == "tied":
        ki = ki[:, :3][:, jnp.arange(seq) % 3]
    ops = (n(0, Hq, seq, D) * 2, n(1, Hk, seq, D), n(2, Hk, seq, D),
           n(3, Hi, seq, Di), ki, n(5, seq, Hi) * 0.3)
    w = n(6, Hq, seq, D)
    front = lambda *made: made
    got = {}
    for impl in (False, "interpret"):
        run = lambda *a, impl=impl: sa.sparse_indexed_attention(
            front, a, topk=topk, q_chunk=8, kv_chunk=8, impl=impl)
        o, L, live = jax.jit(run)(*ops)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(run(*a)[0] * w) + 3.0 * jnp.sum(run(*a)[1]),
            argnums=tuple(range(6))))(*ops)
        bq, tile, kc, Sp = sa.plan(seq, 8, 8)
        padded = tuple(jnp.pad(x, [(0, Sp - seq) if a == axis else (0, 0)
                                   for a in range(x.ndim)])
                       for x, axis in zip(ops, (2, 2, 2, 2, 1, 1)))
        bits = jax.jit(lambda *a, impl=impl: sa._attend_fwd(
            front, a, (seq, topk, bq, kc, tile, impl))[1][4])(*padded)
        got[impl] = (o, L) + tuple(grads), (live, bits)
    for a, b in zip(got[False][1], got["interpret"][1]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(got[False][1][1]).sum()) > 0
    for want, have in zip(got[False][0], got["interpret"][0]):
        assert float(jnp.abs(want).max()) > 0
        _close(have, want, 5e-5)


# ----------------------------------------------------------------------
# the expert layer's shares
# ----------------------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_expert_layer(ref):
    """The guide's share test: the parts that the eight shares of a
    layer's 16 experts give (2 held each, the router scoring all 16 and
    the weights normalised over all ``top_k``) add up to the uncut
    reference layer's result."""
    from mxnet_tpu.ops.nn import routed_experts
    kw = dict(KW, experts_held=[0, 16])
    _, p = _params(ref, kw)
    names = ["moe_gate_weight", "moe_up_weight", "moe_down_weight",
             "moe_router_weight"]
    ws = [p["layer1_" + n] * (50.0 if n.endswith("router_weight") else 5.0)
          for n in names]
    h = _stream(6, (B * S, kw["d_model"]))
    whole, _ = ref.experts(h, {"L_" + n: w for n, w in zip(names, ws)}, "L_",
                           ref.dims(kw), "f32")
    assert float(jnp.abs(whole).max()) > 0
    total = 0.0
    for first in range(0, 16, 2):
        y = routed_experts(
            h, gate_weight=ws[0][first:first + 2],
            up_weight=ws[1][first:first + 2],
            down_weight=ws[2][first:first + 2], router_weight=ws[3],
            router="linear", top_k=kw["top_k"], num_experts=16,
            held_first=first, held_count=2, num_hidden=kw["expert_dim"])[0]
        part, _ = ref.experts(
            h, {"L_" + n: (w if n.endswith("router_weight")
                           else w[first:first + 2])
                for n, w in zip(names, ws)}, "L_",
            ref.dims(dict(kw, experts_held=[first, 2])), "f32")
        _close(y, part, tol=5e-5)
        total = total + y
    _close(total, whole, tol=5e-5)


# ----------------------------------------------------------------------
# the model through Module.fit_step
# ----------------------------------------------------------------------
def test_symbol_parameters_and_outputs_are_the_references(ref):
    import mxnet_tpu as mx
    sym = mx.models.get_symbol("keye_vl2", **KW)
    assert sym.list_outputs() == ["softmax_output", "index_loss_output",
                                  "moe_expert_tokens_output",
                                  "dsa_live_tiles_output"]
    arg_shapes, out_shapes, aux_shapes = sym.infer_shape(
        data=(B, S), softmax_label=(B * S,))
    got = dict(zip(sym.list_arguments(), arg_shapes))
    for name, shape in ref.param_specs(KW):
        assert tuple(got.pop(name)) == tuple(shape), name
    assert set(got) == {"data", "softmax_label"}
    assert not sym.list_auxiliary_states() and not aux_shapes
    L = KW["num_layers"]
    assert [tuple(s) for s in out_shapes] == [
        (B * S, KW["num_classes"]), (1,), (L, KW["num_experts"]), (L, 2)]
    scorer = [n for n, _ in ref.param_specs(KW) if ref.is_scorer(n)]
    assert len(scorer) == 5 * L
    again = mx.sym.load_json(sym.tojson())
    assert again.list_arguments() == sym.list_arguments()
    with pytest.raises(ValueError):
        mx.models.get_symbol("keye_vl2", **dict(KW, experts_held=[12, 8]))


def test_the_two_heads_keep_to_their_leaves_through_the_module(ref):
    """``forward_backward`` of the bound module with the index loss's
    head cut off (behind ``BlockGrad``) leaves the scorer's leaves
    exactly zero gradient; with the softmax's cut off, every other
    leaf; and the whole graph's gradient is the sum of the two."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import keye_vl2
    _, weights = _params(ref, seed=3)
    rng = np.random.default_rng(0)
    d, l = ref.make_batch(rng, KW, B)

    def grads(sym):
        mod = mx.Module(sym, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", (B, S))],
                 label_shapes=[("softmax_label", (B * S,))])
        mod.init_params(arg_params={n: mx.nd.NDArray(w)
                                    for n, w in weights.items()})
        mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(d)],
                                             label=[mx.nd.array(l)]))
        exe = mod._exec_group._exec
        return {n: np.asarray(exe.grad_dict[n]._data)
                for n, _ in ref.param_specs(KW)}

    full = mx.models.get_symbol("keye_vl2", **KW)
    assert keye_vl2.INDEX_LOSS_NODE + "_output" in full.list_outputs()
    ce_only = grads(mx.sym.Group([full[0], mx.sym.BlockGrad(full[1])]))
    li_only = grads(mx.sym.Group([mx.sym.BlockGrad(full[0]), full[1]]))
    both = grads(full)
    for n, _ in ref.param_specs(KW):
        if ref.is_scorer(n):
            assert float(np.abs(ce_only[n]).max()) == 0.0, n
            assert float(np.abs(li_only[n]).max()) > 0.0, n
        else:
            assert float(np.abs(li_only[n]).max()) == 0.0, n
            assert float(np.abs(ce_only[n]).max()) > 0.0, n
        np.testing.assert_allclose(both[n], ce_only[n] + li_only[n],
                                   rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fit_steps_match_the_reference(ref, dtype):
    """``Module.fit_step`` with kvstore='tpu' and Adam at the cell's
    rehearsal sizes, as the benchmark's driver drives it: fused, one
    dispatch a step, ``ce`` read from head 0, losses and every leaf's
    first gradient and change against the reference's first steps (whose
    gradient is that of ``ce + L^I``); in bfloat16 (multi_precision)
    within bfloat16's reach."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler, telemetry
    kw = dict(KW, dtype=dtype)
    low = dtype != "float32"
    key, weights = _params(ref, kw, seed=3)
    mod = mx.Module(mx.models.get_symbol("keye_vl2", **kw),
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
           if n.endswith("router_weight") or n == "tok_embed_weight"
           or ref.is_scorer(n)}
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
    states = mod._kvstore._updater.states
    for name, shape in ref.param_specs(kw):
        w = exe.arg_dict[name]._data
        if low and w.dtype != jnp.float32:      # the float32 master
            w = states[name][1]._data
        got = float(ref.train.delta_norm(key, name, tuple(shape), w, ref))
        assert got == pytest.approx(want["delta_norms"][name],
                                    rel=0.2 if low else 1e-3, abs=1e-7), name
    # the second head's value and the tile counts left the program
    outs = mod.get_outputs()
    assert outs[1].shape == (1,) and float(outs[1].asnumpy()[0]) > 0
    tiles = telemetry.dsa.publish()
    assert tiles["causal"] == kw["num_layers"] * B * 15
    assert 0 < tiles["live"] <= tiles["causal"]
    assert telemetry.REGISTRY.get("dsa_live_block_share").value \
        == tiles["live"] / tiles["causal"]
