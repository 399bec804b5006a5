"""SDAR's language model and its block-diffusion training pass on the CPU
at tiny widths, float32, against the plain reference
(benchmark/reference/sdar_moe.py): the grouped-query operator with q/k
norms and the block-diffusion mask, forward and every gradient; the
mask by its three properties, in program and reference; the mask in the
flash pair, the kernels interpreted, against the XLA path (forward, dq,
dk, dv: a dead quadrant, a strict and a non-strict block diagonal), and
``blocks=None`` bit-equal to the kernels put together by hand as they
were; the loss head's masked, weighted rows and its deferral; the share
test of the ``model-configs`` guide, section 4 (eight shares of 16
experts add up to the uncut layer); three ``Module.fit_step`` steps of
``models.get_symbol('sdar_moe')`` against the reference's first steps.
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
                       "sdar_30b_a3b_train.json")) as _f:
    REHEARSE = json.load(_f)["rehearse"]
KW = dict(REHEARSE["kwargs"])           # the cell's rehearsal sizes
B, S, Bk = 1, KW["seq_len"], KW["block_length"]
ATTN = ["attn_q_weight", "attn_k_weight", "attn_v_weight", "attn_o_weight",
        "attn_q_norm_gamma", "attn_k_norm_gamma"]
GQA = dict(q_heads=KW["q_heads"], kv_heads=KW["kv_heads"],
           head_dim=KW["head_dim"], rope_theta=KW["rope_theta"])


@pytest.fixture
def ref(monkeypatch):
    """The benchmark's reference modules, importable as run.py makes
    them."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    for m in [m for m in sys.modules if m.split(".")[0] == "reference"]:
        monkeypatch.delitem(sys.modules, m)
    from reference import sdar_moe, train
    sdar_moe.train = train
    return sdar_moe


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


def _batch(ref, seed=0, kw=KW, batch=B):
    """A batch whose blocks are of all three kinds: fully masked, partly
    masked and untouched."""
    rng = np.random.default_rng(seed)
    while True:
        data, labels = ref.make_batch(rng, kw, batch)
        per = (data[:, 1] == kw["num_classes"] - 1) \
            .reshape(batch, -1, kw["block_length"]).sum(-1)
        if (per == 0).any() and (per == kw["block_length"]).any() \
                and ((per > 0) & (per < kw["block_length"])).any():
            return data, labels


def test_the_preset_is_a_strict_share_and_grouped_heads():
    assert KW["q_heads"] == 4 * KW["kv_heads"] and Bk == 4 and S == 64
    assert 0 < KW["experts_held"][1] < KW["num_experts"]


def test_the_noise_is_the_collators(ref):
    """``make_batch``: ids below MASK, a masked row shows MASK and
    weighs ``1 / p`` of its block (one ``p`` a block, at least the
    floor), an unmasked row shows its token and weighs 0; the labels are
    ``x0``; the same generator state gives the same batch."""
    kw = dict(KW, seq_len=4096)
    data, labels = ref.make_batch(np.random.default_rng(5), kw, 2)
    again, _ = ref.make_batch(np.random.default_rng(5), kw, 2)
    np.testing.assert_array_equal(data, again)
    assert data.shape == ref.data_shapes(kw, 2)[0] == (2, 3, 4096)
    assert labels.shape == ref.data_shapes(kw, 2)[1]
    x0, xt, w = data[:, 0], data[:, 1], data[:, 2]
    mask_id = kw["num_classes"] - 1
    np.testing.assert_array_equal(labels.reshape(2, -1), x0)
    assert x0.max() < mask_id and x0.min() >= 0
    m = xt == mask_id
    np.testing.assert_array_equal(xt[~m], x0[~m])
    assert (w[~m] == 0).all() and (w[m] >= 1.0).all() \
        and (w[m] <= 1.0 / ref.P_FLOOR + 1).all()
    row = w.reshape(2, -1, Bk)  # the masked rows of a block share one weight
    top = row.max(-1, keepdims=True)
    assert np.where(row > 0, row, top).min(-1).tolist() \
        == top[..., 0].tolist()
    # p is uniform: about half the rows are masked
    assert 0.45 < m.mean() < 0.55


# ----------------------------------------------------------------------
# the attention operator: q/k norms, repeated positions, the mask
# ----------------------------------------------------------------------
def _op_and_plain(ref, p, layer=0, scale=10.0):
    from mxnet_tpu.ops.nn import grouped_query_attention
    ws = [p["layer%d_%s" % (layer, n)] * (scale if n.endswith("_weight")
                                          else 1.0) for n in ATTN]
    z = ref.dims(KW)

    def op(h, *ws):
        return grouped_query_attention(h, *ws, qk_norm=True, eps=1e-6,
                                       blocks=Bk, **GQA)

    def plain(h, *ws):
        return ref.attention_sublayer(
            h, {"L_" + n: x for n, x in zip(ATTN, ws)}, "L_", z, "f32",
            blk=16)

    return ws, op, plain


def test_operator_matches_reference_forward_and_every_gradient(ref):
    """``GroupedQueryAttention`` with ``qk_norm`` and ``blocks`` against
    the reference's sublayer over ``[clean; noised]`` rows: the result
    and the gradient of every input (the gains drawn away from 1), the
    matrices scaled up so that the softmax is far from flat."""
    _, p = _params(ref)
    ws, op, plain = _op_and_plain(ref, p)
    ws[4] = 1.0 + 0.3 * _stream(11, ws[4].shape)
    ws[5] = 1.0 + 0.3 * _stream(12, ws[5].shape)
    h = _stream(5, (1, 2 * S, KW["d_model"]))
    w = _stream(6, (1, 2 * S, KW["d_model"]))
    # one compiled program a side: the result beside the gradients
    run = lambda f: jax.jit(jax.value_and_grad(
        lambda *a: ((lambda y: ((y * w).sum(), y))(f(*a))), has_aux=True,
        argnums=tuple(range(7))))(h, *ws)
    for a, b in zip(jax.tree_util.tree_leaves(run(op)),
                    jax.tree_util.tree_leaves(run(plain))):
        _close(a, b, tol=5e-5)


def test_without_the_gains_the_operator_is_what_it_was(ref):
    """No ``qk_norm``, no ``blocks``: SmallThinker's operator, bit for
    bit what the causal formula gives, and the graph asks for no gain."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops.nn import (_grouped_causal_attention, _rotary_half,
                                  grouped_query_attention)
    _, p = _params(ref)
    q_w, k_w, v_w, o_w = (p["layer0_" + n] * 10.0 for n in ATTN[:4])
    h = _stream(3, (2, S, KW["d_model"]))
    Hq, Hk, D, d = (KW["q_heads"], KW["kv_heads"], KW["head_dim"],
                    KW["d_model"])
    heads = lambda w, n: jnp.einsum("bsd,hed->bhse", h, w.reshape(n, D, d))
    turn = lambda t: _rotary_half(t, D, KW["rope_theta"])
    o = _grouped_causal_attention(turn(heads(q_w, Hq)), turn(heads(k_w, Hk)),
                                  heads(v_w, Hk), D ** -0.5)
    want = jnp.einsum("bhse,dhe->bsd", o, o_w.reshape(d, Hq, D))
    got = grouped_query_attention(h, q_w, k_w, v_w, o_w, **GQA)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    node = mx.sym.contrib.GroupedQueryAttention(
        mx.sym.Variable("x"), name="a", **GQA)
    assert node.list_arguments() == ["x", "a_q_weight", "a_k_weight",
                                     "a_v_weight", "a_o_weight"]
    with_gains = mx.sym.contrib.GroupedQueryAttention(
        mx.sym.Variable("x"), name="a", qk_norm=True, **GQA)
    assert with_gains.list_arguments()[-2:] == ["a_q_norm_gamma",
                                                "a_k_norm_gamma"]


def test_a_window_beside_blocks_and_an_odd_length_are_refused(ref):
    from mxnet_tpu.ops.nn import grouped_query_attention
    _, p = _params(ref)
    ws = [p["layer0_" + n] for n in ATTN]
    h = _stream(1, (1, 2 * S, KW["d_model"]))
    with pytest.raises(ValueError, match="blocks"):
        grouped_query_attention(h, *ws, qk_norm=True, blocks=Bk, window=8,
                                **GQA)
    with pytest.raises(ValueError, match="blocks"):
        grouped_query_attention(h[:, :-1], *ws, qk_norm=True, blocks=Bk,
                                **GQA)


# ----------------------------------------------------------------------
# the mask by its three properties, in program and reference
# ----------------------------------------------------------------------
def _program_stream(ref, params, kw=KW):
    """``data (1, 3, L) -> the stream of all 2 L rows after the last
    layer`` through the model's own graph (what ``noised_half`` cuts)."""
    import mxnet_tpu as mx
    sym = mx.models.get_symbol("sdar_moe", **kw)
    stream = sym.get_internals()["noised_half_output"].get_children()
    exe = stream.simple_bind(mx.cpu(0), data=(1, 3, kw["seq_len"]),
                             grad_req="null")
    for n in exe.arg_dict:
        if n != "data":
            exe.arg_dict[n][:] = mx.nd.NDArray(params[n], mx.cpu(0))

    def run(data):
        exe.arg_dict["data"][:] = mx.nd.array(np.asarray(data))
        return exe.forward(is_train=False)[0].asnumpy()

    return run


def _reference_stream(ref, params, kw=KW):
    def run(data):
        data = jnp.asarray(data)
        return np.asarray(ref.trunk(params, data[:, 0].astype(jnp.int32),
                                    data[:, 1].astype(jnp.int32), kw))
    return run


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_mask_by_its_three_properties(ref, side):
    """On the stream after the last layer (the logits are a row's own
    function of it): (1) changing a clean token of block ``b`` changes
    no noised row of the blocks ``<= b`` (and every later one); (2)
    changing a noised token changes the noised rows of its own block
    only, and no clean row; (3) a noised row equals a causal-by-block
    model's on ``[x0 of the earlier blocks; xt of its own]`` run alone:
    the CLEAN half of a second pass whose ``x0`` is that sequence."""
    _, params = _params(ref)
    stream = (_program_stream if side == "program"
              else _reference_stream)(ref, params)
    data, _ = _batch(ref, seed=4)
    base = stream(data)
    b = 5                                       # a block in the middle
    rows = np.arange(b * Bk, (b + 1) * Bk)
    moved = lambda a: np.abs(a - base).reshape(2, S // Bk, -1).max(-1)

    clean = data.copy()
    clean[0, 0, b * Bk + 1] = (clean[0, 0, b * Bk + 1] + 7) % 90
    of_clean, of_noised = moved(stream(clean))
    assert (of_noised[:b + 1] == 0).all() and (of_noised[b + 1:] > 0).all()
    assert (of_clean[:b] == 0).all() and (of_clean[b:] > 0).all()

    noised = data.copy()
    noised[0, 1, b * Bk + 2] = (noised[0, 1, b * Bk + 2] + 7) % 90
    of_clean, of_noised = moved(stream(noised))
    assert of_noised[b] > 0 and (np.delete(of_noised, b) == 0).all()
    assert (of_clean == 0).all()

    alone = data.copy()
    alone[0, 0, rows] = data[0, 1, rows]        # x0 := xt inside block b
    alone[0, 0, (b + 1) * Bk:] = 0              # what follows is unseen
    alone[0, 1] = alone[0, 0]
    np.testing.assert_allclose(stream(alone)[0, rows], base[0, S + rows],
                               rtol=2e-5, atol=2e-6)


def test_program_and_reference_streams_agree(ref):
    _, params = _params(ref)
    data, _ = _batch(ref, seed=9)
    _close(_program_stream(ref, params)(data),
           _reference_stream(ref, params)(data), tol=5e-5)


# ----------------------------------------------------------------------
# the mask in the flash pair (kernels interpreted)
# ----------------------------------------------------------------------
def _flash_operands(Hq, Hk, S_, dtype=jnp.float32, D=128):
    ks = jax.random.split(jax.random.PRNGKey(Hq * S_), 4)
    q = (jax.random.normal(ks[0], (1, Hq, S_, D)) * D ** -0.5).astype(dtype)
    k = jax.random.normal(ks[1], (1, Hk, S_, D)).astype(dtype)
    v = jax.random.normal(ks[2], (1, Hk, S_, D)).astype(dtype)
    return q, k, v, jax.random.normal(ks[3], (1, Hq, S_, D))


@pytest.mark.parametrize("S_,blocks", [(2048, 4)],
                         ids=["two_blocks_a_half"])
def test_block_diffusion_flash_pair_matches_the_xla_path(S_, blocks):
    """Forward, dq, dk, dv of the mask kernels (interpreted) against the
    XLA core on the same operands at 2L = 2048: a non-strict and a
    strict block diagonal, the noised diagonal, a dead quadrant, and
    unmasked blocks under the diagonals.  (ONE geometry: an interpreted
    case costs ~17 s of the suite's limit, and 2L = 1024 holds no kind
    of block that this one lacks.)"""
    from mxnet_tpu.ops import nn
    q, k, v, do = _flash_operands(2, 1, S_)
    flash = lambda q, k, v: nn._flash_attention(q, k, v, blocks=blocks,
                                                interpret=True)
    xla = lambda q, k, v: nn._grouped_causal_attention(q, k, v, 1.0, None,
                                                       blocks)
    run = lambda f: jax.value_and_grad(
        lambda *a: (f(*a) * do).sum(), argnums=(0, 1, 2))(q, k, v)
    _close(flash(q, k, v), xla(q, k, v), tol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(run(flash)),
                    jax.tree_util.tree_leaves(run(xla))):
        _close(a, b, tol=5e-5)


def test_the_xla_mask_is_the_references(ref):
    """The XLA core's mask, the forward kernel's lazy mask and the
    reference's ``allowed`` are one relation (a block length that does
    not divide the kernels' 512 included, on the XLA side)."""
    from mxnet_tpu.ops import nn
    for L, blocks in ((512, 4), (512, 32), (96, 3)):
        t, s = np.arange(2 * L)[:, None], np.arange(2 * L)[None, :]
        want = np.asarray(ref.allowed(t, s, L, blocks))
        assert int(want.sum()) == L * L + L * blocks
        q = jnp.zeros((1, 1, 2 * L, 8))
        # uniform scores: the probabilities are the mask over its row sums
        p = nn._grouped_causal_attention(q, q, jnp.eye(2 * L)[None, None],
                                         1.0, None, blocks)
        np.testing.assert_array_equal(np.asarray(p[0, 0]) > 0, want)
        if 512 % blocks == 0:
            lazy = nn._block_diffusion_mask(2 * L, blocks)
            np.testing.assert_array_equal(lazy[:, :], want)


def test_blocks_none_is_bit_equal_to_the_kernels_as_they_were():
    """``blocks=None`` builds the causal and the banded pair as before
    this mask: the forward jax's splash kernel over ``CausalMask`` /
    ``LocalMask`` put together by hand, the backward's walk the two old
    fillings (block for block what ``first .. i`` and ``reach`` gave)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        CausalMask, LocalMask, MultiHeadMask, make_splash_mha)
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas import flash_backward as fb
    S_ = 1024
    q, k, v, do = _flash_operands(2, 1, S_)
    for window in (None, 512):
        one = CausalMask((S_, S_)) if window is None else LocalMask(
            (S_, S_), window_size=(window - 1, 0), offset=0)
        with jax.ensure_compile_time_eval():
            kernel = make_splash_mha(
                MultiHeadMask([one] * 2), head_shards=1, q_seq_shards=1,
                interpret=True, save_residuals=True,
                block_sizes=nn._flash_block_sizes(S_))
        o, (lse,) = jax.vmap(kernel)(q, k, v)
        got, vjp = jax.vjp(lambda *a: nn._flash_attention(
            *a, window=window, interpret=True), q, k, v)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(o))
        want = fb.flash_attention_backward(q, k, v, o, lse, do,
                                           window=window, interpret=True)
        for a, b in zip(vjp(do), want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the table's two old fillings, block for block
    for window, reach in ((None, 32), (4096, 8)):
        table = fb.walk(16384, window)
        for i in range(32):
            seen = []
            for kind, a, b in table(i):
                seen += list(range(max(a, 0), b)) if kind == "run" \
                    else [a] * (0 <= a < 32)
            assert sorted(seen) == list(range(max(0, i - reach), i + 1))


def test_block_counters_at_the_cells_geometry():
    """At 2L = 16 384 the backward's table walks 288 of the 1024 blocks
    a head (136 + 136 + 16) where the causal walk is 528 and the band's
    252; the forward's 1024-row tables keep 80 of 256.  The counters
    book them under the mask kernels' own labels."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas import flash_backward as fb
    from mxnet_tpu.pallas.dispatch import (FLASH_BLOCKS_CAUSAL,
                                           FLASH_BLOCKS_WALKED)
    assert fb.blocks_walked(16384) == 528
    assert fb.blocks_walked(16384, 4096) == 252
    assert fb.blocks_walked(16384, blocks=4) == 288
    assert fb.blocks_walked(16384, blocks=512) == 288
    steps = {"run": 0, "one": 0}
    for i in range(32):
        for kind, a, b in fb.walk(16384, blocks=4)(i):
            steps[kind] += max(0, b - a) if kind == "run" else 0 <= a < 32
    assert steps == {"run": 240, "one": 48}
    value = lambda c, k: c.labels(kernel=k).value
    before = {(c.name, k): value(c, k)
              for c in (FLASH_BLOCKS_WALKED, FLASH_BLOCKS_CAUSAL)
              for k in ("flash_attention_blocks",
                        "flash_attention_blocks_bwd")}
    nn._count_flash_blocks(1, 32, 16384, None, True, blocks=4)
    grew = lambda c, k: value(c, k) - before[(c.name, k)]
    assert grew(FLASH_BLOCKS_WALKED, "flash_attention_blocks") == 32 * 80 * 4
    assert grew(FLASH_BLOCKS_CAUSAL, "flash_attention_blocks") == 32 * 136 * 4
    assert grew(FLASH_BLOCKS_WALKED, "flash_attention_blocks_bwd") == 32 * 288
    assert grew(FLASH_BLOCKS_CAUSAL, "flash_attention_blocks_bwd") == 32 * 528


def test_a_block_length_that_does_not_divide_512_is_refused_and_counted(
        monkeypatch):
    """The kernels raise; the gate sends the core to XLA and counts the
    refusal under reason ``flash-blocks`` (and only that refusal)."""
    from mxnet_tpu.ops import nn
    from mxnet_tpu.pallas import dispatch, flash_backward as fb
    q, k, v, _ = _flash_operands(1, 1, 1024)
    with pytest.raises(ValueError, match="blocks"):
        nn._flash_attention(q, k, v, blocks=3, interpret=True)
    with pytest.raises(ValueError, match="blocks"):
        nn._flash_attention(q, k, v, blocks=4, window=512, interpret=True)
    with pytest.raises(ValueError, match="blocks"):
        fb.flash_attention_backward(q, k, v, q, q[..., 0], q, blocks=3,
                                    interpret=True)
    monkeypatch.setattr(dispatch, "_compiles_here",
                        lambda: (True, "", None))
    count = lambda r: dispatch.PALLAS_FALLBACKS.labels(reason=r).value
    n0, g0 = count("flash-blocks"), count("flash-geometry")
    assert nn._use_flash_attention(2048, 128, jnp.bfloat16, blocks=4) \
        == "compiled"
    assert not nn._use_flash_attention(2048, 128, jnp.bfloat16, blocks=3)
    assert not nn._use_flash_attention(1536, 128, jnp.bfloat16, blocks=4)
    assert count("flash-blocks") == n0 + 2 and count("flash-geometry") == g0
    fold, attend = nn._causal_attention_core(1024, 128, jnp.float32, 0.5,
                                             blocks=3)
    assert fold == 1.0
    assert attend(q, k, v).shape == q.shape


def test_flash_branch_of_the_operator_matches_its_xla_branch(
        ref, monkeypatch):
    """The operator at the kernels' widths (heads of 128, 2L = 1024):
    the flash branch (interpreted) against the XLA branch, result and
    every gradient, under scope ``gqa.blockdiff``."""
    from mxnet_tpu.ops import nn
    d, Hq, Hk, D, R = 64, 2, 1, 128, 1024
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    h = jax.random.normal(ks[0], (1, R, d))
    ws = [jax.random.normal(ks[1], (Hq * D, d)) * 0.3,
          jax.random.normal(ks[2], (Hk * D, d)) * 0.3,
          jax.random.normal(ks[3], (Hk * D, d)) * 0.3,
          jax.random.normal(ks[4], (d, Hq * D)) * 0.1,
          1.0 + 0.2 * jax.random.normal(ks[5], (D,)), jnp.ones((D,))]
    op = lambda h, *ws: nn.grouped_query_attention(
        h, *ws, q_heads=Hq, kv_heads=Hk, head_dim=D, qk_norm=True,
        blocks=4)
    run = lambda: jax.jit(jax.value_and_grad(
        lambda *a: (op(*a) ** 2).sum(), argnums=tuple(range(7))))(h, *ws)
    want = run()
    monkeypatch.setattr(nn, "_use_flash_attention",
                        lambda *a, **k: "compiled")
    monkeypatch.setattr(
        nn, "_flash_attention",
        lambda q, k, v, window=None, blocks=None, _f=nn._flash_attention:
        _f(q, k, v, window=window, blocks=blocks, interpret=True))
    text = str(jax.make_jaxpr(op)(h, *ws))
    assert "flash_attention_backward" not in text and "pallas_call" in text
    for a, b in zip(jax.tree_util.tree_leaves(run()),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, tol=1e-4)


# ----------------------------------------------------------------------
# the loss head
# ----------------------------------------------------------------------
def test_the_head_passes_masked_rows_and_weighs_their_gradient():
    """``DiffusionHead``: a masked row passes, an unmasked row puts all
    its mass on the token it shows (its cross-entropy at the label reads
    0) and sends no gradient; a masked row's gradient is its cotangent
    times its weight; output 1 counts the masked rows."""
    from mxnet_tpu.ops.nn import diffusion_head
    V, mask_id = 12, 11
    logits = _stream(1, (1, 6, V))
    xt = jnp.array([[3.0, mask_id, 5.0, mask_id, mask_id, 0.0]])
    w = jnp.array([[0.0, 2.0, 0.0, 1000.0, 1.0, 0.0]])
    out, rows = diffusion_head(logits, xt, w, mask_id=mask_id)
    assert rows.tolist() == [3, 6]
    masked = np.asarray(xt[0] == mask_id)
    np.testing.assert_array_equal(np.asarray(out[0, masked]),
                                  np.asarray(logits[0, masked]))
    logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
    for i in np.nonzero(~masked)[0]:
        assert float(logp[0, i, int(xt[0, i])]) == 0.0
    g = _stream(2, (1, 6, V))
    got = jax.grad(lambda x: (diffusion_head(x, xt, w, mask_id=mask_id)[0]
                              * g).sum())(logits)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(g * jnp.where(xt == mask_id, w,
                                                        0.0)[..., None]),
                               rtol=1e-6)
    low = diffusion_head(logits.astype(jnp.bfloat16), xt, w,
                         mask_id=mask_id)[0]
    assert low.dtype == jnp.bfloat16 and bool(jnp.isfinite(low).all())


def test_the_head_stays_deferred_and_ce_reads_the_masked_mean(ref):
    """The fused step returns the head's stem (the operator's result),
    ``ce`` reads ``(1 / L) sum_i m_i ce_i`` and reading the outputs
    builds the probabilities once; the masked-row gauge is filled from
    the step's own count."""
    import mxnet_tpu as mx
    from mxnet_tpu import loss_head
    from mxnet_tpu.telemetry import diffusion
    key, weights = _params(ref, seed=3)
    sym = mx.models.get_symbol("sdar_moe", **KW)
    (plan,) = loss_head.plans(sym)
    assert plan.stem[0].op.name == "_contrib_DiffusionHead"
    assert [n.op.name for n in plan.chain] == ["Reshape"]
    low = loss_head.plans(mx.models.get_symbol(
        "sdar_moe", **dict(KW, dtype="bfloat16")))[0]
    assert [n.op.name for n in low.chain] == ["Cast", "Reshape"]
    mod = mx.Module(sym, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (B, 3, S))],
             label_shapes=[("softmax_label", (B * S,))])

    class Seeded(mx.init.Initializer):
        def __call__(self, desc, arr):
            arr[:] = mx.nd.NDArray(weights[str(desc)], arr.context)

    mod.init_params(Seeded())
    mod.init_optimizer(kvstore="tpu", optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3})
    data, labels = _batch(ref, seed=1)
    want_ce, _ = ref.losses(weights, *ref.device_batch(data, labels), KW)
    want_ce = float(want_ce)        # before the step donates the weights
    metric = mx.metric.create("ce")
    d0, m0 = loss_head.DEFERRED.value, loss_head.MATERIALIZED.value
    batch = mx.io.DataBatch(data=[mx.nd.array(data)],
                            label=[mx.nd.array(labels)])
    assert mod.fit_step(batch, metric)
    mod.update_metric(metric, batch.label)
    assert loss_head.DEFERRED.value == d0 + 1
    assert loss_head.MATERIALIZED.value == m0
    np.testing.assert_allclose(metric.get()[1], want_ce, rtol=1e-5)
    prob = mod.get_outputs()[0].asnumpy()
    assert loss_head.MATERIALIZED.value == m0 + 1
    shown = data[0, 1].astype(int)
    unmasked = shown != KW["num_classes"] - 1
    assert (prob[np.arange(S), shown][unmasked] == 1.0).all()
    seen = diffusion.publish()
    assert seen == {"masked": int((~unmasked).sum()), "rows": S}
    assert diffusion.MASKED_ROW_SHARE.value == seen["masked"] / S


# ----------------------------------------------------------------------
# the chip's share
# ----------------------------------------------------------------------
def test_rows_slack_sizes_the_sorted_rows_and_changes_no_result():
    """``RoutedExperts(rows_slack=)``: the sorted rows' first size is
    that many times the even share (the cell: 32 768 of 16 384, where
    the other expert cells keep five quarters), and a step whose pairs
    lie between the two sizes gives the same result and gradients from
    the one buffer as from the slabs."""
    from mxnet_tpu.parallel import moe
    assert moe._row_buckets(16384, 8, 16, 128) == [20480, 131072]
    assert moe._row_buckets(16384, 8, 16, 128, 2.0) == [32768, 131072]
    assert moe._row_buckets(64, 3, 4, 16, 100.0) == [192]
    N, k, held, E, d, F = 64, 3, 4, 16, 16, 8
    assert moe._row_buckets(N, k, held, E) == [64, 192]
    assert moe._row_buckets(N, k, held, E, 2.0) == [96, 192]
    rng = np.random.default_rng(0)
    # 80 of the 192 pairs are held here: past 64, inside 96
    chosen = np.full((N, k), E - 1, np.int32)
    chosen[:40, 0], chosen[:40, 1] = rng.integers(0, 2, 40), 2 + rng.integers(0, 2, 40)
    chosen[:, 2] = 8 + rng.integers(0, 7, N)
    assert int((chosen < held).sum()) == 80
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (N, d))
    w = jax.nn.softmax(jax.random.normal(ks[1], (N, k)), -1)
    wg, wu = (jax.random.normal(a, (held, F, d)) * 0.3 for a in ks[2:4])
    wd = jax.random.normal(ks[4], (held, d, F)) * 0.3

    def run(slack):
        f = lambda x, w, wg, wu, wd: moe.dropless_topk_experts(
            x, jnp.asarray(chosen), w, wg, wu, wd, E, slack=slack)[0]
        def both(*a):
            y, back = jax.vjp(f, *a)
            return [y] + list(back(jnp.ones_like(y)))
        return jax.jit(both)(x, w, wg, wu, wd)

    for a, b in zip(run(1.25), run(2.0)):
        _close(a, b, tol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_expert_layer(ref):
    """``model-configs`` guide, section 4: eight shares of 16 experts,
    each through the program's operator, add up to what the uncut
    reference gives for the whole layer of 128 (no shared expert: nothing
    is counted twice)."""
    from mxnet_tpu.ops.nn import routed_experts
    kw = dict(KW, num_experts=128, top_k=8, experts_held=None)
    z = ref.dims(kw)
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    N, d, F, E = 96, kw["d_model"], kw["expert_dim"], 128
    h = jax.random.normal(ks[0], (N, d))
    p = {"L_moe_router_weight": jax.random.normal(ks[1], (E, d)),
         "L_moe_gate_weight": jax.random.normal(ks[2], (E, F, d)) * 0.2,
         "L_moe_up_weight": jax.random.normal(ks[3], (E, F, d)) * 0.2,
         "L_moe_down_weight": jax.random.normal(ks[4], (E, d, F)) * 0.2}
    whole, _ = ref.experts(h, p, "L_", z, "f32")
    total = 0.0
    for first in range(0, E, 16):
        part = slice(first, first + 16)
        y = routed_experts(
            h[None], gate_weight=p["L_moe_gate_weight"][part],
            up_weight=p["L_moe_up_weight"][part],
            down_weight=p["L_moe_down_weight"][part],
            router_weight=p["L_moe_router_weight"], router="linear",
            top_k=8, num_experts=E, held_first=first, held_count=16,
            num_hidden=F)[0]
        total = total + y[0]
    _close(total, whole, tol=5e-5)


# ----------------------------------------------------------------------
# the whole model through Module.fit_step
# ----------------------------------------------------------------------
def test_symbol_parameters_and_outputs_are_the_references(ref):
    import mxnet_tpu as mx
    sym = mx.models.get_symbol("sdar_moe", **KW)
    args, outs, _ = sym.infer_shape(data=(B, 3, S), softmax_label=(B * S,))
    shapes = dict(zip(sym.list_arguments(), args))
    assert {n: tuple(shapes[n]) for n, _ in ref.param_specs(KW)} \
        == {n: tuple(s) for n, s in ref.param_specs(KW)}
    assert set(shapes) == {n for n, _ in ref.param_specs(KW)} \
        | {"data", "softmax_label"}
    assert sym.list_outputs() == ["softmax_output",
                                  "moe_expert_tokens_output",
                                  "diffusion_masked_rows_output"]
    assert [tuple(o) for o in outs] == [
        (B * S, KW["num_classes"]), (KW["num_layers"], KW["num_experts"]),
        (2,)]
    with pytest.raises(ValueError, match="block_length"):
        mx.models.get_symbol("sdar_moe", **dict(KW, block_length=5))


def test_loss_value_and_every_leafs_gradient_match_the_reference(ref):
    """The graph's own gradient (an executor's ``backward``, no
    optimizer between) against ``jax.grad`` of the reference's ``loss``:
    the VALUE is ``ce``, the gradient ``J``'s, leaf by leaf."""
    import mxnet_tpu as mx
    _, weights = _params(ref, seed=5)
    data, labels = _batch(ref, seed=2)
    dev = ref.device_batch(data, labels)
    (value, _), want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, {}, *dev, KW), has_aux=True))(weights)
    ce, J = jax.jit(lambda p: ref.losses(p, *dev, KW))(weights)
    assert float(value) == float(ce) and float(J) > float(ce) > 0
    sym = mx.models.get_symbol("sdar_moe", **KW)
    exe = sym.simple_bind(mx.cpu(0), data=(B, 3, S),
                          softmax_label=(B * S,), grad_req="write")
    for n, v in weights.items():
        exe.arg_dict[n][:] = mx.nd.NDArray(v, mx.cpu(0))
    exe.arg_dict["data"][:] = mx.nd.array(data)
    exe.arg_dict["softmax_label"][:] = mx.nd.array(labels)
    prob = exe.forward(is_train=True)[0].asnumpy()
    exe.backward()
    got_ce = -np.log(prob[np.arange(S), labels.astype(int)]).mean()
    np.testing.assert_allclose(got_ce, float(ce), rtol=1e-5)
    for n in weights:
        _close(exe.grad_dict[n].asnumpy(), want[n], tol=2e-4)


def test_the_stream_stays_float32_in_a_bfloat16_trunk(ref):
    """Masked rows carry one embedding and differ by less than a
    bfloat16 rounding of it, so the residual stream is float32 whatever
    the trunk's dtype: the sublayers read it in bfloat16, each router
    reads the normalised float32 rows, and the gains that scale the
    stream are float32 with it (the q/k gains and the head's are the
    trunk's)."""
    import mxnet_tpu as mx
    kw = dict(KW, dtype="bfloat16")
    sym = mx.models.get_symbol("sdar_moe", **kw)
    nodes = ["layer1_post_norm_output", "layer1_post_norm_low_output",
             "layer1_in_norm_low_output", "layer1_attn_output",
             "layer1_moe_output0", "noised_half_output",
             "noised_half_low_output"]
    inner = sym.get_internals()
    exe = mx.sym.Group([inner[n] for n in nodes]).simple_bind(
        mx.cpu(0), data=(1, 3, S), grad_req="null")
    exe.arg_dict["data"][:] = mx.nd.array(_batch(ref)[0])
    got = dict(zip(nodes, (str(o.dtype) for o in exe.forward(False))))
    assert got == {
        "layer1_post_norm_output": "float32",
        "layer1_post_norm_low_output": "bfloat16",
        "layer1_in_norm_low_output": "bfloat16",
        "layer1_attn_output": "bfloat16", "layer1_moe_output0": "bfloat16",
        "noised_half_output": "float32",
        "noised_half_low_output": "bfloat16"}
    moe = [n for n in json.loads(sym.tojson())["nodes"]
           if n["name"] == "layer1_moe"][0]
    assert moe["attrs"]["router_stream"] == "True"
    full = mx.Module(sym, context=mx.cpu(0))
    full.bind(data_shapes=[("data", (B, 3, S))],
              label_shapes=[("softmax_label", (B * S,))])
    args = full._exec_group._exec.arg_dict
    names = [n for n, _ in ref.param_specs(kw)]
    assert {n for n in names if str(args[n].dtype) == "float32"} == {
        n for n in names
        if n.endswith(("router_weight", "in_norm_gamma", "post_norm_gamma"))
        or n == "tok_embed_weight"}


@pytest.mark.parametrize("dtype", ["float32"])
def test_three_fit_steps_match_the_reference(ref, dtype):
    """``Module.fit_step`` with kvstore='tpu' and Adam at the cell's
    rehearsal sizes, as the benchmark's driver drives it: fused, one
    dispatch a step, losses and every leaf's first gradient and change
    against the reference's first steps.  (bfloat16 runs on the chip,
    in the cell; its graph's types are held by the test above.)"""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    kw = dict(KW, dtype=dtype)
    low = dtype != "float32"
    key, weights = _params(ref, kw, seed=3)
    mod = mx.Module(mx.models.get_symbol("sdar_moe", **kw),
                    context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (B, 3, S))],
             label_shapes=[("softmax_label", (B * S,))])

    class Seeded(mx.init.Initializer):
        def __call__(self, desc, arr):
            arr[:] = mx.nd.NDArray(weights[str(desc)].astype(arr.dtype),
                                   arr.context)

    mod.init_params(Seeded())
    exe = mod._exec_group._exec
    names = [n for n, _ in ref.param_specs(kw)]
    # the residual stream is float32, and with it the gains that scale it
    f32 = {n for n in names
           if n.endswith(("router_weight", "in_norm_gamma",
                          "post_norm_gamma")) or n == "tok_embed_weight"}
    assert {n for n in names if str(exe.arg_dict[n].dtype) == "float32"} \
        == (f32 if low else set(names))
    opt = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
           "epsilon": 1e-8, "wd": 0.1}
    mod.init_optimizer(kvstore="tpu", optimizer="adam",
                       optimizer_params=dict(opt, multi_precision=low))
    pool = [_batch(ref, seed, kw) for seed in range(3)]
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
    assert int(load["counts"].sum()) \
        == kw["num_layers"] * B * 2 * S * kw["top_k"]
