"""Mixture-of-Experts with expert parallelism (parallel/moe.py — new
TPU-native capability; the reference predates MoE, SURVEY.md §2.3).
Pins: switch_moe equals the dense oracle when capacity is ample,
capacity overflow drops tokens, gradients reach router AND experts,
training descends, and the ep-sharded jit matches the unsharded run."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mxnet_tpu.parallel import (switch_moe, moe_reference,
                                init_moe_params)


def _params(seed=0, d=8, h=16, E=4):
    return init_moe_params(jax.random.key(seed), d, h, E)


def test_top1_matches_reference_with_ample_capacity():
    """top-1 with capacity >= N: every token reaches its argmax expert,
    so switch_moe equals the dense oracle restricted to the top gate."""
    params = _params()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 8).astype("float32"))
    y, aux = switch_moe(params, x, k=1, capacity_factor=16.0)
    # oracle: route each token to argmax expert with its softmax weight
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    top = jnp.argmax(probs, axis=-1)
    h = jnp.einsum("nd,edh->neh", x, params["w1"]) + params["b1"][None]
    h = jax.nn.relu(h)
    ye = jnp.einsum("neh,ehd->ned", h, params["w2"]) + params["b2"][None]
    want = ye[jnp.arange(16), top] * probs[jnp.arange(16), top][:, None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert float(aux) > 0


def test_topk_full_capacity_matches_dense_reference():
    """k = E with ample capacity = every token through every expert =
    the dense mixture oracle."""
    params = _params(seed=1)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(12, 8).astype("float32"))
    y, _ = switch_moe(params, x, k=4, capacity_factor=16.0)
    want = moe_reference(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_capacity_overflow_drops_tokens():
    """With capacity 1 and all tokens forced to one expert, only the
    first token per expert survives (standard Switch dropping)."""
    params = _params(seed=2)
    # router that sends everything to expert 0
    params = dict(params)
    router = np.zeros((8, 4), "float32")
    router[:, 0] = 10.0
    params["router"] = jnp.asarray(router)
    rng = np.random.RandomState(2)
    # all-positive tokens: x @ router puts every token's expert-0 logit
    # at +10*sum(x) >> others, so routing really is all-to-expert-0
    x = jnp.asarray((np.abs(rng.randn(6, 8)) + 0.1).astype("float32"))
    y, _ = switch_moe(params, x, k=1, capacity_factor=1.0 / 6 + 1e-6)
    out = np.asarray(y)
    # capacity C=1: token 0 processed, tokens 1.. dropped to zeros
    assert np.abs(out[0]).sum() > 0
    np.testing.assert_allclose(out[1:], 0.0, atol=1e-6)


def test_gradients_reach_router_and_experts():
    params = _params(seed=3)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(16, 8).astype("float32"))
    tgt = jnp.asarray(rng.randn(16, 8).astype("float32"))

    def loss(p):
        y, aux = switch_moe(p, x, k=2, capacity_factor=2.0)
        return jnp.mean((y - tgt) ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    for name in ("router", "w1", "w2"):
        gn = float(jnp.abs(g[name]).sum())
        assert gn > 0, name


def test_moe_training_descends_and_specializes():
    params = _params(seed=4, d=8, h=16, E=4)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(64, 8).astype("float32"))
    tgt = jnp.asarray(np.tanh(rng.randn(8, 8)).astype("float32"))
    y_true = jnp.tanh(x @ tgt)

    @jax.jit
    def step(p):
        def loss(p):
            y, aux = switch_moe(p, x, k=2, capacity_factor=2.0)
            return jnp.mean((y - y_true) ** 2) + 0.01 * aux
        l, g = jax.value_and_grad(loss)(p)
        return l, jax.tree.map(lambda a, b: a - 0.1 * b, p, g)

    l0, params = step(params)
    for _ in range(60):
        l1, params = step(params)
    assert float(l1) < float(l0) * 0.6, (float(l0), float(l1))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >=4 devices")
def test_ep_sharded_matches_unsharded():
    """jit over an ep mesh with the expert axis sharded produces the
    same numbers as the single-device run (GSPMD inserts the
    all-to-alls; results must be placement-invariant)."""
    params = _params(seed=5)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(32, 8).astype("float32"))
    want, aux_want = switch_moe(params, x, k=2, capacity_factor=2.0)

    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    eshard = NamedSharding(mesh, P("ep"))
    repl = NamedSharding(mesh, P())
    placed = {
        k: jax.device_put(v, eshard if v.shape[0] == 4 and v.ndim >= 2
                          else repl)
        for k, v in params.items()}
    xs = jax.device_put(x, repl)

    @jax.jit
    def f(p, x):
        return switch_moe(p, x, k=2, capacity_factor=2.0, mesh=mesh)

    got, aux_got = f(placed, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux_got), float(aux_want),
                               rtol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >=4 devices")
def test_ep_sharded_training_descends():
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    params = _params(seed=6)
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(32, 8).astype("float32"))
    y_true = jnp.tanh(x @ jnp.asarray(
        np.tanh(rng.randn(8, 8)).astype("float32")))

    @jax.jit
    def step(p):
        def loss(p):
            y, aux = switch_moe(p, x, k=1, capacity_factor=2.0,
                                mesh=mesh)
            return jnp.mean((y - y_true) ** 2) + 0.01 * aux
        l, g = jax.value_and_grad(loss)(p)
        return l, jax.tree.map(lambda a, b: a - 0.1 * b, p, g)

    l0, params = step(params)
    for _ in range(40):
        l1, params = step(params)
    assert float(l1) < float(l0), (float(l0), float(l1))


def test_switch_moe_symbol_op_and_moe_transformer():
    """SwitchMoE as a graph operator + the MoE transformer variant
    (models/transformer.py moe_experts) trains through TrainStep."""
    import mxnet_tpu as mx
    from mxnet_tpu import models, nd
    from mxnet_tpu.parallel import TrainStep

    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(6, 8).astype("float32"))
    router = nd.array(rng.randn(8, 4).astype("float32") * 0.2)
    w1 = nd.array(rng.randn(4, 8, 16).astype("float32") * 0.2)
    b1 = nd.zeros((4, 16))
    w2 = nd.array(rng.randn(4, 16, 8).astype("float32") * 0.2)
    b2 = nd.zeros((4, 8))
    y, aux = nd.contrib.SwitchMoE(x, router, w1, b1, w2, b2,
                                  num_experts=4, num_hidden=16)
    # (positional inputs bind in declaration order: router_weight,
    # expert_up_weight, expert_up_bias, expert_down_weight,
    # expert_down_bias)
    assert y.shape == (6, 8)
    assert float(aux.asnumpy()) > 0

    symb = models.get_symbol("transformer", num_classes=61, num_layers=4,
                             d_model=32, num_heads=4, seq_len=12,
                             moe_experts=4, moe_every=2)
    # shape inference sized the expert stacks from the rule
    args = dict(zip(symb.list_arguments(),
                    symb.infer_shape(data=(4, 12),
                                     softmax_label=(48,))[0]))
    assert args["layer1_moe_expert_up_weight"] == (4, 32, 128)
    assert args["layer1_moe_expert_up_bias"] == (4, 128)
    ts = TrainStep(symb, mx.optimizer.Adam(learning_rate=2e-3),
                   data_shapes={"data": (4, 12)},
                   label_shapes={"softmax_label": (48,)})
    ts.init_params(mx.init.Xavier())
    tokens = rng.randint(0, 61, (4, 12)).astype("float32")
    labels = np.roll(tokens, -1, axis=1).reshape(-1)
    batch = {"data": tokens, "softmax_label": labels}

    def loss_of(outs):
        p = np.asarray(outs[0])
        return -np.log(np.maximum(
            p[np.arange(48), labels.astype(int)], 1e-9)).mean()

    outs = ts.step(batch)
    first = loss_of(outs)
    assert float(np.asarray(outs[1])) > 0     # aux loss head present
    for _ in range(80):
        outs = ts.step(batch)
    assert loss_of(outs) < first * 0.5


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >=4 devices")
def test_ep_sharded_grads_match_unsharded():
    """Gradient parity under expert parallelism: differentiating
    THROUGH the GSPMD all-to-alls must give the same router and expert
    gradients as the single-device run (placement-invariant backward,
    the property the ep-sharded training arm relies on)."""
    params = _params(seed=9)
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(32, 8).astype("float32"))
    y_true = jnp.asarray(rng.randn(32, 8).astype("float32"))

    def loss(p, mesh=None):
        y, aux = switch_moe(p, x, k=2, capacity_factor=2.0, mesh=mesh)
        return jnp.mean((y - y_true) ** 2) + 0.01 * aux

    g_ref = jax.grad(loss)(params)

    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    eshard = NamedSharding(mesh, P("ep"))
    repl = NamedSharding(mesh, P())
    placed = {
        k: jax.device_put(v, eshard if v.shape[0] == 4 and v.ndim >= 2
                          else repl)
        for k, v in params.items()}
    g_ep = jax.jit(jax.grad(lambda p: loss(p, mesh=mesh)))(placed)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_ep[k]),
                                   np.asarray(g_ref[k]),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg="grad %s diverged" % k)
        # the expert-dim sharding survived the grad transpose
        if params[k].shape[0] == 4 and params[k].ndim >= 2:
            assert "ep" in str(g_ep[k].sharding)


# ----------------------------------------------------------------------
# the dropless layer's sums over a token's rows, in token order
# ----------------------------------------------------------------------
def _sum_cases():
    """(k, experts an expert held, dtype, routing): the four cells' k
    and shares and ZAYA-free extremes, then the routings the issue
    names, each at one k."""
    cases = [(k, share, dtype, "even") for dtype in ("float32", "bfloat16")
             for share in (4, 8, 16, 1) for k in (2, 6, 8, 10)]
    cases += [(6, 4, dtype, routing) for dtype in ("float32", "bfloat16")
              for routing in ("empty-tile", "all-held-token", "none-held",
                              "collapsed")]
    return cases


def _routing(rng, routing, N, k, held, E, first):
    """(N, k) expert choices: ``even`` any k distinct experts;
    ``empty-tile`` the second tile of 256 tokens chooses none held here;
    ``all-held-token`` every 32nd token chooses k held ones;
    ``none-held`` nobody does; ``collapsed`` everybody's k are held (the
    slab arm)."""
    inside = np.arange(first, first + held)
    outside = np.setdiff1d(np.arange(E), inside)
    draw = lambda pool: rng.permutation(pool)[:k]
    e = np.stack([draw(np.arange(E)) for _ in range(N)])
    if routing == "empty-tile":
        e[256:512] = np.stack([draw(outside) for _ in range(256)])
    if routing == "all-held-token":
        e[::32] = np.stack([draw(inside) for _ in range(len(e[::32]))])
    if routing == "none-held":
        e = np.stack([draw(outside) for _ in range(N)])
    if routing == "collapsed":
        e = np.stack([draw(inside) for _ in range(N)])
    return jnp.asarray(e, jnp.int32)


@pytest.mark.parametrize("k, share, dtype, routing", _sum_cases())
def test_token_ordered_sum_is_the_scatter_add(k, share, dtype, routing):
    """Where ``k > 1`` the combine and the gather's gradient are sums
    over a token's rows; with the kernels (interpreted here) they run as
    ``pallas/token_sum.py``'s token-ordered sum.  Against the layer as
    XLA runs it, the sums as ``.at[token].add``: the result and the
    gradients for
    ``x``, ``weights`` and the three expert stacks, at the four cells' k
    and shares of experts held, with rows past the real ones, a tile of
    256 tokens that holds no pair, tokens whose every choice is held,
    nothing held at all, and a routing that takes the slab arm."""
    from mxnet_tpu.parallel import moe
    from mxnet_tpu.pallas.dispatch import PALLAS_LAUNCHES
    from mxnet_tpu.pallas.token_sum import token_sum
    rng = np.random.default_rng(k * 100 + share)
    d, F = 128, 32
    # sizes the interpreted grouped products take: rows in whole tiles
    def whole_tiles(N, held):
        rows = moe._row_buckets(N, k, held, held * share)[0]
        return k <= held and rows % moe._gmm_tiling(rows, held, 1, 1)[0] == 0

    N, held = next((N, held) for N in (1024, 2048)
                   for held in (16, 8, 32, 4, 64) if whole_tiles(N, held))
    E, first = held * share, held * (share > 1)
    buckets = moe._row_buckets(N, k, held, E)
    e = _routing(rng, routing, N, k, held, E, first)
    real = int(((e >= first) & (e < first + held)).sum())
    if routing == "collapsed":
        assert len(buckets) == 2 and real == N * k > buckets[0]
    elif share > 1:
        assert real < buckets[0]        # rows past the real ones
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    w = jax.nn.softmax(draw(N, k), -1)
    x = draw(N, d).astype(dtype)
    wg, wu = (draw(held, F, d) * d ** -0.5).astype(dtype), \
        (draw(held, F, d) * d ** -0.5).astype(dtype)
    wd = (draw(held, d, F) * F ** -0.5).astype(dtype)
    cot = draw(N, d)

    def both(impl, x, w, wg, wu, wd):
        def loss(*a):
            y = moe.dropless_topk_experts(a[0], e, *a[1:], E, first,
                                          impl=impl)[0]
            return jnp.sum(y.astype(jnp.float32) * cot), y
        (_, y), grads = jax.value_and_grad(loss, (0, 1, 2, 3, 4),
                                           has_aux=True)(x, w, wg, wu, wd)
        return (y,) + grads

    launches = PALLAS_LAUNCHES.labels(kernel="token_sum")
    token_sum.clear_cache()     # a launch is counted where a kernel is built
    before = launches.value
    got = jax.jit(lambda *a: both("interpret", *a))(x, w, wg, wu, wd)
    assert launches.value > before
    before = launches.value
    want = jax.jit(lambda *a: both(False, *a))(x, w, wg, wu, wd)
    assert launches.value == before
    # float32: the same products summed in another order; bfloat16: the
    # grouped products round apart, and the gather's gradient is rounded
    # once where the scatter-add rounds every addition (the sums alone,
    # to float32's last bits: the next test)
    tol = 2e-6 if dtype == "float32" else 2e-2
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert a.dtype == b.dtype and np.isfinite(a).all()
        if routing == "none-held":
            assert np.abs(a).max() == 0.0
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30)
    if routing == "empty-tile":
        assert np.abs(np.asarray(got[0][256:512], np.float64)).max() == 0.0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("weighted", [True, False])
def test_token_sum_keeps_float32_weights_and_sums(weighted, dtype):
    """The sum alone against ``.at[token].add`` of the float32 products
    ``c * v``: bfloat16 rows under float32 weights come out to float32's
    last bits (the weight's three bfloat16 parts, no rounding of the
    weight or of the weighted row), a row keyed past the last token adds
    nothing, an empty tile of 256 tokens is zeros, and the other
    direction is the gather and the row dot."""
    from mxnet_tpu.parallel import moe
    rng = np.random.default_rng(7)
    rows, N, d = 1152, 1024, 128
    key = rng.integers(0, N, rows)
    key = np.where((key >= 512) & (key < 768), key - 512, key)  # no row
    key[rng.random(rows) < 0.2] = N                             # no token
    key = jnp.asarray(key, jnp.int32)
    v = jnp.asarray(rng.standard_normal((rows, d)), dtype)
    c = jnp.asarray(rng.random(rows) + 0.5, jnp.float32) if weighted else None
    cot = jnp.asarray(rng.standard_normal((N, d)), jnp.float32)
    perm = jnp.argsort(key)

    def kernel(c, v):
        return moe._summed(c, v, key, perm, jnp.take(key, perm), N,
                           jnp.float32, "interpret")

    def scatter(c, v):
        v = v.astype(jnp.float32) * (1 if c is None else c[:, None])
        return jnp.zeros((N, d), jnp.float32).at[key].add(v, mode="drop")

    got, pull = jax.vjp(kernel, c, v)
    want, pull_want = jax.vjp(scatter, c, v)
    assert float(jnp.abs(got[512:768]).max()) == 0.0
    tol = 1e-6 * float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= tol
    for a, b in zip(pull(cot), pull_want(cot)):
        if b is not None:
            assert a.dtype == b.dtype
            assert float(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32)).max()) \
                <= 1e-6 * float(jnp.abs(b.astype(jnp.float32)).max())


# ----------------------------------------------------------------------
# the small arm's own backward
# ----------------------------------------------------------------------
def _masked_dense(act, x, e, w, wg, wu, wd, first):
    """Every held expert over every token in float32, a mask keeping its
    pairs: the layer with no sorting, buffer or arm."""
    gate = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
    x, wg, wu, wd = (t.astype(jnp.float32) for t in (x, wg, wu, wd))
    y = jnp.zeros_like(x)
    for i in range(wg.shape[0]):
        mine = jnp.sum(jnp.where(e == first + i, w, 0.0), -1, keepdims=True)
        y = y + mine * ((gate(x @ wg[i].T) * (x @ wu[i].T)) @ wd[i].T)
    return y


def _held_pairs(rng, routing, N, k, held, E, first, size):
    """(N, k) choices of distinct experts: ``even`` among all;
    ``collapsed`` all held (every slab of the worst case is full);
    ``empty`` none held; ``one-past`` exactly ``size + 1`` pairs held,
    the fewest that take the slab arm."""
    inside = np.arange(first, first + held)
    outside = np.setdiff1d(np.arange(E), inside)
    pool = {"even": np.arange(E), "collapsed": inside}.get(routing, outside)
    e = np.stack([rng.permutation(pool)[:k] for _ in range(N)])
    if routing == "one-past":
        full, rest = divmod(size + 1, k)
        e[:full] = np.stack([rng.permutation(inside)[:k]
                             for _ in range(full)])
        e[full, :rest] = rng.permutation(inside)[:rest]
    return jnp.asarray(e, jnp.int32)


@pytest.mark.parametrize("impl", ["interpret", False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", ["even", "collapsed", "empty", "one-past"])
@pytest.mark.parametrize("act", ["silu", "relu"])
def test_sized_backward_matches_the_masked_dense_form(act, routing, dtype,
                                                      impl):
    """With two buffer sizes the layer's gradient is its own: the small
    arm keeps its gate and up products and goes back from them by hand
    (six grouped products, the router weights' gradient a row dot over
    ``hidden``), the worst arm runs each slab again.  All five gradients
    (``x``, ``weights``, the three stacks) against ``jax.grad`` of the
    masked dense form in float32: 256 tokens (a tile of the
    token-ordered sum), 3 choices of 16 experts, 4 held, a buffer of 256
    or 768 rows; with the Pallas kernels interpreted and as XLA runs
    it."""
    from mxnet_tpu.parallel import moe
    N, d, F, E, k, first, held = 256, 128, 32, 16, 3, 4, 4
    size, worst = moe._row_buckets(N, k, held, E)
    assert (size, worst) == (256, 768)
    rng = np.random.default_rng(46)
    e = _held_pairs(rng, routing, N, k, held, E, first, size)
    assert (np.sort(np.asarray(e), -1)[:, 1:]
            != np.sort(np.asarray(e), -1)[:, :-1]).all()
    real = int(((e >= first) & (e < first + held)).sum())
    assert {"collapsed": real == worst, "empty": real == 0,
            "one-past": real == size + 1, "even": 0 < real <= size}[routing]
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    w = jax.nn.softmax(draw(N, k), -1)
    x = draw(N, d).astype(dtype)
    wg, wu = ((draw(held, F, d) * d ** -0.5).astype(dtype) for _ in "gu")
    wd = (draw(held, d, F) * F ** -0.5).astype(dtype)
    cot = draw(N, d)

    def grads(layer):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(layer(*a).astype(jnp.float32) * cot),
            (0, 1, 2, 3, 4)))(x, w, wg, wu, wd)

    got = grads(lambda x, w, *stacks: moe.dropless_topk_experts(
        x, e, w, *stacks, E, first, impl=impl, act=act)[0])
    want = grads(lambda x, w, *stacks: _masked_dense(
        act, x, e, w, *stacks, first))
    # float32: the same products summed in another order; bfloat16: the
    # layer rounds each product where the dense form rounds nothing
    tol = 5e-5 if dtype == "float32" else 2e-2
    for name, a, b in zip(("x", "weights", "gate", "up", "down"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.isfinite(a).all(), name
        if routing == "empty":
            assert np.abs(a).max() == 0.0, name
        else:
            assert np.abs(b).max() > 0, name
        assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30), \
            (name, np.abs(a - b).max(), np.abs(b).max())
