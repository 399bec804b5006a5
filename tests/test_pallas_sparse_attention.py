"""The sparse indexed attention's cores as Pallas kernels
(``pallas/sparse_attention.py``) in interpret mode: one query block
against the dense formulas under a hand-made mask (rows whose leading
key tiles hold no chosen pair, sums that come in and go out in place,
tiles past the count left alone); the whole forward and backward cores
against the XLA loops they replace on the chip
(``ops/sparse_attention.py`` ``_core_fwd`` / ``_core_bwd``) on the same
operands and the same bits: several query blocks, a padded length,
``topk >= S``, one and eight query heads a group, query blocks and key
tiles of different sizes, bfloat16; and the choice between the paths.
The operator through its ``custom_vjp`` with the kernels forced is
``tests/test_keye_vl2.py``'s ``cores`` cases.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import sparse_attention as sa
from mxnet_tpu.pallas import sparse_attention as kernels
from mxnet_tpu.pallas.dispatch import PALLAS_FALLBACKS, PALLAS_LAUNCHES

HI, DI = 4, 8


def _forget_builds():
    kernels._run_forward.clear_cache()
    kernels._run_backward.clear_cache()


def _normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-9))


# ----------------------------------------------------------------------
# one query block against the dense formulas
# ----------------------------------------------------------------------
def _block(R, dtype=jnp.float32, Hk=2, bq=16, tile=8, S=48, D=8):
    """A block of 16 rows at rows 16..31 of 48: four key tiles up to its
    diagonal, two past it.  The even rows' chosen keys all lie in the
    LAST two tiles up to the diagonal (their first two hold nothing)."""
    q = _normal(1, (Hk, R, bq, D), dtype) * 2
    k, v = _normal(2, (Hk, S, D), dtype), _normal(3, (Hk, S, D), dtype)
    row = 16 + np.arange(bq)[:, None]
    col = np.arange(S)[None, :]
    on = (col <= row) & (np.random.default_rng(0).random((bq, S)) < 0.4)
    on[::2, :16] = False
    on[np.arange(bq), row[:, 0]] = True         # a row keeps its own key
    assert not on[::2, :16].any() and on[1::2, :16].any()
    return q, k, v, jnp.asarray(on), 4


def _dense(q, k, v, on):
    """(o, lse, pt, p) of the block, plainly."""
    f32 = jnp.float32
    s = jnp.einsum("grqd,gkd->grqk", q.astype(f32), k.astype(f32)) \
        * q.shape[-1] ** -0.5
    s = jnp.where(on, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("grqk,gkd->grqd", p, v.astype(f32))
    return o, lse, jnp.mean(p, axis=(0, 1)), p


@pytest.mark.parametrize("R", [1, 8])
def test_forward_block_under_a_mask_with_empty_leading_tiles(R):
    q, k, v, on, tiles = _block(R)
    o, lse, pt = kernels.forward(q, k, v, on.astype(jnp.int8), tiles, 8,
                                 interpret=True)
    want_o, want_lse, want_pt, _ = _dense(q, k, v, on)
    assert o.shape == q.shape and o.dtype == q.dtype
    assert lse.shape == q.shape[:3] and pt.shape == on.shape
    assert _gap(o, want_o) < 2e-6
    assert _gap(lse, want_lse) < 2e-6
    assert _gap(pt[:, :32], want_pt[:, :32]) < 2e-6
    # a row's probabilities sum to one over its chosen keys
    np.testing.assert_allclose(np.asarray(pt[:, :32]).sum(-1), 1.0,
                               rtol=1e-5)


@pytest.mark.parametrize("R", [1, 8])
def test_backward_block_adds_into_the_sums_it_is_given(R):
    """dq, dk, dv and pt against ``jax.vjp`` of the dense formulas; the
    sums that come in come back with this block's added, and the key
    tiles past the count come back as they came."""
    q, k, v, on, tiles = _block(R)
    do = _normal(4, q.shape)
    o, lse, _, _ = _dense(q, k, v, on)
    di = jnp.sum(do * o, axis=-1)
    dk0, dv0 = _normal(5, k.shape), _normal(6, v.shape)
    dq, dk, dv, pt = kernels.backward(
        q, do, lse, di, on.astype(jnp.int8), k, v, dk0, dv0, tiles, 8,
        interpret=True)
    (_, _, want_pt, _), pull = jax.vjp(lambda *a: _dense(*a, on), q, k, v)
    zero = lambda t: jnp.zeros_like(t)
    want = pull((do, zero(lse), zero(want_pt),
                 jnp.zeros(q.shape[:3] + (k.shape[1],))))
    assert dq.dtype == q.dtype and dk.dtype == dv.dtype == jnp.float32
    assert _gap(dq, want[0]) < 5e-6
    assert _gap(dk - dk0, want[1]) < 5e-6
    assert _gap(dv - dv0, want[2]) < 5e-6
    assert _gap(pt[:, :32], want_pt[:, :32]) < 2e-6
    assert np.array_equal(np.asarray(dk[:, 32:]), np.asarray(dk0[:, 32:]))
    assert np.array_equal(np.asarray(dv[:, 32:]), np.asarray(dv0[:, 32:]))


# ----------------------------------------------------------------------
# the whole cores against the XLA loops, on the same bits
# ----------------------------------------------------------------------
def _padded_operands(S, Sp, Hq, Hk, D, dtype):
    pad = lambda x, axis: jnp.pad(x, [
        (0, Sp - S) if a == axis else (0, 0) for a in range(x.ndim)])
    return (pad(_normal(10, (Hq, S, D), dtype) * 2, 1),
            pad(_normal(11, (Hk, S, D), dtype), 1),
            pad(_normal(12, (Hk, S, D), dtype), 1),
            pad(_normal(13, (HI, S, DI)), 1), pad(_normal(14, (S, DI)), 0),
            pad(_normal(15, (S, HI)) * 0.3, 0))


CORES = {
    # S, topk, q_chunk, kv_chunk, Hq, Hk, dtype
    "five_blocks": (40, 12, 8, 8, 4, 2, jnp.float32),
    "padded_length": (37, 9, 8, 8, 4, 2, jnp.float32),
    "topk_at_least_the_length": (40, 40, 8, 8, 4, 2, jnp.float32),
    "one_head_a_group": (40, 12, 8, 8, 2, 2, jnp.float32),
    "eight_heads_a_group": (40, 12, 8, 8, 8, 1, jnp.float32),
    "blocks_of_two_tiles": (64, 5, 16, 8, 4, 2, jnp.float32),
    "tiles_of_two_blocks": (48, 7, 8, 16, 4, 2, jnp.float32),
    "bfloat16": (40, 12, 8, 8, 4, 2, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(CORES))
def test_the_cores_match_the_xla_loops(case):
    """Every output of ``_core_fwd`` and ``_core_bwd`` with the kernels
    against the XLA loops: the result, the index loss, the rows'
    statistics, the SAME bits and live tiles, and all six gradients (the
    scorer's read the kernels' head-mean probabilities)."""
    S, topk, qc, kvc, Hq, Hk, dtype = CORES[case]
    bq, tile, kc, Sp = sa.plan(S, qc, kvc)
    ops = _padded_operands(S, Sp, Hq, Hk, 8, dtype)
    do = _normal(16, ops[0].shape)
    got = {}
    for impl in (False, "interpret"):
        (o, L, live), (lse, lse_i, bits) = jax.jit(
            lambda *a, impl=impl: sa._core_fwd(*a, S, topk, bq, kc, tile,
                                               impl))(*ops)
        grads = jax.jit(
            lambda *a, impl=impl: sa._core_bwd(*a, S, bq, kc, tile, impl))(
                *ops, o, lse, lse_i, bits, do, jnp.float32(1.7))
        got[impl] = (o, L, lse, lse_i) + tuple(grads), (live, bits)
    for a, b in zip(got[False][1], got["interpret"][1]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    names = "o L lse lse_i dq dk dv dqi dki dwi".split()
    for name, want, have in zip(names, got[False][0], got["interpret"][0]):
        assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0, name
        assert have.dtype == want.dtype, name
        assert _gap(have, want) < tol, name


# ----------------------------------------------------------------------
# which path runs
# ----------------------------------------------------------------------
def _cell_shapes(D=128, dtype=jnp.bfloat16, S=16384):
    return (jax.ShapeDtypeStruct((1, 32, S, D), dtype),
            jax.ShapeDtypeStruct((1, 4, S, D), dtype))


def _scorer_shape(Di=64, Hi=16, S=16384):
    return jax.ShapeDtypeStruct((1, Hi, S, Di), jnp.float32)


@pytest.mark.parametrize("why,kw,plan", [
    ("narrow heads", dict(D=64), (512, 512, 16384)),
    ("half precision of another kind", dict(dtype=jnp.float16),
     (512, 512, 16384)),
    ("blocks of 256", {}, (256, 512, 16384)),
    ("tiles of 256", {}, (512, 256, 16384)),
    ("more heads than VMEM holds", dict(D=512), (512, 512, 16384)),
])
def test_shapes_the_kernels_refuse(why, kw, plan):
    assert kernels.supported(*_cell_shapes(), 512, 512, 16384)[0]
    assert kernels.supported(*_cell_shapes(dtype=jnp.float32), 512, 512,
                             8192)[0]
    ok, said = kernels.supported(*_cell_shapes(**kw), *plan)
    assert not ok and "head_dim" in said, why


def test_the_choice_is_counted_and_has_no_knob(monkeypatch):
    """On the CPU the cores are the XLA loops and book
    ``pallas_fallbacks{reason="backend"}``; ``impl="interpret"`` books a
    build of ``sparse_attention`` and of ``sparse_attention_bwd``, which
    two layers of one geometry share; in a one-device TPU program the
    cell's shapes take the kernels, other shapes book
    ``sparse-attention-geometry`` and a mesh books ``mesh``.  Nothing
    reads the environment."""
    import mxnet_tpu as mx
    _forget_builds()
    count = lambda reason: PALLAS_FALLBACKS.labels(reason=reason).value
    built = lambda: (
        PALLAS_LAUNCHES.labels(kernel="sparse_attention").value,
        PALLAS_LAUNCHES.labels(kernel="sparse_attention_bwd").value)
    ops = tuple(t[None] for t in _padded_operands(40, 40, 4, 2, 8,
                                                  jnp.float32))

    def two_layers(impl):
        def loss(*a):
            total = 0.0
            for _ in range(2):
                o, L, _ = sa.sparse_indexed_attention(
                    lambda *b: b, a, topk=12, q_chunk=8, kv_chunk=8,
                    impl=impl)
                total = total + jnp.sum(o * o) + jnp.sum(L)
            return total
        return jax.jit(jax.grad(loss, argnums=tuple(range(6))))(*ops)

    before, (f0, b0), environ = count("backend"), built(), dict(os.environ)
    want = two_layers(None)
    assert count("backend") == before + 2 and built() == (f0, b0)
    have = two_layers("interpret")
    assert built() == (f0 + 1, b0 + 1) and count("backend") == before + 2
    for a, b in zip(have, want):
        assert _gap(a, b) < 2e-5
    assert dict(os.environ) == environ

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # a one-device program: an earlier test's multi-context bind in this
    # process leaves its 'dp' mesh in the registry
    from mxnet_tpu.parallel import mesh as mesh_mod
    monkeypatch.setitem(mesh_mod._CURRENT, "mesh", None)
    cell = (512, 512, 16384)
    assert sa._cores_impl(*_cell_shapes(), _scorer_shape(), *cell) \
        == "compiled"
    before = count("sparse-attention-geometry")
    assert sa._cores_impl(*_cell_shapes(D=192), _scorer_shape(),
                          *cell) is False
    assert sa._cores_impl(*_cell_shapes(), _scorer_shape(), 8, 8,
                          16384) is False
    # the scorer's kernels take a width of 64 and what VMEM holds
    assert sa._cores_impl(*_cell_shapes(), _scorer_shape(Di=128),
                          *cell) is False
    assert sa._cores_impl(*_cell_shapes(), _scorer_shape(Hi=32),
                          *cell) is False
    assert count("sparse-attention-geometry") == before + 4
    before = count("mesh")
    mx.sharding.set_mesh({"dp": 4, "mp": 2})
    try:
        assert sa._cores_impl(*_cell_shapes(), _scorer_shape(),
                              *cell) is False
    finally:
        mx.sharding.set_mesh(None)
    assert count("mesh") == before + 1
