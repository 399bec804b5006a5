"""The index scorer's kernels (``pallas/index_scorer.py``) in interpret
mode at the widths the chip runs them at (``Di`` 64, query blocks and
key tiles of 512 rows) on short sequences: the three-part split and the
six-term product it feeds (which pin the precision: a later edit cannot
drop terms unseen), the forward row against the XLA loop's
``_scorer_chunk``, one block's gradients against the dense formulas with
sums that come in and tiles past the count left alone, and the whole
cores (``ops/sparse_attention.py`` ``_core_fwd`` / ``_core_bwd``) with
the kernels against the XLA loops: a padded tail, ``topk`` below and
above a block's first row.  The path's choice is
``tests/test_pallas_sparse_attention.py``'s last test.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu.ops import sparse_attention as sa
from mxnet_tpu.pallas import index_scorer as scorer

DI, BQ = 64, 512


def _normal(seed, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all()
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ----------------------------------------------------------------------
# the precision: three parts a value, six terms a product
# ----------------------------------------------------------------------
def test_three_parts_sum_to_the_float32_value():
    x = jnp.concatenate([_normal(0, (4096,)) * 10.0 ** _normal(1, (4096,)),
                         jnp.asarray([0.0, 1.0, -3.0, 1e-30, 65504.0])])
    parts = scorer.split3(x)
    for p in parts:     # bfloat16 holds each exactly
        assert np.array_equal(np.asarray(p.astype(jnp.bfloat16)
                                         .astype(jnp.float32)),
                              np.asarray(p))
    total = sum(np.asarray(p, np.float64) for p in parts)
    assert np.all(np.abs(total - np.asarray(x, np.float64))
                  <= 2.0 ** -24 * np.abs(np.asarray(x, np.float64)))
    assert float(jnp.abs(parts[1]).max()) > 0 < float(jnp.abs(parts[2]).max())


def test_six_terms_are_a_float32_product_and_three_are_not():
    """The side-by-side product of ``terms`` against float64: no worse
    than ``Precision.HIGHEST``'s, and far better than the three terms
    (hi.hi, hi.mid, mid.hi) a cheaper split would keep."""
    a, b = _normal(2, (256, DI)), _normal(3, (384, DI))
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64).T
    dot = lambda x, y: np.asarray(lax.dot_general(
        x, y, scorer._NT, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), np.float64)
    err = lambda got: float(np.abs(got - want).max() / np.abs(want).max())
    left, right = scorer.terms(a, scorer._LEFT), scorer.terms(b, scorer._RIGHT)
    assert left.shape == (256, 6 * DI) and left.dtype == jnp.bfloat16
    six = err(dot(left, right))
    highest = err(dot(a, b))
    three = err(dot(scorer.terms(a, (0, 0, 1)), scorer.terms(b, (0, 1, 0))))
    assert six <= 1.5 * highest and six < 5e-7, (six, highest)
    assert three > 8 * six, (three, six)
    # the order of the parts IS the six terms of HIGHEST
    assert sorted(zip(scorer._LEFT, scorer._RIGHT)) == [
        (0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (2, 0)]


# ----------------------------------------------------------------------
# one query block
# ----------------------------------------------------------------------
def _scorer_operands(S, Hi, seed=10):
    return (_normal(seed, (Hi, S, DI)), _normal(seed + 1, (S, DI)),
            _normal(seed + 2, (S, Hi), 0.3))


@pytest.mark.parametrize("S,Hi,block", [(1024, 2, 0), (1024, 4, 1),
                                        (1536, 3, 1), (1536, 2, 2)])
def test_forward_row_matches_the_xla_scorer(S, Hi, block):
    qi, ki, wi = _scorer_operands(S, Hi)
    r0, tiles = block * BQ, block + 1
    qib, wib = qi[:, r0:r0 + BQ], wi[r0:r0 + BQ]
    ib = scorer.forward(qib, wib, scorer.keys(ki), tiles, BQ, interpret=True)
    _, want = sa._scorer_chunk(qib, ki, wib)
    assert ib.shape == (BQ, S) and ib.dtype == jnp.float32
    assert _gap(ib[:, :tiles * BQ], want[:, :tiles * BQ]) < 1e-6


@pytest.mark.parametrize("S,Hi,block", [(1024, 2, 1), (1536, 4, 1),
                                        (1536, 3, 2)])
def test_backward_block_adds_into_the_sum_it_is_given(S, Hi, block):
    """``g`` (hence dqi and dwi) and the keys' gradient against
    ``jax.grad`` of a function whose gradient to ``I`` is the kernel's
    ``dI = on * (exp(I - lse) - pt)``; the sum that comes in comes back
    with this block's added, tiles past the count as they came.  The
    mask leaves out the handful of pairs with a head's product within
    1e-4 of the ReLU's kink, where float32 products that differ in
    their last bits differ in the gradient's 0 / 1."""
    qi, ki, wi = _scorer_operands(S, Hi, seed=20)
    r0, tiles = block * BQ, block + 1
    qib, wib = qi[:, r0:r0 + BQ], wi[r0:r0 + BQ]
    causal = jnp.arange(S)[None] <= (r0 + jnp.arange(BQ))[:, None]
    on = causal & (jax.random.uniform(jax.random.PRNGKey(5), (BQ, S)) < 0.3)
    z, score = sa._scorer_chunk(qib, ki, wib)
    on = on & jnp.all(jnp.abs(z) > 1e-4, axis=0)
    pt = jax.random.uniform(jax.random.PRNGKey(6), (BQ, S)) * on / 100.0
    lse = jax.nn.logsumexp(jnp.where(on, score, -1e30), axis=-1)
    sum0 = _normal(7, (DI, S))
    g, sums = scorer.backward(
        qib, wib, lse, on.astype(jnp.int8), pt, scorer.keys(ki),
        scorer.gradient_keys(ki), sum0, tiles, BQ, interpret=True)

    def loss(qib, ki, wib):
        _, score = sa._scorer_chunk(qib, ki, wib)
        return jnp.sum(jnp.where(
            on, jnp.exp(score - lse[:, None]) - pt * score, 0.0))

    dqi, dki, dwi = jax.grad(loss, argnums=(0, 1, 2))(qib, ki, wib)
    assert g.shape == (Hi, BQ, DI)
    assert _gap(wib.T[:, :, None] * g, dqi) < 1e-5
    assert _gap(jnp.sum(qib * g, axis=-1).T, dwi) < 1e-5
    assert _gap((sums - sum0).T, dki) < 1e-5
    assert np.array_equal(np.asarray(sums[:, tiles * BQ:]),
                          np.asarray(sum0[:, tiles * BQ:]))


# ----------------------------------------------------------------------
# the whole cores against the XLA loops
# ----------------------------------------------------------------------
@pytest.mark.parametrize("S,topk,Hi", [(1100, 300, 2), (1536, 700, 4)])
def test_the_operator_with_the_kernels_matches_the_xla_loops(S, topk, Hi):
    """``_core_fwd`` and ``_core_bwd`` with both kernel pairs against the
    XLA loops at blocks of 512: a padded tail (1100 -> 1536), ``topk``
    below a block's first row (300: every block past the first chooses)
    and above one (700: the second block's first rows take all their
    keys).  The choice's bits are equal but for a counted handful of
    near-ties; L and the scorer's three gradients to 1e-5."""
    Hq, Hk, D = 2, 1, 16
    bq, tile, kc, Sp = sa.plan(S, BQ, BQ)
    assert (bq, tile, Sp) == (BQ, BQ, 1536)
    pad = lambda x, axis: jnp.pad(x, [
        (0, Sp - S) if a == axis else (0, 0) for a in range(x.ndim)])
    qi, ki, wi = _scorer_operands(S, Hi, seed=30)
    ops = (pad(_normal(40, (Hq, S, D), 2.0), 1),
           pad(_normal(41, (Hk, S, D)), 1), pad(_normal(42, (Hk, S, D)), 1),
           pad(qi, 1), pad(ki, 0), pad(wi, 0))
    do = _normal(43, ops[0].shape)
    got = {}
    for impl in (False, "interpret"):
        (o, L, live), (lse, lse_i, bits) = jax.jit(
            lambda *a, impl=impl: sa._core_fwd(*a, S, topk, bq, kc, tile,
                                               impl))(*ops)
        grads = jax.jit(
            lambda *a, impl=impl: sa._core_bwd(*a, S, bq, kc, tile, impl))(
                *ops, o, lse, lse_i, bits, do, jnp.float32(1.7))
        got[impl] = (L, lse_i) + tuple(grads[3:]), bits
    flips = int(np.unpackbits(np.asarray(got[False][1])
                              ^ np.asarray(got["interpret"][1])).sum())
    assert flips <= 4, flips
    for name, want, have in zip("L lse_i dqi dki dwi".split(),
                                got[False][0], got["interpret"][0]):
        assert float(jnp.abs(want).max()) > 0, name
        assert have.shape == want.shape and have.dtype == want.dtype, name
        assert _gap(have, want) < 1e-5, name
