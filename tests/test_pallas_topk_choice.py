"""The choice's kernel (``pallas/topk_choice.py``) in interpret mode
against its rule and oracle, ``ops/sparse_attention.py`` ``choose``: the
int8 mask equals ``choose``'s to the bit, and the packed bits equal
``_pack``'s of it, on random rows; on planted ties
that fit a row's room and ties that do not (the kernel applies the tie
rule itself, to every row); on zeros of both signs, negative scores and
rows of one value; on rows with no more than ``topk`` causal columns; at
1, 3 and all key tiles up to the diagonal with garbage planted past the
diagonal (what the scorer's kernel leaves there is not defined); at a
padded length; at the chip's block and tile of 512 and at the small
blocks the operator's other tests use.  ``supported`` refuses other
dtypes and tile sizes with a reason; the kernel's build is counted.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import sparse_attention as sa
from mxnet_tpu.pallas import topk_choice

FIVE = np.array([-1.5, -0.0, 0.0, 0.25, 3.0], np.float32)


def _oracle(ib, r0, topk):
    bq, Sp = ib.shape
    row = r0 + jnp.arange(bq, dtype=jnp.int32)
    causal = jnp.arange(Sp, dtype=jnp.int32)[None, :] <= row[:, None]
    return np.asarray(sa.choose(ib, causal, topk)), np.asarray(causal)


def _check(scores, block, bq, tile, topk, garbage=np.nan):
    """The kernel on query block ``block`` of ``scores`` (bq, Sp), the
    tiles past the diagonal overwritten with ``garbage``, against
    ``choose`` on the clean row; returns the mask."""
    r0 = block * bq
    n = (r0 + bq + tile - 1) // tile
    want, causal = _oracle(jnp.asarray(scores), r0, topk)
    dirty = np.array(scores)
    dirty[:, n * tile:] = garbage
    kc = sa.plan(scores.shape[1], bq, tile)[2]
    got, bits = topk_choice.choose(
        jnp.asarray(dirty), jnp.int32(r0), jnp.int32(n), topk, tile, kc,
        interpret=True)
    got = np.asarray(got)
    assert got.dtype == np.int8 and set(np.unique(got)) <= {0, 1}
    assert np.array_equal(got.astype(bool), want)
    assert not got[~causal].any()
    assert bits.dtype == jnp.uint8
    assert np.array_equal(np.asarray(bits),
                          np.asarray(sa._pack(jnp.asarray(want), kc)))
    return got


def _scores(kind, shape, seed):
    rng = np.random.RandomState(seed)
    if kind == "normal":
        return rng.randn(*shape).astype(np.float32)
    if kind == "five_values":       # nearly every row's ties overflow
        return rng.choice(FIVE, shape)
    if kind == "negative":
        return -np.abs(rng.randn(*shape)).astype(np.float32) - 0.5
    if kind == "zeros_of_both_signs":
        return rng.choice(np.array([-0.0, 0.0], np.float32), shape)
    if kind == "equal_rows":        # every row one value, its own
        return np.broadcast_to(rng.randn(shape[0], 1).astype(np.float32),
                               shape).copy()
    if kind == "few_ties":          # distinct but for a planted few
        x = rng.randn(*shape).astype(np.float32)
        x[:, ::97] = 0.125
        return x
    if kind == "wide_range":        # every exponent, infinities, NaNs
        x = (rng.randn(*shape) * 10.0 ** rng.randint(-30, 30, shape)) \
            .astype(np.float32)
        x[:, 5::211] = np.inf
        x[:, 7::223] = -np.inf
        x[:, 11::227] = np.nan
        return x
    raise ValueError(kind)


KINDS = ["normal", "five_values", "negative", "zeros_of_both_signs",
         "equal_rows", "few_ties", "wide_range"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("block", [0, 2, 7])
def test_the_mask_is_chooses_to_the_bit(kind, block):
    """Blocks and tiles of 128 on a row of 1024: 1, 3 and all 8 tiles up
    to the diagonal, NaN planted past it; ``topk`` 100 lies inside the
    first block, so its rows have no more than ``topk`` causal columns
    up to row 99 and more from there."""
    scores = _scores(kind, (128, 1024), seed=block + 1)
    got = _check(scores, block, 128, 128, topk=100)
    if kind in ("normal", "negative"):      # no ties: exactly topk a row
        full = np.arange(block * 128, block * 128 + 128) >= 100
        assert (got.sum(axis=1)[full] == 100).all()


@pytest.mark.parametrize("garbage", [np.inf, -np.inf, 3e38, 0.0])
def test_what_lies_past_the_diagonal_is_never_read(garbage):
    scores = _scores("five_values", (128, 1024), seed=3)
    _check(scores, 2, 128, 128, topk=100, garbage=garbage)


@pytest.mark.parametrize("kind,room", [
    ("fit", 40), ("overflow_by_one", 9), ("overflow_widely", 3),
    ("fit_exactly", 10)])
def test_planted_ties_that_fit_the_room_and_ties_that_do_not(kind, room):
    """Every row holds ten ties at the threshold, spread over the tiles,
    below ``topk - room`` larger scores: with room for all ten the ties
    are taken whole; with less, the lower columns win."""
    bq = tile = 128
    topk, block = 60, 5
    rng = np.random.RandomState(11)
    scores = -1.0 - np.abs(rng.randn(bq, 1024)).astype(np.float32)
    for r in range(bq):
        cols = rng.permutation(block * bq)      # causal for every row
        scores[r, cols[:topk - room]] = 5.0 + rng.rand(topk - room)
        scores[r, cols[topk - room:topk - room + 10]] = 2.0
    got = _check(scores, block, bq, tile, topk)
    ties = (scores == 2.0) & got.astype(bool)
    assert (ties.sum(axis=1) == min(room, 10)).all()
    for r in (0, 63, 127):      # the lower columns, and no other
        where = np.flatnonzero(scores[r] == 2.0)
        assert np.array_equal(np.flatnonzero(ties[r]), where[:min(room, 10)])


@pytest.mark.parametrize("case,bq,tile,Sp,topk,blocks", [
    ("the_chips_blocks", 512, 512, 2048, 700, (1, 3)),
    ("blocks_of_eight", 8, 8, 40, 12, (1, 2, 4)),
    ("blocks_of_two_tiles", 16, 8, 64, 5, (0, 1, 3)),
    ("tiles_of_two_blocks", 8, 16, 48, 7, (0, 1, 2, 5)),
    ("topk_inside_a_block", 128, 128, 512, 200, (1, 2)),
    ("three_strips_a_block", 192, 64, 384, 50, (0, 1)),
    ("chunks_of_two_tiles", 128, 128, 768, 90, (1, 3, 5)),
    ("chunks_of_one_tile", 128, 128, 640, 90, (2, 4)),
])
def test_other_blocks_and_tiles(case, bq, tile, Sp, topk, blocks):
    """The chip's geometry (strips of 128 rows of a block of 512, lane
    groups of 128), the small blocks the operator's tests run, a block
    of two tiles and a tile of two blocks, ``topk`` inside a block (rows
    on both sides of it), a block that is not whole strips, and rows
    whose loop chunk (the bits' unit) is two tiles and one."""
    for block in blocks:
        for kind in ("normal", "five_values"):
            _check(_scores(kind, (bq, Sp), seed=block), block, bq, tile, topk)


def test_at_a_padded_length_the_padding_ties_at_zero():
    """A padded sequence's keys are zeros, so the columns past the real
    length score exactly 0 in every row: ties, which the rule gives to
    the lower columns like any other."""
    S, Sp = 900, 1024
    scores = _scores("normal", (128, Sp), seed=5)
    scores[:, S:] = 0.0
    scores[:, 100:400] = 0.0    # and real zeros below them
    _check(scores, 7, 128, 128, topk=700)


@pytest.mark.parametrize("why,args", [
    ("bfloat16 scores", (jnp.bfloat16, 512, 512, 16384)),
    ("float16 scores", (jnp.float16, 512, 512, 16384)),
    ("blocks of 256", (jnp.float32, 256, 512, 16384)),
    ("tiles of 256", (jnp.float32, 512, 256, 16384)),
    ("tiles of 1024", (jnp.float32, 512, 1024, 16384)),
    ("a length that is not whole tiles", (jnp.float32, 512, 512, 16640)),
    ("a row longer than VMEM holds", (jnp.float32, 512, 512, 1 << 18)),
])
def test_shapes_the_kernel_refuses(why, args):
    assert topk_choice.supported(jnp.float32, 512, 512, 16384)[0]
    assert topk_choice.supported(np.float32, 512, 512, 8192)[0]
    ok, said = topk_choice.supported(*args)
    assert not ok, why
    assert "choice=" in said and "blocks=" in said and "choice_vmem=" in said


def test_the_build_is_counted_and_layers_share_it():
    """One ``_count_launch("topk_choice")`` a geometry: two calls of one
    shape share the trace, another shape is another build."""
    from mxnet_tpu.pallas.attention import PALLAS_LAUNCHES
    built = lambda: PALLAS_LAUNCHES.labels(kernel="topk_choice").value
    topk_choice._run.clear_cache()
    before = built()
    ib = jnp.asarray(_scores("normal", (16, 64), seed=0))
    for _ in range(2):
        topk_choice.choose(ib, jnp.int32(16), jnp.int32(2), 5, 16, 64,
                           interpret=True)
    assert built() == before + 1
    topk_choice.choose(ib, jnp.int32(16), jnp.int32(4), 5, 8, 32,
                       interpret=True)
    assert built() == before + 2
