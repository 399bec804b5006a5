"""The dropless expert layer's token-ordered sum as a Pallas kernel
(docs/KERNELS.md; the caller is ``parallel/moe.py``
``dropless_topk_experts``, scopes ``moe.combine`` and ``moe.dispatch``).

    out[n] = sum over rows r with token[r] == n of c[r] * v[r]

for rows ``v`` (rows, d) that arrive SORTED by ``token``: then a tile of
256 tokens owns a contiguous run of rows, and its (256, d) block of the
result is ``onehot^T . v`` over that run, a product for the MXU, where
XLA's ``scatter-add`` of the same rows takes one at a time.  The one-hot
never exists in HBM: a grid step builds its (256, tm) block from the
row's token numbers (an iota compare against the tile's first token), so
a row of another tile, or one keyed past the last token (a row of no
expert held here), meets no column and adds nothing; a tile with no row
is visited once and written as zeros.  The walk over (token tile, row
block) pairs (:func:`_walk`) is three scalar-prefetch arrays, as in the
grouped products' kernels.

**float32 sums out of bfloat16 passes.**  bfloat16 rows stand in the
product as they are; a float32 ``c`` is split in XLA into the three
bfloat16 parts that sum to it (``pallas/index_scorer.py`` ``split3``)
and each part's product with a bfloat16 row is exact in float32, so the
three passes accumulate ``c * v`` in float32 with no rounding of the
weight or of the weighted row.  Without ``c`` the 0 / 1 is exact in one
pass.  float32 rows take one product at ``HIGHEST``.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _count_launch, _note_kernel_build
from .index_scorer import split3

_F32, _BF16 = jnp.float32, jnp.bfloat16
TOKENS = 256        # tokens a tile of the result
_ROWS = 128         # rows a grid step takes: the fastest of 128 / 256 / 512
                    # at the four cells' shapes (PERF.md, PR 42)
_WORKING = 8 << 20  # of VMEM beside the blocks and the accumulator
_VMEM = 100 << 20   # what a call may ask of a v5e core's 128 MiB


def _row_tile(rows):
    """Rows of a grid step's block: the largest power of two within
    ``_ROWS`` that divides ``rows``."""
    return min(_ROWS, rows & -rows)


def _resident_bytes(tm, width, itemsize, out_itemsize):
    """VMEM a call's blocks (two buffers each) and accumulator hold."""
    return 2 * (tm * width * itemsize + TOKENS * width * out_itemsize) \
        + TOKENS * width * 4


def supported(rows, tokens, width, dtype):
    """Whether the compiled kernel takes ``rows`` sorted rows of
    ``width`` for ``tokens`` tokens: whole tiles of 256 tokens, row
    blocks of whole lane tiles (the token numbers lie in the lanes),
    rows of whole lane tiles that fit VMEM, bf16 or float32.  Returns
    ``(ok, why)``."""
    tm = _row_tile(rows)
    ok = (tokens % TOKENS == 0 and tm % 128 == 0 and width % 128 == 0
          and dtype in (jnp.bfloat16, jnp.float32)
          and _resident_bytes(tm, width, 4, 4) + _WORKING <= _VMEM)
    return ok, "tokens=%d rows=%d width=%d dtype=%s" % (
        tokens, rows, width, jnp.dtype(dtype).name)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _walk(token, tiles, tm):
    """The grid's steps over ascending ``token`` (rows,): a tile of 256
    tokens takes the row blocks of ``tm`` that hold its rows, one after
    the other, and a tile with no row takes one step that adds nothing
    (it is written as zeros).  Returns the rows a tile owns (tiles,),
    each step's tile and row block (both ``rows // tm + tiles`` long, a
    bound: a block is walked once more for each tile that begins inside
    it) and the number of steps.  Jitted on its own: the calls of a
    layer, and a model's layers, share one trace."""
    _note_kernel_build()
    blocks = token.shape[0] // tm
    ends = jnp.sum(
        (token // TOKENS)[:, None] <= jnp.arange(tiles, dtype=jnp.int32),
        axis=0, dtype=jnp.int32)
    begins = ends - jnp.diff(ends, prepend=0)
    sizes = ends - begins
    first = jnp.minimum(begins // tm, blocks - 1)
    visits = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 1)
    start = jnp.cumsum(visits) - visits
    tile = jnp.repeat(jnp.arange(tiles, dtype=jnp.int32), visits,
                      total_repeat_length=blocks + tiles)
    step = jnp.arange(blocks + tiles, dtype=jnp.int32)
    block = jnp.minimum(first[tile] + step - start[tile], blocks - 1)
    return sizes, tile, block, jnp.sum(visits)


def _kernel(parts, tm, precision):
    def kernel(sizes, tiles, blocks, *refs):
        del blocks
        tok_ref, refs = refs[0], refs[1:]
        c_ref = refs[0] if parts else None
        v_ref, out_ref, acc_ref = refs[-3:]
        i = pl.program_id(0)
        tile = tiles[i]

        @pl.when((i == 0) | (tiles[jnp.maximum(i - 1, 0)] != tile))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

        @pl.when(sizes[tile] > 0)
        def _():
            v = v_ref[...]
            hit = lax.broadcasted_iota(jnp.int32, (TOKENS, tm), 0) \
                == jnp.broadcast_to(tok_ref[...] - tile * TOKENS,
                                    (TOKENS, tm))
            if not parts:
                lhs = [hit.astype(v.dtype)]
            else:
                lhs = [jnp.where(hit, jnp.broadcast_to(c_ref[p:p + 1, :],
                                                       (TOKENS, tm)),
                                 0.0).astype(v.dtype) for p in range(parts)]
            for onehot in lhs:
                acc_ref[...] += lax.dot(onehot, v, precision=precision,
                                        preferred_element_type=_F32)

        last = i == pl.num_programs(0) - 1
        following = tiles[jnp.minimum(i + 1, pl.num_programs(0) - 1)]

        @pl.when(last | (following != tile))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("tokens", "out_dtype",
                                             "interpret"))
def token_sum(v, token, c=None, *, tokens, out_dtype=None, interpret=False):
    """``out (tokens, d)``, ``out[n] = sum_r [token[r] == n] c[r] *
    v[r]`` accumulated in float32 and rounded once to ``out_dtype``
    (``v``'s).  ``v`` (rows, d) bf16 or float32; ``token`` (rows,) int32,
    ASCENDING, a row keyed ``tokens`` or more adds nothing; ``c`` (rows,)
    float32, or None for 1.  Scope ``pallas.token_sum``."""
    rows, d = v.shape
    tm = _row_tile(rows)
    out_dtype = jnp.dtype(out_dtype or v.dtype)
    _count_launch("token_sum")
    sizes, tiles, blocks, steps = _walk(token, tokens // TOKENS, tm)
    operands = [token[None, :]]
    parts = 0
    if c is not None:
        # float32 rows take the weight whole, at HIGHEST
        operands.append(jnp.stack(split3(c.astype(_F32)))
                        if v.dtype == _BF16 else c.astype(_F32)[None, :])
        parts = operands[-1].shape[0]
    lanes = lambda i, sizes, tiles, blocks: (0, blocks[i])
    with jax.named_scope("pallas.token_sum"):
        return pl.pallas_call(
            _kernel(parts, tm, lax.Precision.DEFAULT if v.dtype == _BF16
                    else lax.Precision.HIGHEST),
            out_shape=jax.ShapeDtypeStruct((tokens, d), out_dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                in_specs=[pl.BlockSpec((1, tm), lanes)]
                + [pl.BlockSpec((parts, tm), lanes)] * bool(parts)
                + [pl.BlockSpec(
                    (tm, d), lambda i, sizes, tiles, blocks: (blocks[i], 0))],
                out_specs=pl.BlockSpec(
                    (TOKENS, d),
                    lambda i, sizes, tiles, blocks: (tiles[i], 0)),
                grid=(steps,),
                scratch_shapes=[pltpu.VMEM((TOKENS, d), _F32)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_resident_bytes(
                    tm, d, v.dtype.itemsize, out_dtype.itemsize) + _WORKING),
            interpret=interpret, name="token_sum",
        )(sizes, tiles, blocks, *operands, v)
