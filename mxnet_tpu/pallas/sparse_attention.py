"""The sparse indexed attention's cores as a Pallas kernel pair
(docs/KERNELS.md; the operator is ``ops/sparse_attention.py``).

One call is one block of ``bq`` query rows of one sequence against the
key tiles up to the block's diagonal, under a mask that is an OPERAND:
``on`` (bq, S) int8, the scorer's choice, the same for all heads (it
lies inside the causal triangle, so the kernels apply no mask of their
own).  Grouped heads: ``q`` (Hk, R, bq, D) on ``k``, ``v`` (Hk, S, D).

Every (tile x bq) score tile lives in VMEM only: float32 scores,
statistics and accumulators, ``p`` and ``ds`` rounded to the operands'
dtype for the products, as the operator's XLA loops do.  Keys stand in
the sublanes and queries in the lanes (a row's statistics are (1, bq)
rows that the sublanes share), so the mask tile is turned once a key
tile into a float32 fill (0 where chosen, -1e30 elsewhere: ``s + fill``
is the XLA path's ``where(on, s, -1e30)`` to the bit) and the
head-summed probabilities are turned back once a key tile.

**The trip count is data.**  The grid is the static ``S / tile`` key
tiles by the ``Hk`` groups; the number of tiles up to the diagonal comes
as a scalar-prefetch operand, a step past it does nothing and its block
indices stay on the last real step's (nothing is fetched, and an output
block is written back once, with what the last real step left in it).

**Forward**: two walks over the key tiles in one grid (the leading grid
dimension).  The first keeps the running maximum and sum of every head;
the second takes ``p = exp(s - lse)``, adds ``p v`` into a float32
accumulator of all heads (written once) and the heads' ``p`` into a
float32 tile that is written, as their mean, when the last group has
passed.  Returns ``o``, ``lse`` and ``pt`` (bq, S).

**Backward**: one walk.  ``dq`` of all heads is a float32 accumulator
written once; ``dk`` and ``dv`` (Hk, S, D) float32 come in and go out
under ``input_output_aliases``: a step adds its tile's sums to the rows
it reads, tiles past the diagonal are never touched.  It emits the same
``pt`` row.

``pt`` is defined on the tiles up to the diagonal; what lies past them
is never written (the mask is 0 there, and the operator reads ``pt``
under the mask).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _count_launch

_NEG = -1e30                # ops/sparse_attention.py's fill
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_WORKING = 40 << 20         # of VMEM beside the resident blocks: a
#                             step's score tiles and their transposes
_VMEM = 110 << 20           # what a call may ask of a v5e core's 128 MiB


def _resident_bytes(Hq, bq, tile, D, size, backward):
    """VMEM the blocks and scratch of one call hold (blocks in two
    buffers each)."""
    heads = Hq * bq * D
    tiles = 2 * tile * bq * 4 + 2 * bq * tile * 4 + 2 * bq * tile
    if backward:        # q, do, dq | dq's sum | k, v, dk, dv in and out
        return heads * (6 * size + 4) + tiles \
            + tile * D * (4 * size + 16) + 4 * Hq * 8 * bq * 4
    # q, o | o's sum | k, v | m, l, lse
    return heads * (4 * size + 4) + tiles + tile * D * 4 * size \
        + 6 * Hq * 8 * bq * 4


def supported(q, k, bq, tile, S_pad):
    """Whether the compiled kernels take the operator's plan for these
    ``q`` (B, Hq, S, D) and ``k`` (B, Hk, S, D): head width whole lane
    tiles, query blocks and key tiles of 512 rows that divide the padded
    length, bfloat16 or float32, and blocks that fit VMEM.  Returns
    ``(ok, why)``."""
    Hq, D = q.shape[1], q.shape[3]
    size = jnp.dtype(q.dtype).itemsize
    need = _resident_bytes(Hq, bq, tile, D, size, True) + _WORKING
    ok = (D % 128 == 0 and bq == tile == 512 and S_pad % 512 == 0
          and q.dtype in (jnp.bfloat16, jnp.float32) and k.dtype == q.dtype
          and need <= _VMEM)
    return ok, "head_dim=%d blocks=%dx%d S_pad=%d dtype=%s vmem=%dMB" % (
        D, bq, tile, S_pad, q.dtype, need >> 20)


def _precision(dtype):
    return lax.Precision.HIGHEST if dtype == jnp.float32 \
        else lax.Precision.DEFAULT


def _fill(on_ref):
    """The mask tile (bq, tile) int8 as the float32 fill (tile, bq)."""
    return jnp.where(on_ref[...].astype(_F32).T > 0, 0.0, _NEG)


def _forward_kernel(R, scale, low):
    dot = functools.partial(lax.dot_general, precision=_precision(low),
                            preferred_element_type=_F32)

    def kernel(n_ref, q_ref, k_ref, v_ref, on_ref, o_ref, lse_ref, pt_ref,
               m_s, l_s, o_acc, fill_s, pt_acc):
        walk, j, g = (pl.program_id(i) for i in range(3))
        n, groups = n_ref[0], pl.num_programs(2)
        heads = pl.ds(g * R, R)

        def scores(h):
            return dot(k_ref[...], q_ref[h], _NT) * scale + fill_s[...]

        @pl.when((j < n) & (g == 0))
        def _():
            fill_s[...] = _fill(on_ref)

        @pl.when((j < n) & (walk == 0))
        def _():
            @pl.when(j == 0)
            def _():
                m_s[heads] = jnp.full((R,) + m_s.shape[1:], _NEG, _F32)
                l_s[heads] = jnp.zeros((R,) + l_s.shape[1:], _F32)

            def head(r, carry):
                h = g * R + r
                s, m = scores(h), m_s[h]
                m2 = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                l_s[h] = l_s[h] * jnp.exp(m - m2) \
                    + jnp.sum(jnp.exp(s - m2), axis=0, keepdims=True)
                m_s[h] = m2
                return carry

            lax.fori_loop(0, R, head, 0)

        @pl.when((j < n) & (walk == 1))
        def _():
            @pl.when(j == 0)
            def _():        # the statistics become the log-sum-exp
                m_s[heads] = m_s[heads] + jnp.log(l_s[heads])
                lse_ref[heads] = m_s[heads]
                o_acc[heads] = jnp.zeros((R,) + o_acc.shape[1:], _F32)

            @pl.when(g == 0)
            def _():
                pt_acc[...] = jnp.zeros(pt_acc.shape, _F32)

            def head(r, carry):
                h = g * R + r
                p = jnp.exp(scores(h) - m_s[h])
                pt_acc[...] += p
                o_acc[h] += dot(p.astype(low).T, v_ref[...], _NN)
                return carry

            lax.fori_loop(0, R, head, 0)

            @pl.when(g == groups - 1)
            def _():
                pt_ref[...] = pt_acc[...].T * (1.0 / (groups * R))

            @pl.when(j == n - 1)
            def _():
                o_ref[heads] = o_acc[heads].astype(o_ref.dtype)
    return kernel


def _backward_kernel(R, scale, low):
    dot = functools.partial(lax.dot_general, precision=_precision(low),
                            preferred_element_type=_F32)

    def kernel(n_ref, q_ref, do_ref, lse_ref, di_ref, on_ref, k_ref, v_ref,
               dk_in, dv_in, dq_ref, dk_ref, dv_ref, pt_ref,
               dq_acc, fill_s, pt_acc):
        j, g = pl.program_id(0), pl.program_id(1)
        n, groups = n_ref[0], pl.num_programs(1)
        heads = pl.ds(g * R, R)

        @pl.when(j < n)
        def _():
            @pl.when(j == 0)
            def _():
                dq_acc[heads] = jnp.zeros((R,) + dq_acc.shape[1:], _F32)

            @pl.when(g == 0)
            def _():
                fill_s[...] = _fill(on_ref)
                pt_acc[...] = jnp.zeros(pt_acc.shape, _F32)

            dk_ref[...] = dk_in[...]
            dv_ref[...] = dv_in[...]

            def head(r, carry):
                h = g * R + r
                q, do, k, v = q_ref[h], do_ref[h], k_ref[...], v_ref[...]
                s = dot(k, q, _NT) * scale + fill_s[...]
                p = jnp.exp(s - lse_ref[h])
                pt_acc[...] += p
                dv_ref[...] += dot(p.astype(low), do, _NN)
                dp = dot(v, do, _NT)
                ds = (p * (dp - di_ref[h]) * scale).astype(low)
                dk_ref[...] += dot(ds, q, _NN)
                dq_acc[h] += dot(ds.T, k, _NN)
                return carry

            lax.fori_loop(0, R, head, 0)

            @pl.when(g == groups - 1)
            def _():
                pt_ref[...] = pt_acc[...].T * (1.0 / (groups * R))

            @pl.when(j == n - 1)
            def _():
                dq_ref[heads] = dq_acc[heads].astype(dq_ref.dtype)
    return kernel


def _specs(Hk, Hq, bq, tile, D):
    """Block specs of a grid (..., key tile, group) with the tile count
    as the scalar-prefetch operand: ``whole`` a block of all heads that
    never moves, ``rows`` (heads, 1, bq) statistics, ``tiled`` a key
    tile's (tile, D) rows of a group, ``mask`` a (bq, tile) tile of a
    (bq, S) row.  A step past the count stays on the last real step's
    tile and group; ``second`` (the forward's v and pt) keeps a block on
    its first tile during the first of two walks, which does not use
    it."""
    def at(ids, n, second):
        j, g = ids[-2:]
        j_at = jnp.minimum(j, n[0] - 1)
        g_at = jnp.where(j < n[0], g, Hk - 1)
        return (g_at * ids[0], j_at * ids[0]) if second else (g_at, j_at)

    whole = pl.BlockSpec((Hq, bq, D), lambda *a: (0, 0, 0))
    rows = pl.BlockSpec((Hq, 1, bq), lambda *a: (0, 0, 0))
    tiled = lambda second=False: pl.BlockSpec(
        (None, tile, D), lambda *a: at(a[:-1], a[-1], second) + (0,))
    mask = lambda second=False: pl.BlockSpec(
        (bq, tile), lambda *a: (0, at(a[:-1], a[-1], second)[1]))
    return whole, rows, tiled, mask


def _params(Hq, bq, tile, D, size, backward, dims):
    return pltpu.CompilerParams(
        # scratch is carried over every step: nothing is independent
        dimension_semantics=("arbitrary",) * dims,
        vmem_limit_bytes=_resident_bytes(Hq, bq, tile, D, size, backward)
        + _WORKING)


# Jitted on their own, as the flash backward's pass: a model's layers
# of one geometry share ONE trace and ONE lowering of each kernel.
@functools.partial(jax.jit, static_argnums=(5, 6))
def _run_forward(n, q, k, v, on, tile, interpret):
    Hk, R, bq, D = q.shape
    Hq, Sp = Hk * R, k.shape[1]
    whole, rows, tiled, mask = _specs(Hk, Hq, bq, tile, D)
    _count_launch("sparse_attention")
    o, lse, pt = pl.pallas_call(
        _forward_kernel(R, D ** -0.5, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(2, Sp // tile, Hk),
            in_specs=[whole, tiled(), tiled(True), mask()],
            out_specs=[whole, rows, mask(True)],
            scratch_shapes=[pltpu.VMEM((Hq, 1, bq), _F32),
                            pltpu.VMEM((Hq, 1, bq), _F32),
                            pltpu.VMEM((Hq, bq, D), _F32),
                            pltpu.VMEM((tile, bq), _F32),
                            pltpu.VMEM((tile, bq), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((Hq, bq, D), q.dtype),
                   jax.ShapeDtypeStruct((Hq, 1, bq), _F32),
                   jax.ShapeDtypeStruct((bq, Sp), _F32)],
        compiler_params=_params(Hq, bq, tile, D, q.dtype.itemsize, False, 3),
        name="sparse_attention_forward", interpret=interpret,
    )(n, q.reshape(Hq, bq, D), k, v, on)
    return o.reshape(q.shape), lse.reshape(Hk, R, bq), pt


@functools.partial(jax.jit, static_argnums=(10, 11))
def _run_backward(n, q, do, lse, di, on, k, v, dk, dv, tile, interpret):
    Hk, R, bq, D = q.shape
    Hq, Sp = Hk * R, k.shape[1]
    whole, rows, tiled, mask = _specs(Hk, Hq, bq, tile, D)
    _count_launch("sparse_attention_bwd")
    heads = lambda t: t.reshape(Hq, bq, D)
    stat = lambda t: t.reshape(Hq, 1, bq)
    dq, dk, dv, pt = pl.pallas_call(
        _backward_kernel(R, D ** -0.5, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Sp // tile, Hk),
            in_specs=[whole, whole, rows, rows, mask()] + [tiled()] * 4,
            out_specs=[whole, tiled(), tiled(), mask()],
            scratch_shapes=[pltpu.VMEM((Hq, bq, D), _F32),
                            pltpu.VMEM((tile, bq), _F32),
                            pltpu.VMEM((tile, bq), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((Hq, bq, D), q.dtype),
                   jax.ShapeDtypeStruct(dk.shape, _F32),
                   jax.ShapeDtypeStruct(dv.shape, _F32),
                   jax.ShapeDtypeStruct((bq, Sp), _F32)],
        # dk and dv are added to in place (the count is operand 0)
        input_output_aliases={8: 1, 9: 2},
        compiler_params=_params(Hq, bq, tile, D, q.dtype.itemsize, True, 2),
        name="sparse_attention_backward", interpret=interpret,
    )(n, heads(q), heads(do), stat(lse), stat(di), on, k, v, dk, dv)
    return dq.reshape(q.shape), dk, dv, pt


def _count(tiles):
    return jnp.reshape(tiles, (1,)).astype(jnp.int32)


def forward(q, k, v, on, tiles, tile, *, interpret=False):
    """``(o, lse, pt)`` of one query block: ``q`` (Hk, R, bq, D), ``k``,
    ``v`` (Hk, S, D), ``on`` (bq, S) int8 the chosen mask, ``tiles`` (a
    traced int32, at least 1) the key tiles of ``tile`` rows up to the
    block's diagonal.  ``o`` in q's dtype, ``lse`` (Hk, R, bq) and
    ``pt`` (bq, S) float32, the mean over all heads of the
    probabilities, defined on the first ``tiles`` tiles."""
    with jax.named_scope("pallas.sparse_attention"):
        return _run_forward(_count(tiles), q, k, v, on, int(tile),
                            bool(interpret))


def backward(q, do, lse, di, on, k, v, dk, dv, tiles, tile, *,
             interpret=False):
    """``(dq, dk, dv, pt)`` of one query block: ``do`` as ``q``, ``lse``
    and ``di`` (the rows' ``sum(do * o)``) (Hk, R, bq) float32, ``dk``,
    ``dv`` (Hk, S, D) float32 the sums so far, which come back with this
    block's added (in place).  ``dq`` in q's dtype."""
    with jax.named_scope("pallas.sparse_attention"):
        return _run_backward(_count(tiles), q, do, lse, di, on, k, v,
                             dk, dv, int(tile), bool(interpret))
