"""The sparse indexed attention's index scorer as a Pallas kernel pair
(docs/KERNELS.md; the operator is ``ops/sparse_attention.py``, the
heads' cores are ``pallas/sparse_attention.py``).

One call is one block of ``bq`` query rows of one sequence against the
key tiles up to the block's diagonal (the count a scalar-prefetch
operand, a step past it does nothing and moves no block, as in the
cores' kernels).  The scorer's queries ``qi`` (Hi, bq, Di), its one key
a token ``ki`` (S, Di) and the head weights ``wi`` (bq, Hi) are float32
and so is the arithmetic:

    z[h]  = qi[h] . ki^T                 (bq, tile), one a head
    I     = sum_h wi[:, h] * relu(z[h])  (bq, tile)

No array with a head axis AND a key axis leaves VMEM, forward or
backward.

**float32 products out of bfloat16 passes.**  A float32 ``x`` is the
exact sum of three bfloat16 parts (:func:`split3`); the product of two
at ``lax.Precision.HIGHEST`` is the six part products hi.hi, hi.mid,
hi.lo, mid.hi, mid.mid, lo.hi summed in float32.  At ``Di`` 64 each of
those fills half the MXU's depth (or width).  Here the parts are split
in XLA, on the small operands (the keys once a sequence, a block's
queries where the block is taken: its rows serve no other), and laid
side by side (:func:`terms`) so the six are ONE bfloat16 product:

* ``z``: contraction ``6 Di`` = 384 deep, queries' parts in the order
  ``_LEFT``, keys' in the order ``_RIGHT``: three full passes for six
  half-filled;
* the two gradient products of ``dz[h] = 1[z[h] > 0] * wi[:, h] * dI``:
  ``wi`` folds into the small operand on either side and the 0/1 is
  exact in bfloat16, so only ``dI`` (one tile, shared by the heads) is
  split; against its hi part stand three parts of the other operand
  side by side, against its mid part two, against its lo part one.

**Forward** (:func:`forward`): the (bq, S) row ``I``, defined on the
tiles up to the diagonal (what lies past them is never written; the
operator reads it under the causal mask).

**Backward** (:func:`backward`): a tile's ``z`` of all heads are made
again and kept in a VMEM scratch, ``I`` summed, ``dI = on * (exp(I -
lse) - pt)`` taken (``on`` the chosen mask, int8; ``pt`` the cores'
head-mean probabilities; the loss's cotangent is a scalar the operator
multiplies in afterwards), then a head at a time

    g[h]  += (1[z[h] > 0] * dI) . ki          (bq, Di)
    dki^T += (wi[:, h] * qi[h])^T . (1[z[h] > 0] * dI)    (Di, tile)

``g`` is written once a block and gives both ``dqi[h] = wi[:, h] * g[h]``
and ``dwi[:, h] = sum_d qi[h] * g[h]``; ``dki^T`` (Di, S) float32 comes
in and goes out under ``input_output_aliases`` (keys in the lanes: the
masked tile is the product's right operand as it stands, no transpose
anywhere).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _count_launch

_F32, _BF16 = jnp.float32, jnp.bfloat16
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_HI, _MID, _LO = 0, 1, 2
# the six terms of a float32 product, left part against right part
_LEFT = (_HI, _HI, _HI, _MID, _MID, _LO)
_RIGHT = (_HI, _MID, _LO, _HI, _MID, _HI)
_WORKING = 24 << 20         # of VMEM beside the blocks and the scratch
_VMEM = 110 << 20           # what a call may ask of a v5e core's 128 MiB

_dot = functools.partial(lax.dot_general, precision=lax.Precision.DEFAULT,
                         preferred_element_type=_F32)


def split3(x):
    """float32 ``x`` as three float32 arrays that bfloat16 holds exactly
    and that sum to ``x`` (to 2^-24 of it): hi, mid, lo."""
    # not ``astype``: XLA keeps a cast pair's excess precision (PERF.md,
    # PR 23)
    cut = lambda t: lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    hi = cut(x)
    mid = cut(x - hi)
    return hi, mid, cut(x - hi - mid)


def terms(x, order, axis=-1):
    """The parts of float32 ``x`` in ``order`` (``None`` a block of
    zeros) side by side along ``axis``, bfloat16."""
    parts = split3(x)
    return jnp.concatenate(
        [jnp.zeros_like(x) if p is None else parts[p] for p in order],
        axis=axis).astype(_BF16)


def _lanes(tile):
    return min(128, tile)


def _resident_bytes(Hi, bq, tile, Di, backward):
    """VMEM the blocks (two buffers each) and the scratch of a call
    hold."""
    wide = _lanes(tile)
    blocks = Hi * bq * (6 * Di * 2 + wide * 4) + tile * 6 * Di * 2 \
        + bq * tile * 4
    if not backward:
        return 2 * blocks
    # wi * qi's parts and g | lse, the mask | the keys again, dki in and out
    blocks += Hi * bq * (6 * Di * 2 + 2 * Di * 4) + bq * wide * 4 \
        + bq * tile + tile * 6 * Di * 2 + 2 * Di * tile * 4
    return 2 * blocks + (Hi + 4) * bq * tile * 4 + 6 * Di * tile * 4


def supported(qi, bq, tile, S_pad):
    """Whether the compiled kernels take the operator's plan for the
    scorer's queries ``qi`` (B, Hi, S, Di): float32, 64 wide (six parts
    are three MXU passes), query blocks and key tiles of 512 rows, and
    blocks that fit VMEM.  Returns ``(ok, why)``."""
    Hi, Di = qi.shape[1], qi.shape[3]
    need = _resident_bytes(Hi, bq, tile, Di, True) + _WORKING
    ok = (Di == 64 and bq == tile == 512 and S_pad % 512 == 0
          and qi.dtype == jnp.float32 and need <= _VMEM)
    return ok, "idx_dim=%d idx_heads=%d idx_vmem=%dMB" % (Di, Hi, need >> 20)


def _wide(x, tile):
    """(rows, lanes) -> (rows, tile): a row's value over a tile."""
    reps = tile // x.shape[1]
    return x if reps == 1 else jnp.concatenate([x] * reps, axis=1)


def _forward_kernel(Hi, tile):
    def kernel(n_ref, q_ref, w_ref, k_ref, ib_ref):
        @pl.when(pl.program_id(0) < n_ref[0])
        def _():
            ib_ref[...] = jnp.zeros(ib_ref.shape, _F32)

            def head(h, carry):
                z = _dot(q_ref[h], k_ref[...], _NT)
                ib_ref[...] += _wide(w_ref[h], tile) * jnp.maximum(z, 0.0)
                return carry

            lax.fori_loop(0, Hi, head, 0)
    return kernel


def _backward_kernel(Hi, Di, tile):
    def kernel(n_ref, q_ref, wq_ref, w_ref, lse_ref, on_ref, pt_ref, k_ref,
               kg_ref, dk_in, g_ref, dk_ref, z_s, i_s, d_s, dk_acc):
        j, n = pl.program_id(0), n_ref[0]

        @pl.when(j < n)
        def _():
            @pl.when(j == 0)
            def _():
                g_ref[...] = jnp.zeros(g_ref.shape, _F32)

            i_s[...] = jnp.zeros(i_s.shape, _F32)

            def score(h, carry):
                z = _dot(q_ref[h], k_ref[...], _NT)
                z_s[h] = z
                i_s[...] += _wide(w_ref[h], tile) * jnp.maximum(z, 0.0)
                return carry

            lax.fori_loop(0, Hi, score, 0)
            di = jnp.where(
                on_ref[...].astype(_F32) > 0,
                jnp.exp(i_s[...] - _wide(lse_ref[...], tile)) - pt_ref[...],
                0.0)
            # dI's three parts (in a kernel a cast pair rounds)
            d_s[_HI] = di.astype(_BF16).astype(_F32)
            rest = di - d_s[_HI]
            d_s[_MID] = rest.astype(_BF16).astype(_F32)
            d_s[_LO] = rest - d_s[_MID]
            dk_acc[...] = jnp.zeros(dk_acc.shape, _F32)

            def grads(h, carry):
                live = z_s[h] > 0
                hi, mid, lo = (jnp.where(live, d_s[p], 0.0).astype(_BF16)
                               for p in (_HI, _MID, _LO))
                # kg = [hi mid | lo 0 | hi 0] of the keys, wq = [hi mid
                # lo | hi mid | hi] of wi * qi: the six terms
                r = _dot(hi, kg_ref[:, :4 * Di], _NN)
                g_ref[h] += r[:, :2 * Di] + r[:, 2 * Di:] \
                    + _dot(mid, kg_ref[:, :2 * Di], _NN) \
                    + _dot(lo, kg_ref[:, 4 * Di:], _NN)
                wq = wq_ref[h]
                dk_acc[:3 * Di] += _dot(wq[:3 * Di], hi, _NN)
                dk_acc[3 * Di:5 * Di] += _dot(wq[3 * Di:5 * Di], mid, _NN)
                dk_acc[5 * Di:] += _dot(wq[5 * Di:], lo, _NN)
                return carry

            lax.fori_loop(0, Hi, grads, 0)
            dk = dk_in[...]
            for t in range(6):
                dk = dk + dk_acc[t * Di:(t + 1) * Di]
            dk_ref[...] = dk
    return kernel


def _specs(bq, tile, Di):
    """Block specs of a grid (key tile,) with the tile count as the
    scalar-prefetch operand: ``whole(shape)`` a block that never moves;
    ``keys`` a key tile's (tile, 6 Di) rows of parts, ``mask`` a (bq,
    tile) tile of a (bq, S) row, ``sums`` a (Di, tile) tile of the
    (Di, S) key gradient.  A step past the count stays on the last real
    step's tile."""
    at = lambda j, n: jnp.minimum(j, n[0] - 1)
    whole = lambda *shape: pl.BlockSpec(shape, lambda j, n: (0,) * len(shape))
    keys = pl.BlockSpec((tile, 6 * Di), lambda j, n: (at(j, n), 0))
    mask = pl.BlockSpec((bq, tile), lambda j, n: (0, at(j, n)))
    sums = pl.BlockSpec((Di, tile), lambda j, n: (0, at(j, n)))
    return whole, keys, mask, sums


def _params(Hi, bq, tile, Di, backward):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=_resident_bytes(Hi, bq, tile, Di, backward)
        + _WORKING)


# Jitted on their own, as the cores' kernels: a model's layers of one
# geometry share ONE trace and ONE lowering of each.
@functools.partial(jax.jit, static_argnums=(4, 5))
def _run_forward(n, q, w, k, tile, interpret):
    Hi, bq, Di = q.shape[0], q.shape[1], q.shape[2] // 6
    Sp, wide = k.shape[0], _lanes(tile)
    whole, keys, mask, _ = _specs(bq, tile, Di)
    _count_launch("index_scorer")
    return pl.pallas_call(
        _forward_kernel(Hi, tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Sp // tile,),
            in_specs=[whole(Hi, bq, 6 * Di), whole(Hi, bq, wide), keys],
            out_specs=mask),
        out_shape=jax.ShapeDtypeStruct((bq, Sp), _F32),
        compiler_params=_params(Hi, bq, tile, Di, False),
        name="index_scorer_forward", interpret=interpret,
    )(n, q, w, k)


@functools.partial(jax.jit, static_argnums=(10, 11))
def _run_backward(n, q, wq, w, lse, on, pt, k, kg, dk, tile, interpret):
    Hi, bq, Di = q.shape[0], q.shape[1], q.shape[2] // 6
    Sp, wide = k.shape[0], _lanes(tile)
    whole, keys, mask, sums = _specs(bq, tile, Di)
    _count_launch("index_scorer_bwd")
    return pl.pallas_call(
        _backward_kernel(Hi, Di, tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Sp // tile,),
            in_specs=[whole(Hi, bq, 6 * Di), whole(Hi, 6 * Di, bq),
                      whole(Hi, bq, wide), whole(bq, wide), mask, mask,
                      keys, keys, sums],
            out_specs=[whole(Hi, bq, 2 * Di), sums],
            scratch_shapes=[pltpu.VMEM((Hi, bq, tile), _F32),
                            pltpu.VMEM((bq, tile), _F32),
                            pltpu.VMEM((3, bq, tile), _F32),
                            pltpu.VMEM((6 * Di, tile), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((Hi, bq, 2 * Di), _F32),
                   jax.ShapeDtypeStruct(dk.shape, _F32)],
        # the key gradient is added to in place (the count is operand 0)
        input_output_aliases={9: 1},
        compiler_params=_params(Hi, bq, tile, Di, True),
        name="index_scorer_backward", interpret=interpret,
    )(n, q, wq, w, lse, on, pt, k, kg, dk)


def _count(tiles):
    return jnp.reshape(tiles, (1,)).astype(jnp.int32)


def _over_lanes(x, tile):
    """(..., rows) -> (..., rows, lanes): a row's value across a lane
    tile, so the kernels multiply by it as it lies."""
    return jnp.broadcast_to(x[..., None], x.shape + (_lanes(tile),))


def keys(ki):
    """The scorer's keys (S, Di) as the right operand of ``z``: (S,
    6 Di) bfloat16.  Made once a sequence; a query block's operands are
    split where the block is taken (a block's rows serve no other)."""
    return terms(ki, _RIGHT)


def gradient_keys(ki):
    """The keys' parts as the right operand of ``g``: (S, 6 Di)
    bfloat16, [hi mid | lo 0 | hi 0]."""
    return terms(ki, (_HI, _MID, _LO, None, _HI, None))


def forward(qi, wi, kcat, tiles, tile, *, interpret=False):
    """The row ``I`` (bq, S) float32 of one query block: ``qi`` (Hi,
    bq, Di) and ``wi`` (bq, Hi) float32, ``kcat`` (S, 6 Di)
    :func:`keys`, ``tiles`` (a traced int32, at least 1) the key tiles
    of ``tile`` rows up to the block's diagonal; defined on those."""
    with jax.named_scope("pallas.index_scorer"):
        return _run_forward(_count(tiles), terms(qi, _LEFT),
                            _over_lanes(wi.T, tile), kcat, int(tile),
                            bool(interpret))


def backward(qi, wi, lse, on, pt, kcat, kg, dki_t, tiles, tile, *,
             interpret=False):
    """``(g, dki_t)`` of one query block for a loss cotangent of 1:
    ``kg`` (S, 6 Di) :func:`gradient_keys`, ``lse`` (bq,) the chosen
    scores' log-sum-exp, ``on`` (bq, S) int8 the chosen pairs of real
    rows, ``pt`` (bq, S) the heads' mean probabilities, ``dki_t`` (Di,
    S) float32 the keys' gradient so far, transposed, which comes back
    with this block's added (in place).  ``g`` (Hi, bq, Di):
    ``dqi = wi * g`` and ``dwi = sum_d qi * g``."""
    Di = qi.shape[2]
    with jax.named_scope("pallas.index_scorer"):
        # wi * qi, transposed, its parts stacked: [hi mid lo | hi mid | hi]
        wq = terms(jnp.swapaxes(wi.T[:, :, None] * qi, 1, 2),
                   (_HI, _MID, _LO, _HI, _MID, _HI), axis=1)
        g, dki_t = _run_backward(
            _count(tiles), terms(qi, _LEFT), wq, _over_lanes(wi.T, tile),
            _over_lanes(lse, tile), on, pt, kcat, kg, dki_t, int(tile),
            bool(interpret))
    return g[..., :Di] + g[..., Di:], dki_t
