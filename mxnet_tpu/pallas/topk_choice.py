"""The sparse indexed attention's choice of a query block's keys as a
Pallas kernel (docs/KERNELS.md; the operator is
``ops/sparse_attention.py``, whose ``choose`` is this kernel's rule and
its oracle: the chosen set is ``choose``'s to the bit, for every input).

One call is one block of ``bq`` query rows of one sequence: the scorer's
row ``ib`` (bq, S) float32, defined on the key tiles up to the block's
diagonal (their count a scalar-prefetch operand, as in the scorer's and
the cores' kernels; what lies past them is never read).  Per row the
``topk`` largest causal scores are chosen, the ``topk``-th largest found
by bisection over the scores' bit patterns:

1. every causal score becomes its order-preserving integer key ONCE, in
   a VMEM scratch (``_sortable``'s function with the sign bit flipped,
   so the order is int32's: Mosaic compares signed; -0.0 as +0.0; the
   lowest key where the column is past the row);
2. 32 counting passes over the tiles up to the diagonal ONLY, a row's
   count kept as (rows, 128) lane sums that are added across lanes once
   a pass; the rows' thresholds stay in registers between passes;
3. one more such walk counts the keys above the threshold, which leaves
   a row's room for ties; the last walk writes the mask once, as the
   int8 (bq, S) operand the cores' kernel reads, zero past the diagonal,
   and beside it the same mask as the bits the backward pass keeps (the
   operator's ``_pack``: XLA's packing of the int8 read 7.2 ms of the
   Keye step, for 1.3 from the ``pred`` it had before).

**The tie rule** is ``choose``'s and is applied in the last walk, to
every row, with no other branch: ties at the threshold go to the lower
column while the row has room.  A tie's place among its row's ties is a
running count: over the 128 lanes of a tile's lane group it is one
product with a constant 0/1 matrix on the otherwise idle MXU (``[k <= c
| 1]``: the ties up to each lane, and the group's total on every lane,
exact in float32), carried from group to group in registers.  (The cell
``keyevl2_30b_train_ep8`` has a row whose ties do not fit in 84 of its
112 blocks a step: PERF.md section 6, PR 45.)

The grid is strips of ``_STRIP`` rows: a strip's row of scores comes in
while the strip before it is counted, and its keys (``_STRIP`` x S
int32) stay in VMEM for all 35 walks.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _count_launch

_I32, _F32 = jnp.int32, jnp.float32
_NN = (((1,), (0,)), ((), ()))      # a @ b
_LOWEST = -(1 << 31)        # a column past the row: under every score
_STRIP = 128                # rows a grid step takes
_LANES = 128
_UNROLL = 4                 # tiles a counting loop's iteration takes
_WORKING = 16 << 20         # of VMEM beside the blocks and the scratch
_VMEM = 110 << 20           # what a call may ask of a v5e core's 128 MiB


def _resident_bytes(S_pad):
    """VMEM a call's blocks (two buffers each) and its scratch hold."""
    # the scores, the mask and its bits | the keys
    return 2 * _STRIP * (S_pad * 5 + S_pad // 8) + _STRIP * S_pad * 4


def supported(dtype, bq, tile, S_pad):
    """Whether the compiled kernel takes the operator's plan for a row
    of ``dtype`` scores: float32 (the keys are its 32 bits), query
    blocks and key tiles of 512 rows that divide the padded length, and
    a strip's row that fits VMEM.  Returns ``(ok, why)``."""
    need = _resident_bytes(S_pad) + _WORKING
    ok = (jnp.dtype(dtype) == jnp.float32 and bq == tile == 512
          and S_pad % 512 == 0 and need <= _VMEM)
    return ok, "choice=%s blocks=%dx%d choice_vmem=%dMB" % (
        jnp.dtype(dtype).name, bq, tile, need >> 20)


def _keys(x):
    """float32 -> int32, order-preserving (-0.0 as +0.0): the
    operator's ``_sortable`` with the sign bit flipped."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), _I32)
    return bits ^ ((bits >> 31) & _I32(0x7FFFFFFF))


def _bytes(x):
    """int32 0 .. 255 -> the int8 of the same eight bits."""
    return (x - ((x & 128) << 1)).astype(jnp.int8)


def _kernel(topk, tile, kc, rows):
    wide = min(_LANES, tile)        # lanes a row's count is kept in
    groups = tile // wide
    per = kc // 8                   # bytes a loop chunk's columns pack to
    tiles, slices = kc // tile, tile // per     # a chunk, of a tile

    def kernel(n_ref, ib_ref, on_ref, bits_ref, key_s):
        n, first = n_ref[0], n_ref[1] + pl.program_id(0) * rows
        # tiles that lie under the strip's first row: causal as they are
        whole = jnp.minimum((first + 1) // tile, n)
        at = lambda j: pl.ds(pl.multiple_of(j * tile, tile), tile)
        row = first + lax.broadcasted_iota(_I32, (rows, tile), 0)
        lane = lax.broadcasted_iota(_I32, (rows, tile), 1)

        def causal(j):
            return j * tile + lane <= row

        def make(masked):
            def step(j, carry):
                key = _keys(ib_ref[:, at(j)])
                if masked:
                    key = jnp.where(causal(j), key, _LOWEST)
                key_s[:, at(j)] = key
                return carry
            return step

        lax.fori_loop(0, whole, make(False), 0)
        lax.fori_loop(whole, n, make(True), 0)

        def lanes(x):
            """(rows, 1) -> (rows, 128): a row's value over the lanes."""
            return jnp.broadcast_to(x, (rows, wide))

        def count(hit):
            """Per row, how many keys of the walked tiles ``hit`` takes:
            ``hit(key (rows, 128))`` bool."""
            def step(j, acc):
                key = key_s[:, at(j)]
                for g in range(groups):
                    acc = acc + hit(
                        key[:, g * wide:(g + 1) * wide]).astype(_I32)
                return acc
            def steps(i, acc):
                for u in range(_UNROLL):
                    acc = step(i * _UNROLL + u, acc)
                return acc
            acc = lax.fori_loop(0, n // _UNROLL, steps,
                                jnp.zeros((rows, wide), _I32))
            acc = lax.fori_loop(n // _UNROLL * _UNROLL, n, step, acc)
            return jnp.sum(acc, axis=1, keepdims=True)

        def bit(i, tau):
            # int32 order: the threshold's sign bit is stored flipped,
            # so setting a bit of the unsigned threshold is an XOR
            cand = tau ^ (_I32(1) << (31 - i))
            over = lanes(cand)
            return jnp.where(count(lambda key: key >= over) >= topk,
                             cand, tau)

        tau = lax.fori_loop(0, 32, bit, jnp.full((rows, 1), _LOWEST, _I32))
        over = lanes(tau)
        room = lanes(topk - count(lambda key: key > over)).astype(_F32)
        # a tie's place among its row's ties: [k <= c | 1] (lanes, 2 lanes)
        k = lax.broadcasted_iota(_I32, (wide, 2 * wide), 0)
        c = lax.broadcasted_iota(_I32, (wide, 2 * wide), 1)
        upto = ((k <= c) | (c >= wide)).astype(_F32).astype(jnp.bfloat16)

        def emit(masked):
            def step(j, carry):
                before, packed = carry
                key, on = key_s[:, at(j)], []
                for g in range(groups):
                    part = key[:, g * wide:(g + 1) * wide]
                    tie = part == over
                    place = lax.dot_general(
                        tie.astype(_F32).astype(jnp.bfloat16), upto, _NN,
                        precision=lax.Precision.DEFAULT,
                        preferred_element_type=_F32)
                    on.append(((part > over) | (
                        tie & (before + place[:, :wide] <= room)))
                        .astype(_I32))
                    before = before + place[:, wide:]
                on = jnp.concatenate(on, axis=1) if groups > 1 else on[0]
                if masked:
                    on = jnp.where(causal(j), on, 0)
                on_ref[:, at(j)] = on.astype(jnp.int8)
                # the operator's ``_pack``: bit b of a chunk's byte i is
                # the chunk's column b * per + i; this tile holds the
                # columns of ``slices`` bits of it
                t = j % tiles
                mine = on[:, :per] << (t * slices)
                for b in range(1, slices):
                    mine = mine | (on[:, b * per:(b + 1) * per]
                                   << (t * slices + b))
                packed = jnp.where(t == 0, mine, packed | mine)
                bits_ref[:, pl.ds(pl.multiple_of(j // tiles * per, per),
                                  per)] = _bytes(packed)
                return before, packed
            return step

        carry = lax.fori_loop(
            0, whole, emit(False),
            (jnp.zeros((rows, wide), _F32), jnp.zeros((rows, per), _I32)))
        lax.fori_loop(whole, n, emit(True), carry)

        def blank(j, carry):
            on_ref[:, at(j)] = jnp.zeros((rows, tile), jnp.int8)
            return carry

        lax.fori_loop(n, on_ref.shape[1] // tile, blank, 0)

        def blank_bits(c, carry):
            bits_ref[:, pl.ds(pl.multiple_of(c * per, per), per)] = \
                jnp.zeros((rows, per), jnp.int8)
            return carry

        lax.fori_loop((n + tiles - 1) // tiles, bits_ref.shape[1] // per,
                      blank_bits, 0)
    return kernel


# Jitted on its own, as the operator's other kernels: a model's layers
# of one geometry share ONE trace and ONE lowering.
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _run(n, ib, topk, tile, kc, interpret):
    bq, Sp = ib.shape
    # the tallest strip that divides the block (blocks are whole 8s)
    rows = next(r for r in range(min(_STRIP, bq), 0, -8) if bq % r == 0)
    strip = lambda width: pl.BlockSpec((rows, width), lambda s, n: (s, 0))
    _count_launch("topk_choice")
    return pl.pallas_call(
        _kernel(topk, tile, kc, rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bq // rows,),
            in_specs=[strip(Sp)],
            out_specs=[strip(Sp), strip(Sp // 8)],
            scratch_shapes=[pltpu.VMEM((rows, Sp), _I32)]),
        out_shape=[jax.ShapeDtypeStruct((bq, Sp), jnp.int8),
                   jax.ShapeDtypeStruct((bq, Sp // 8), jnp.int8)],
        compiler_params=pltpu.CompilerParams(
            # a strip's keys and counts serve no other strip
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_resident_bytes(Sp) + _WORKING),
        name="topk_choice", interpret=interpret,
    )(n, ib)


def choose(ib, r0, tiles, topk, tile, kc, *, interpret=False):
    """``(on, bits)`` of one query block whose first row is ``r0``:
    ``ib`` (bq, S) float32 the scorer's row, defined on the first
    ``tiles`` (a traced int32, at least 1) key tiles of ``tile``
    columns, which reach the block's diagonal.  ``on`` (bq, S) int8 the
    chosen mask: per row the ``topk`` largest causal scores, a tie to
    the lower column; every causal column of a row that has no more
    than ``topk``; zero past the diagonal: the operator's ``choose`` to
    the bit.  ``bits`` (bq, S / 8) uint8: the same mask as the
    operator's ``_pack`` lays it out, a loop chunk of ``kc`` columns
    (whole tiles, at most eight) into ``kc / 8`` bytes."""
    with jax.named_scope("pallas.topk_choice"):
        n = jnp.stack([jnp.asarray(tiles, _I32), jnp.asarray(r0, _I32)])
        on, bits = _run(n, ib, int(topk), int(tile), int(kc),
                        bool(interpret))
        return on, lax.bitcast_convert_type(bits, jnp.uint8)
