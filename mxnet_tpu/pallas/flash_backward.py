"""The training flash attention's backward as one Pallas kernel
(docs/KERNELS.md).

Masked softmax attention of head-major q (B, Hq, S, D) over k (B, Hk, S,
D) and v (B, Hk, S, Dv), Hq a multiple of Hk, q carrying the softmax
scale: from the forward's result ``o``, its row log-sum-exp and the
cotangent ``do`` the kernel emits dq, dk and dv.  Every live 512 x 512
score block is computed once, float32 scores, statistics and
accumulators whatever the operands' dtype; p and ds are rounded to the
operands' dtype for the products.  Three static mask families, one
kernel body: which key blocks a query block visits, and under which
rule for their cells, is a small table a query block (:func:`walk`), and
a family is a filling of it.

**Causal** (``window=None, blocks=None``).  Query block ``i`` walks key
blocks ``0 .. i - 1`` unmasked and its own under ``key <= query``: every
block on or under the diagonal (136 a head at 8192 rows, 528 at 16 384,
none above it).

**The band.**  With ``window`` (whole blocks: a query attends the
``window`` keys that end with its own, ``query - key < window``) a query
block ``i`` walks key blocks ``i - window / 512 .. i`` and nothing left
of them: the leftmost under the band's rule (its keys right of the
block's own diagonal), the ones between unmasked, its own under the
causal rule; at 16 384 rows and a window of 4096 that is 252 of the 528
causal blocks a head (:func:`blocks_walked`).

**Block diffusion.**  With ``blocks`` (the block length ``Bk``, which
divides 512) the S rows are a clean sequence of ``L = S / 2`` rows and
its noised copy side by side, ``pos(r) = r mod L``, ``blk(r) = pos(r) //
Bk``: a clean row attends the clean rows of blocks up to and including
its own, a noised row the clean rows of the blocks strictly before its
own and the NOISED rows of its own block (both directions: keys right of
the query are attended), and nothing else.  Of the ``T = L / 512`` score
blocks a half, a clean query block ``i`` walks clean key blocks ``0 ..
i - 1`` unmasked and its own under ``blk(key) <= blk(query)``; a noised
one (``c = i - T``) clean key blocks ``0 .. c - 1`` unmasked, clean
block ``c`` under ``blk(key) < blk(query)`` and noised block ``i`` under
``blk(key) == blk(query)``; the clean-noised quadrant is never visited.
At 16 384 rows (L 8192, T 16) that is 136 + 136 + 16 = 288 of the 1024
blocks a head, where the causal walk of the same rows would be 528.

**No partial sums.**  A key/value head's rows stay in VMEM while its
query heads' blocks pass by: k and v as they are, dk and dv as float32
scratch of the whole (S, D) and (S, Dv), zeroed when the head starts and
written once, rounded once, when its last query head ends (a group's
Hq / Hk query heads add into the same scratch: K and V come at their own
head count).  A grid step is one query head's block of 512 rows: it
walks the key/value blocks before its own in a ``fori_loop`` (no mask),
then its own under the diagonal's mask, adds each block's dk and dv into
the scratch's rows and its dq into a float32 (512, D) accumulator that
is written once when the step ends.  So HBM sees q, do, o's row sums
and the log-sum-exp read once a query head, k and v once a key/value
head, and dq, dk, dv written once.

**Transposed sums.**  Where D is not whole lane tiles (latent
attention's 192) dq and dk are summed as (D, rows) from a k turned once
a head and a q turned once a step, and turned back when they are
written: the MXU pads a result's WIDTH to its 128 columns, not its
height (``plan``).

**Segments.**  What a key/value head holds in VMEM is ``S`` rows of k,
v, dk, dv (``plan``); where that passes the budget the key/value rows
are cut into the fewest equal segments that fit, each a pass of the same
kernel over all query blocks (those before the segment walk nothing).  A
later pass starts its dq accumulator from the float32 dq of the one
before, so dq is still one float32 sum rounded once.
"""
import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _count_launch

_BLOCK = 512                # rows and columns of a score block
_BUDGET = 64 << 20          # of VMEM for a key/value head's resident rows
_WORKING = 24 << 20         # beside them: a step's blocks and score tiles
# splash attention's fill: exp(fill - lse) is 0 and never NaN
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T

Plan = collections.namedtuple("Plan",
                              "segments rows transposed vmem_limit_bytes")


def plan(seq_len, head_dim, v_dim, dtype, budget=_BUDGET):
    """How the backward holds a key/value head of these shapes: the
    number of passes, the rows resident in each, whether dq and dk are
    accumulated transposed, and the kernel's VMEM limit.

    ``transposed`` where ``head_dim`` is not whole lane tiles (latent
    attention's 192): a product whose result is ``head_dim`` wide pads it
    to the next 128 inside the MXU, one whose result is ``head_dim`` TALL
    does not, so dq and dk are summed as (D, rows) and turned once (with
    whole lane tiles it would only load the MXU's weights four times as
    often).

    A row costs k and v in two buffers, float32 dk and dv, and dk and dv
    in the operands' dtype in two buffers, each padded to whole lane
    tiles (the transposed dk is not, and has a transposed k beside it);
    the segments are the fewest equal ones, whole score blocks each,
    whose rows fit ``budget``."""
    size = jnp.dtype(dtype).itemsize
    lanes = lambda n: -(-n // 128) * 128
    transposed = head_dim % 128 != 0
    row = lanes(v_dim) * (4 * size + 4) + lanes(head_dim) * 4 * size \
        + (head_dim * (4 + size) if transposed else lanes(head_dim) * 4)
    tiles = seq_len // _BLOCK
    segments = next((n for n in range(1, tiles + 1)
                     if tiles % n == 0 and row * seq_len // n <= budget),
                    tiles)
    rows = seq_len // segments
    return Plan(segments, rows, transposed, row * rows + _WORKING)


def _pick(cond, a, b):
    """``a if cond else b`` for a query block index that is a Python
    int (:func:`blocks_walked`) or the kernel's ``program_id``."""
    return (a if cond else b) if isinstance(cond, bool) \
        else jnp.where(cond, a, b)


def walk(seq_len, window=None, blocks=None):
    """The walk of a query block, as ``table(i)``: it yields the steps of
    query block ``i`` in the order the kernel takes them, each ``("run", lo,
    hi)`` (key blocks ``lo .. hi - 1``, unmasked) or ``("one", j,
    rule)`` (key block ``j`` with ``rule(key, query)`` over the block's
    own 0 .. 511 saying which cells live; a ``j`` outside the key blocks
    is no step).  ``i`` is a Python int or a traced scalar.  The three
    fillings (the module's docstring): causal, the band (``window``, a
    multiple of 512) and block diffusion (``blocks``, which divides 512;
    ``seq_len`` is then the clean and the noised half)."""
    causal = lambda key, query: key <= query
    if blocks is not None:
        half = seq_len // _BLOCK // 2
        shift = blocks.bit_length() - 1         # blocks is a power of two
        blk = lambda t: t >> shift

        def table(i):
            noised = i >= half
            own = _pick(noised, i - half, i)    # the clean block at pos(i)
            yield "run", 0, own
            yield ("one", _pick(noised, -1, own),
                   lambda key, query: blk(key) <= blk(query))
            yield ("one", _pick(noised, own, -1),
                   lambda key, query: blk(key) < blk(query))
            yield ("one", _pick(noised, i, -1),
                   lambda key, query: blk(key) == blk(query))
    elif window is not None:
        reach = window // _BLOCK

        def table(i):
            # the band's leftmost block lies ``reach`` blocks back: there
            # query - key < window is key > query inside the block
            left = i - reach
            yield "one", left, lambda key, query: key > query
            yield "run", left + 1, i
            yield "one", i, causal
    else:
        def table(i):
            yield "run", 0, i
            yield "one", i, causal
    return table


def blocks_walked(seq_len, window=None, blocks=None):
    """Score blocks the backward computes for one query head, counted
    from the walk's table: every block on or under the diagonal (528 at
    16 384 rows); with ``window`` those of the band, ``min(i, window /
    512) + 1`` for query block ``i`` (252 at a window of 4096); with
    ``blocks`` the two block-triangles and the noised diagonal (288)."""
    tiles = seq_len // _BLOCK
    table = walk(seq_len, window, blocks)
    n = 0
    for i in range(tiles):
        for kind, a, b in table(i):
            n += max(0, min(b, tiles) - max(a, 0)) if kind == "run" \
                else 0 <= a < tiles
    return n


def _kernel(first, tiles, chained, transposed, table):
    """The kernel of the pass that holds key/value blocks ``first ..
    first + tiles - 1``; ``chained`` where it starts from an earlier
    pass's float32 dq; ``transposed`` as ``plan`` says; ``table`` the
    walk of a query block (:func:`walk`)."""
    def kernel(*refs):
        q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref = refs[:6]
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs[6 + chained:][:6]
        kT = refs[-1]       # the head's k as (D, rows): transposed only
        g, i = pl.program_id(2), pl.program_id(3)
        turned = (lambda t: t.T) if transposed else (lambda t: t)
        block_of = lambda j: pl.ds(pl.multiple_of(j * _BLOCK, _BLOCK), _BLOCK)

        @pl.when((g == 0) & (i == 0))
        def _():
            dk_acc[...] = jnp.zeros(dk_acc.shape, _F32)
            dv_acc[...] = jnp.zeros(dv_acc.shape, _F32)
            if transposed:
                def turn(j, carry):
                    kT[:, block_of(j)] = k_ref[block_of(j), :].T
                    return carry
                lax.fori_loop(0, tiles, turn, 0)

        dq_acc[...] = turned(refs[6][...]) if chained \
            else jnp.zeros(dq_acc.shape, _F32)
        q, do = q_ref[...], do_ref[...]
        qT = q.T if transposed else None
        # keys in the sublanes, queries in the lanes: the rows' statistics
        # are (1, 512) rows that the sublanes share
        lse, di = lse_ref[...], di_ref[...]

        def block(j, allowed=None):
            """Key/value block ``j``; ``allowed(key, query)`` masks it."""
            rows = block_of(j - first)
            k, v = k_ref[rows, :], v_ref[rows, :]
            s = lax.dot_general(k, q, _NT, preferred_element_type=_F32)
            if allowed is not None:
                key = lax.broadcasted_iota(jnp.int32, s.shape, 0)
                query = lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(allowed(key, query), s, _MASKED)
            p = jnp.exp(s - lse)
            dv_acc[rows, :] += lax.dot(p.astype(do.dtype), do,
                                       preferred_element_type=_F32)
            dp = lax.dot_general(v, do, _NT, preferred_element_type=_F32)
            ds = ((dp - di) * p).astype(q.dtype)
            if transposed:      # (D, keys) and (D, queries)
                dk_acc[:, rows] += lax.dot_general(
                    qT, ds, _NT, preferred_element_type=_F32)
                dq_acc[...] += lax.dot(kT[:, rows], ds,
                                       preferred_element_type=_F32)
            else:
                dk_acc[rows, :] += lax.dot(ds, q,
                                           preferred_element_type=_F32)
                dq_acc[...] += lax.dot(ds.T, k, preferred_element_type=_F32)

        def before(j, carry):
            block(j)
            return carry

        # a step's part inside this pass's key/value blocks
        for kind, a, b in table(i):
            if kind == "run":
                lax.fori_loop(max(first, a) if isinstance(a, int)
                              else jnp.maximum(first, a),
                              jnp.minimum(b, first + tiles), before, 0)
            else:
                pl.when((a >= first) & (a < first + tiles))(
                    lambda a=a, b=b: block(a, b))
        dq_ref[...] = turned(dq_acc[...]).astype(dq_ref.dtype)

        @pl.when((g == pl.num_programs(2) - 1)
                 & (i == pl.num_programs(3) - 1))
        def _():
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
            if transposed:
                def turn(j, carry):
                    dk_ref[block_of(j), :] = \
                        dk_acc[:, block_of(j)].T.astype(dk_ref.dtype)
                    return carry
                lax.fori_loop(0, tiles, turn, 0)
            else:
                dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
    return kernel


# Jitted on its own, as the delta rule's: a model's layers of one
# geometry share ONE trace and ONE lowering of the kernel.
@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11))
def _run_pass(q, k, v, do, lse, di, dq, segment, z, interpret, window=None,
              blocks=None):
    """One pass of the ``Plan`` ``z``: (dq, dk, dv) of key/value segment
    ``segment``; ``dq`` is the earlier passes' float32 sum or None, and
    the result's dq is float32 unless the pass is the last.  ``window``
    (whole blocks) is the band, ``blocks`` the block diffusion's block
    length; both None is the causal walk."""
    B, Hq, S, D = q.shape
    Hk, Dv = k.shape[1], v.shape[3]
    G, tiles = Hq // Hk, z.rows // _BLOCK
    chained, last = dq is not None, segment == z.segments - 1
    of_q = lambda width: pl.BlockSpec(
        (None, None, _BLOCK, width), lambda b, h, g, i: (b, h * G + g, i, 0))
    stat = pl.BlockSpec(
        (None, None, 1, _BLOCK), lambda b, h, g, i: (b, h * G + g, 0, i))
    # a key/value head's rows: fetched when the head changes, written
    # back when it changes again
    of_kv = lambda width, at: pl.BlockSpec(
        (None, None, z.rows, width), lambda b, h, g, i: (b, h, at, 0))
    across = lambda shape: shape[::-1] if z.transposed else shape
    _count_launch("flash_attention_blocks_bwd" if blocks is not None
                  else "flash_attention_bwd" if window is None
                  else "flash_attention_window_bwd")
    return pl.pallas_call(
        _kernel(segment * tiles, tiles, chained, z.transposed,
                walk(S, window, blocks)),
        grid=(B, Hk, G, S // _BLOCK),
        in_specs=[of_q(D), of_q(Dv), stat, stat,
                  of_kv(D, segment), of_kv(Dv, segment)]
        + [of_q(D)] * chained,
        out_specs=[of_q(D), of_kv(D, 0), of_kv(Dv, 0)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype if last else _F32),
            jax.ShapeDtypeStruct((B, Hk, z.rows, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hk, z.rows, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM(across((_BLOCK, D)), _F32),
                        pltpu.VMEM(across((z.rows, D)), _F32),
                        pltpu.VMEM((z.rows, Dv), _F32)]
        + [pltpu.VMEM((D, z.rows), k.dtype)] * z.transposed,
        # a key/value head's scratch is carried over its query heads and
        # their blocks: only sequences are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=z.vmem_limit_bytes),
        name="flash_attention_backward", interpret=interpret,
    )(q, do, lse, di, k, v, *([dq] if chained else []))


def flash_attention_backward(q, k, v, o, lse, do, *, window=None,
                             blocks=None, interpret=False):
    """(dq, dk, dv) of masked softmax attention under one of the three
    static masks: ``o`` (B, Hq, S, Dv) and ``lse`` (B, Hq, S) float32 are
    the forward's result and row log-sum-exp, ``do`` the result's
    cotangent.  S is whole score blocks of 512, D and Dv what the
    forward's gate admits.  Causal (neither keyword): the 528 blocks on
    or under the diagonal a head at 16 384 rows.  ``window`` (a multiple
    of 512): a query attends the ``window`` keys that end with its own,
    and the blocks left of that band are never computed (252 of the 528
    at a window of 4096).  ``blocks`` (the block length, a divisor of
    512; S is a clean and a noised half of whole score blocks each): the
    block-diffusion mask of the module's docstring, 288 blocks."""
    B, Hq, S, D = q.shape
    if window is not None and (window <= 0 or window % _BLOCK):
        raise ValueError("pallas flash backward: window=%r is not whole "
                         "blocks of %d" % (window, _BLOCK))
    if blocks is not None and (window is not None or blocks <= 0
                               or _BLOCK % blocks or S % (2 * _BLOCK)):
        raise ValueError("pallas flash backward: blocks=%r does not divide "
                         "%d, S=%d is not two halves of whole blocks, or a "
                         "window was given beside it" % (blocks, _BLOCK, S))
    if S % _BLOCK or Hq % k.shape[1]:
        raise ValueError("pallas flash backward: S=%d is not whole blocks "
                         "of %d, or %d query heads are not whole groups of "
                         "%d key/value heads" % (S, _BLOCK, Hq, k.shape[1]))
    z = plan(S, D, v.shape[3], q.dtype)
    # the softmax's own term, sum_j p_ij dp_ij = sum(o_i * do_i): XLA's
    # row sums (inside the kernel, a query block at a time, they were
    # 0.3-3 % of the backward slower: PERF.md, PR 37)
    di = jnp.einsum("bhsd,bhsd->bhs", o.astype(_F32), do.astype(_F32))
    as_rows = lambda t: t.reshape(B, Hq, 1, S)
    dq, dks, dvs = None, [], []
    for segment in range(z.segments):
        dq, dk, dv = _run_pass(q, k, v, do, as_rows(lse), as_rows(di), dq,
                               segment, z, bool(interpret), window,
                               *(() if blocks is None else (blocks,)))
        dks.append(dk)
        dvs.append(dv)
    if z.segments == 1:
        return dq, dk, dv
    return dq, jnp.concatenate(dks, axis=2), jnp.concatenate(dvs, axis=2)
