"""Paged-KV-cache attention kernels (docs/KERNELS.md).

The XLA reference path in ops/nn.py gathers the **entire addressable
context** per slot per decode step (``jnp.take`` over the block table
-> a ``(C, M*bs, H, D)`` temp) — O(cache) HBM traffic per token.  The
kernels here walk the block table INSIDE the kernel via Pallas scalar
prefetch: each grid step's ``BlockSpec`` index map reads the prefetched
table to pull exactly one cache block into VMEM, an online softmax
accumulates across blocks, and no gathered context tensor ever exists.

* :func:`paged_decode_attend` — one token per slot against its cache
  rows ``[0, pos]``; inactive slots (``pos < 0``) emit zeros (the XLA
  path emits don't-care values there; the engine masks both).
* :func:`paged_prefill_attend` — causal MHA over the padded prompt
  batch with the K/V cache scatter FUSED into the same kernel: per
  (row, cache-block) grid step the kernel writes the block's new rows
  (masked to ``< length``) through an input/output-aliased cache.
  Grid steps past a row's last real block are clamped onto that block
  (an idempotent duplicate write), so padded table entries are never
  dereferenced — the in-kernel equivalent of the XLA path's ``nb*bs``
  OOB-drop sentinel.

* :func:`paged_chunk_prefill_attend` — the chunked-prefill variant:
  a K-token chunk of ONE prompt attends causally against the full
  context so far (prior chunks read back from the paged cache, the
  chunk's own rows merged in-kernel), with the chunk's K/V scatter
  fused through the same clamp-onto-last-real-block discipline but
  addressed at an absolute ``start`` offset into an EXISTING cache.

What Mosaic (the TPU kernel compiler) needs, and tests/
test_chip_compile.py holds at real widths: every ``dot_general``
batches over the LEADING axis, so the cache blocks — stored seq-major
``(bs, H, D)`` — are swapped head-major in VMEM before they meet the
MXU; rows are picked by ``pl.ds`` on a ref or by a BlockSpec index
map, never by ``lax.dynamic_slice`` on a value; masks are built at the
full shape of what they select.

The wrappers compile for the backend they run on.  ``interpret=True``
(CPU tier-1: the exact kernel logic against the XLA reference, parity
pinned at rtol<=2e-5 f32 in tests/test_pallas.py) is only ever an
explicit argument.  Block-size tuning notes live in docs/KERNELS.md.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry as _telemetry
from ..telemetry.registry import RETRACE_SUPPRESS
from .dispatch import PALLAS_LAUNCHES, PALLAS_RETRACES

# kernel (re)builds land in a vital counter like every other trace
# site; wrappers note() at build time (trace time of the enclosing
# program), so a kernel being reconstructed per call is visible
_SITE = _telemetry.RetraceSite(PALLAS_RETRACES, _telemetry.JIT_COMPILE_MS,
                               site="pallas")
_note_kernel_build = _SITE.note


# finite mask fill (not -inf): a whole block can be masked for a query
# row, and exp(m_prev - max(m_prev, fill)) must stay 0/1, never NaN
_NEG = -1e30


def _count_launch(kernel):
    """One kernel build (trace time).  Each site then runs its kernel
    under ``jax.named_scope("pallas." + kernel)``, the label
    ``PALLAS_LAUNCHES`` carries: the kernel's instructions keep that
    name in a device trace (docs/OBSERVABILITY.md, "Scope names")."""
    _note_kernel_build()
    if not RETRACE_SUPPRESS.on:   # skip program-registry re-lowers
        PALLAS_LAUNCHES.labels(kernel=kernel).inc()


def _head_major(k, v, dtype):
    """Seq-major cache blocks ``(bs, H, D)`` -> ``(H, bs, D)`` in the
    query's dtype: Mosaic batches a matmul over the leading axis only."""
    return (jnp.swapaxes(k.astype(dtype), 0, 1),
            jnp.swapaxes(v.astype(dtype), 0, 1))


def _bdot(a, b, b_contract):
    """Head-batched matmul, f32 accumulation: contracts ``a``'s last
    axis with axis ``b_contract`` of ``b``, batching over axis 0.
    bf16 operands pin the MXU's native pass: a process-wide
    ``jax_default_matmul_precision`` of float32 (tests/conftest.py sets
    one) would otherwise ask Mosaic for an fp32 contraction of bf16
    vectors, which it refuses."""
    return jax.lax.dot_general(
        a, b, (((2,), (b_contract,)), ((0,), (0,))),
        precision=(jax.lax.Precision.DEFAULT
                   if a.dtype == jnp.bfloat16 else None),
        preferred_element_type=jnp.float32)


def _online_softmax_step(s, v, acc_ref, m_ref, l_ref):
    """One block of the running softmax: ``s (H, Q, bs)`` masked f32
    scores (finite fill), ``v (H, bs, D)``; state refs ``(H, Q, D)`` /
    ``(H, Q, 1)``."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + _bdot(p.astype(v.dtype), v, 1)
    m_ref[...] = m_new


def _softmax_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)


def _softmax_emit(o_ref, acc_ref, l_ref):
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(
        o_ref.dtype)


def _last_block(end, bs):
    """Index of the last block holding any of rows ``[0, end)`` (block
    0 for an empty row)."""
    return jnp.maximum(-(-end // bs), 1) - 1


# ----------------------------------------------------------------------
# decode: one token per slot, online softmax over the slot's blocks
# ----------------------------------------------------------------------
def _paged_decode_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, bs, scale):
    c = pl.program_id(0)
    m = pl.program_id(1)
    pos = pos_ref[c]

    @pl.when(m == 0)
    def _init():
        _softmax_init(acc_ref, m_ref, l_ref)

    # blocks past the slot's position never enter the softmax (their
    # grid steps re-address the last real block, so they cost no DMA
    # either); an inactive slot, pos < 0, skips every block
    @pl.when(jnp.logical_and(pos >= 0, m * bs <= pos))
    def _block():
        q = q_ref[0]                                  # (H, 1, D)
        k, v = _head_major(k_ref[0], v_ref[0], q.dtype)
        s = _bdot(q, k, 2) * scale                    # (H, 1, bs)
        j = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) + m * bs
        _online_softmax_step(jnp.where(j <= pos, s, _NEG), v,
                             acc_ref, m_ref, l_ref)

    @pl.when(m == pl.num_programs(1) - 1)
    def _emit():
        _softmax_emit(o_ref, acc_ref, l_ref)


def paged_decode_attend(q, k_cache, v_cache, block_table, positions, *,
                        scale, interpret=False):
    """Paged decode attention: ``q (C, H, D)`` against cache rows
    ``[0, positions[c]]`` addressed through ``block_table (C, M)``;
    ``k_cache/v_cache (num_blocks, block_size, H, D)`` already hold
    the current token's K/V (the scatter is XLA-side in ops/nn.py,
    shared with the reference path).  Returns ``(C, H, D)``; inactive
    slots (``positions < 0``) return zeros.  The grid is (slot, table
    block); each step's index map reads the scalar-prefetched table so
    exactly one cache block streams through VMEM per step."""
    C, H, D = q.shape
    bs = k_cache.shape[1]
    M = block_table.shape[1]
    _count_launch("paged_decode_attend")

    def cache_block(c, m, t, p):
        # steps past the slot's last block stay on it: an unchanged
        # block index is not fetched again, and padded table entries
        # are never dereferenced
        return (t[c, jnp.minimum(m, _last_block(p[c] + 1, bs))], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(C, M),
        in_specs=[
            pl.BlockSpec((1, H, 1, D), lambda c, m, t, p: (c, 0, 0, 0)),
            pl.BlockSpec((1, bs, H, D), cache_block),
            pl.BlockSpec((1, bs, H, D), cache_block),
        ],
        out_specs=pl.BlockSpec((1, H, 1, D),
                               lambda c, m, t, p: (c, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1, D), jnp.float32),   # online-softmax acc
            pltpu.VMEM((H, 1, 1), jnp.float32),   # running max
            pltpu.VMEM((H, 1, 1), jnp.float32),   # running denom
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_paged_decode_kernel, bs=bs,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, H, 1, D), q.dtype),
        interpret=interpret,
    )
    with jax.named_scope("pallas.paged_decode_attend"):
        return fn(block_table.astype(jnp.int32),
                  positions.astype(jnp.int32), q.reshape(C, H, 1, D),
                  k_cache, v_cache).reshape(C, H, D)


# ----------------------------------------------------------------------
# prefill: causal MHA + the cache scatter fused into one kernel
# ----------------------------------------------------------------------
def _paged_prefill_kernel(table_ref, len_ref, q_ref, k_ref, v_ref,
                          kn_ref, vn_ref, kc_ref, vc_ref, o_ref, ko_ref,
                          vo_ref, *, bs, scale):
    b = pl.program_id(0)
    m = pl.program_id(1)
    L = len_ref[b]

    # causal attention for query rows [m*bs, (m+1)*bs) against the
    # row's full K/V (VMEM-resident: prefill buckets are short); the
    # wrapper hands q/k/v over head-major
    q = q_ref[0]                                      # (H, bs, D)
    s = _bdot(q, k_ref[0], 2) * scale                 # (H, bs, S)
    jq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + m * bs
    jk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(jq >= jk, s, _NEG)
    p = jnp.exp(s - jnp.max(s, axis=2, keepdims=True))
    p = p / jnp.sum(p, axis=2, keepdims=True)
    o_ref[0] = _bdot(p.astype(q.dtype), v_ref[0], 1).astype(o_ref.dtype)

    # fused scatter: this block's K/V rows (kn/vn: the seq-major rows
    # of block m_eff, picked by the index map) into cache block
    # table[b, m_eff], masked to rows < L.  Grid steps PAST the row's
    # last real block (m*bs >= L, where the table holds
    # padding/garbage) are CLAMPED onto it by every index map, so the
    # step re-emits that block's exact bytes: a duplicate idempotent
    # write instead of a write through an untrusted table entry (the
    # in-kernel analog of the XLA path's nb*bs OOB-drop sentinel,
    # which likewise never dereferences padded entries).
    m_eff = jnp.minimum(m, _last_block(L, bs))
    row = (jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[1:], 0)
           + m_eff * bs)
    keep = row < L
    ko_ref[0] = jnp.where(keep, kn_ref[0].astype(ko_ref.dtype), kc_ref[0])
    vo_ref[0] = jnp.where(keep, vn_ref[0].astype(vo_ref.dtype), vc_ref[0])


def paged_prefill_attend(q, k, v, k_cache, v_cache, block_table,
                         lengths, *, scale, interpret=False):
    """Causal MHA over ``q/k/v (B, S, H, D)`` with the scatter of each
    row's first ``lengths[b]`` K/V rows into the paged cache fused into
    the same kernel (the caches are input/output aliased — in-place
    block writes, no whole-cache copy).  Returns
    ``(out (B, S, H, D), new_k_cache, new_v_cache)``.  ``S`` is padded
    up to a block-size multiple internally, so any prefill bucket
    geometry works."""
    B, S, H, D = q.shape
    bs = k_cache.shape[1]
    pad = (-S) % bs
    if pad:
        # padded keys sit at jk >= S: the causal mask keeps them out of
        # every real query row, and `keep` (row >= L) keeps them out of
        # the cache
        zeros = jnp.zeros((B, pad, H, D), q.dtype)
        q = jnp.concatenate([q, zeros], axis=1)
        k = jnp.concatenate([k, zeros.astype(k.dtype)], axis=1)
        v = jnp.concatenate([v, zeros.astype(v.dtype)], axis=1)
    Sp = S + pad
    Mq = Sp // bs
    if block_table.shape[1] < Mq:
        raise ValueError(
            "paged_prefill_attend: block_table width %d < %d blocks "
            "needed for a %d-token prompt at block_size %d"
            % (block_table.shape[1], Mq, S, bs))
    _count_launch("paged_prefill_attend")

    def clamped(b, m, l):
        # clamp to the row's LAST REAL block once m runs past the
        # length: table entries there are padding (the engine leaves
        # zeros) and must never be dereferenced — the kernel re-emits
        # the last real block instead (idempotent duplicate write)
        return jnp.minimum(m, _last_block(l[b], bs))

    def cache_block(b, m, t, l):
        return (t[b, clamped(b, m, l)], 0, 0, 0)

    def new_rows(b, m, t, l):
        return (b, clamped(b, m, l), 0, 0)

    head_major = pl.BlockSpec((1, H, Sp, D), lambda b, m, t, l: (b, 0, 0, 0))
    q_block = pl.BlockSpec((1, H, bs, D), lambda b, m, t, l: (b, 0, m, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Mq),
        in_specs=[
            q_block, head_major, head_major,
            pl.BlockSpec((1, bs, H, D), new_rows),
            pl.BlockSpec((1, bs, H, D), new_rows),
            pl.BlockSpec((1, bs, H, D), cache_block),
            pl.BlockSpec((1, bs, H, D), cache_block),
        ],
        out_specs=[
            q_block,
            pl.BlockSpec((1, bs, H, D), cache_block),
            pl.BlockSpec((1, bs, H, D), cache_block),
        ],
        scratch_shapes=[],
    )
    fn = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, bs=bs,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, D), q.dtype),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        # cache in -> cache out: in-place block writes, no cache copy
        # (scalar-prefetch args count: table=0, len=1, q=2, k=3, v=4,
        # kn=5, vn=6, k_cache=7, v_cache=8)
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
    )
    hm = lambda x: x.transpose(0, 2, 1, 3)            # noqa: E731
    with jax.named_scope("pallas.paged_prefill_attend"):
        out, ko, vo = fn(block_table.astype(jnp.int32),
                         lengths.astype(jnp.int32), hm(q),
                         hm(k).astype(q.dtype), hm(v).astype(q.dtype), k, v,
                         k_cache, v_cache)
    return hm(out)[:, :S], ko, vo


# ----------------------------------------------------------------------
# chunked prefill: one prompt chunk against an EXISTING cache prefix
# ----------------------------------------------------------------------
def _paged_chunk_prefill_kernel(table_ref, start_ref, len_ref, q_ref,
                                kpad_ref, vpad_ref, kc_ref, vc_ref,
                                o_ref, ko_ref, vo_ref, acc_ref, m_ref,
                                l_ref, *, bs, scale):
    b = pl.program_id(0)
    m = pl.program_id(1)
    st = start_ref[b]
    L = len_ref[b]
    end = st + L
    # blocks holding real context once this chunk lands: [0, nctx)
    nctx = _last_block(end, bs) + 1
    m_eff = jnp.minimum(m, nctx - 1)

    @pl.when(m == 0)
    def _init():
        _softmax_init(acc_ref, m_ref, l_ref)

    # merge the chunk's rows into this step's cache block: block m_eff
    # holds absolute rows [m_eff*bs, m_eff*bs + bs); rows inside
    # [start, end) come from the chunk (kpad carries bs zero rows on
    # each side so the slice stays in-bounds when the chunk straddles a
    # block boundary; a block that lies wholly outside the chunk clamps
    # its slice onto the padding, where `in_chunk` selects nothing),
    # every other row keeps its existing cache bytes.  Clamped steps
    # (m >= nctx) re-emit the last real block's exact bytes — the
    # idempotent duplicate write that keeps padded table entries
    # undereferenced.
    row_abs = (jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[1:], 0)
               + m_eff * bs)
    in_chunk = jnp.logical_and(row_abs >= st, row_abs < end)
    # chunk-local (padded) index of the block's first row
    off = jnp.clip(m_eff * bs - st + bs, 0, kpad_ref.shape[1] - bs)
    kblk = jnp.where(in_chunk,
                     kpad_ref[0, pl.ds(off, bs)].astype(kc_ref.dtype),
                     kc_ref[0])
    vblk = jnp.where(in_chunk,
                     vpad_ref[0, pl.ds(off, bs)].astype(vc_ref.dtype),
                     vc_ref[0])
    ko_ref[0] = kblk
    vo_ref[0] = vblk

    # online softmax over the merged context blocks; clamped steps are
    # skipped so the duplicate write never double-counts a block
    @pl.when(m < nctx)
    def _block():
        q = q_ref[0]                                  # (H, K, D)
        kk, vv = _head_major(kblk, vblk, q.dtype)     # (H, bs, D)
        s = _bdot(q, kk, 2) * scale                   # (H, K, bs)
        jq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + st
        jk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) + m * bs
        # causal over the FULL context: prior chunks fully visible,
        # in-chunk keys causally
        _online_softmax_step(jnp.where(jk <= jq, s, _NEG), vv,
                             acc_ref, m_ref, l_ref)

    @pl.when(m == pl.num_programs(1) - 1)
    def _emit():
        _softmax_emit(o_ref, acc_ref, l_ref)


def paged_chunk_prefill_attend(q, k, v, k_cache, v_cache, block_table,
                               start, lengths, *, scale,
                               interpret=False):
    """Chunked prefill attention over an EXISTING cache: the chunk rows
    ``q/k/v (B, K, H, D)`` sit at absolute positions
    ``[start[b], start[b] + lengths[b])`` of their sequences; each
    chunk query attends causally against the full context so far —
    earlier chunks' K/V are streamed back from the paged cache block by
    block, the chunk's own K/V are merged in-kernel before the block is
    both attended and written back through the input/output-aliased
    caches.  Rows past ``lengths[b]`` are padding: never scattered,
    outputs don't-care.  ``lengths[b] == 0`` makes row ``b`` a no-op
    (block 0 is re-emitted byte-identically).  Returns
    ``(out (B, K, H, D), new_k_cache, new_v_cache)``.

    The per-row start/length geometry makes this kernel double as the
    VERIFY step of speculative decoding (docs/DECODE.md): the engine's
    span step batches one draft span per slot — row ``b`` holds a
    slot's last committed token plus its draft, ``start[b]`` its cache
    cursor — so scoring K+1 positions for every slot costs the same
    single launch as one prompt chunk.  Nothing here is spec-specific:
    the span IS a chunk that happens to contain unverified tokens."""
    B, K, H, D = q.shape
    bs = k_cache.shape[1]
    M = block_table.shape[1]
    _count_launch("paged_chunk_prefill_attend")
    zk = jnp.zeros((B, bs, H, D), k.dtype)
    zv = jnp.zeros((B, bs, H, D), v.dtype)
    kpad = jnp.concatenate([zk, k, zk], axis=1)   # (B, K + 2*bs, H, D)
    vpad = jnp.concatenate([zv, v, zv], axis=1)
    Kp = K + 2 * bs

    def cache_block(b, m, t, st, l):
        # same clamp as paged_prefill_attend, but the last real block
        # is start+length blocks in — the chunk extends a live prefix
        return (t[b, jnp.minimum(m, _last_block(st[b] + l[b], bs))],
                0, 0, 0)

    q_block = pl.BlockSpec((1, H, K, D),
                           lambda b, m, t, st, l: (b, 0, 0, 0))
    chunk_rows = pl.BlockSpec((1, Kp, H, D),
                              lambda b, m, t, st, l: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, M),
        in_specs=[
            q_block, chunk_rows, chunk_rows,
            pl.BlockSpec((1, bs, H, D), cache_block),
            pl.BlockSpec((1, bs, H, D), cache_block),
        ],
        out_specs=[
            q_block,
            pl.BlockSpec((1, bs, H, D), cache_block),
            pl.BlockSpec((1, bs, H, D), cache_block),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, K, D), jnp.float32),   # online-softmax acc
            pltpu.VMEM((H, K, 1), jnp.float32),   # running max
            pltpu.VMEM((H, K, 1), jnp.float32),   # running denom
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_paged_chunk_prefill_kernel, bs=bs,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, K, D), q.dtype),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        # cache in -> cache out: in-place block writes, no cache copy
        # (scalar-prefetch args count: table=0, start=1, len=2, q=3,
        # kpad=4, vpad=5, k_cache=6, v_cache=7)
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
    )
    with jax.named_scope("pallas.paged_chunk_prefill_attend"):
        out, ko, vo = fn(
            block_table.astype(jnp.int32), start.astype(jnp.int32),
            lengths.astype(jnp.int32), q.transpose(0, 2, 1, 3), kpad, vpad,
            k_cache, v_cache)
    return out.transpose(0, 2, 1, 3), ko, vo
