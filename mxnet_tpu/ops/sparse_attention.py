"""Attention over keys that a learned scorer picks (the published
DeepSeek Sparse Attention; ``sym.contrib.SparseIndexedAttention`` in
``ops/nn.py`` makes the operands).

For one sequence: queries ``q`` (Hq, S, D), keys and values ``k``, ``v``
(Hk, S, D), Hq a multiple of Hk; the scorer's queries ``qi`` (Hi, S, Di),
its ONE key a token ``ki`` (S, Di) and its head weights ``wi`` (S, Hi),
float32, the two constant scales already in ``wi``.

    I[t, s] = sum_j wi[t, j] * relu(qi[j, t] . ki[s])        for s <= t
    S_t     = the ``topk`` keys s <= t with the largest I[t, s], a tie
              going to the lower s; all of them while t < topk
    o[h, t] = softmax over s in S_t of (q[h, t] . k[s] * scale) times v
    L       = sum_t KL(p_t || softmax over S_t of I[t, :]),
              p_t[s] = mean over the Hq heads of their probabilities

:func:`sparse_indexed_attention` takes the function that makes these
six from a layer's operands, and returns ``(o, L, live)``.  Its gradient
is written out (``jax.custom_vjp``): ``o``'s reaches q, k, v and nothing
else; ``L``'s reaches qi, ki, wi and nothing else (``p_t`` is a
constant of the scorer's objective, and the choice carries no gradient);
from the six it goes on through the maker's own.

How the S x S work is done.  A block of ``q_chunk`` query rows at a
time (``lax.map`` forward, ``lax.scan`` backward), because a block's
choice is made from its whole (q_chunk, S) row of scorer scores before
its cores can run: nothing S x S x heads is ever whole.  The choice is
the ``topk``-th largest of that row by bisection over the scores' bit
patterns (32 counting passes, no sort), then the tie rule.  It is kept
for the backward pass as bits (S x S / 8 bytes), so it is never made
twice and cannot come out differently.

The scorer's row and its gradients (scopes ``dsa.indexer``,
``dsa.index_loss``), the choice (scope ``dsa.select``) and the heads'
cores (scope ``dsa.attention``) have two forms that share everything
else (``plan``, the bits, the log-sum-exp and the KL of the index loss)
and are chosen together from the program, not by a user
(:func:`_cores_impl`; a test passes ``impl``):

* in a one-device TPU program whose shapes they take, two Pallas kernel
  pairs in which no array with a head axis and a key axis reaches HBM,
  and the choice's kernel between them.
  ``pallas/index_scorer.py``: forward a block's row ``I`` a 512-key tile
  at a time up to the diagonal, the heads' products behind the ReLU
  summed in VMEM; backward the products again, kept in VMEM beside
  ``dI = on * (exp(I - lse_i) - pt)``, and from them ``g`` (which gives
  ``dqi`` and ``dwi``) and the keys' gradient, added in place into the
  (Di, S) float32 sum the scan carries.  Its products are float32 as
  ``HIGHEST`` makes them, the six bfloat16 terms of each laid side by
  side so the MXU runs full.
  ``pallas/topk_choice.py``: :func:`choose`'s set to the bit, from the
  block's row ``I`` held in VMEM as integer keys: the 32 counting passes
  and the two walks after them over the tiles up to the diagonal only,
  the tie rule in the last walk, the mask written once as the int8 the
  cores read and as the bits the backward pass keeps.
  ``pallas/sparse_attention.py``: the
  block's mask goes in as an int8 operand, every (kv_chunk x q_chunk)
  score tile up to the diagonal lives in VMEM only, forward two walks
  over the key tiles in one call (the heads' row statistics; then the
  exact probabilities, ``p v`` and the head-mean probabilities ``pt`` as
  a (q_chunk, S) row), backward one walk that emits dq, adds into the
  float32 dk and dv the scan carries (in place) and hands back the same
  ``pt`` row, which the scorer's backward kernel reads.  XLA keeps the
  live tiles, ``lse_i`` and the KL, each one pass over the block's
  rows;
* everywhere else (the CPU, a mesh, other shapes), plain XLA: the
  scorer's row a chunk of keys at a time up to the block's diagonal (a
  ``fori_loop`` with the block's trip count, float32 at ``HIGHEST``);
  the choice :func:`choose` over the block's whole row; the cores
  forward two passes over chunks of keys (statistics, then
  probabilities, result and the KL terms), backward one pass that
  recomputes the heads' scores and the scorer's and emits every
  gradient, float32 score arrays (heads, q_chunk, chunk) in memory.

Either way every S x S product runs over ALL causal pairs under the
mask: at 16 384 tokens and 2048 keys 23 % of that work is chosen pairs
(``live`` counts the ``q_chunk`` x ``kv_chunk`` tiles that hold one).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..pallas import index_scorer as scorer
from ..pallas import sparse_attention as kernels
from ..pallas import topk_choice

_HI = lax.Precision.HIGHEST
_LOOP_CHUNKS = 4        # kv_chunk tiles a key-loop iteration takes
_NEG = -1e30


def plan(S, q_chunk, kv_chunk):
    """``(bq, tile, kc, S_pad)``: rows a query block, keys a counted
    tile, keys a loop iteration (a few tiles), and the padded length (a
    multiple of all three and of 8)."""
    bq = -(-min(int(q_chunk), S) // 8) * 8
    tile = -(-min(int(kv_chunk), S) // 8) * 8
    S_pad = -(-S // math.lcm(bq, tile)) * math.lcm(bq, tile)
    for m in (_LOOP_CHUNKS, 2):
        if S_pad % (tile * m) == 0:
            return bq, tile, tile * m, S_pad
    return bq, tile, tile, S_pad


def _sortable(x):
    """float32 -> uint32, order-preserving (-0.0 as +0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def choose(scores, causal, topk):
    """The chosen mask (rows, S) bool of float32 ``scores`` (rows, S):
    per row the ``topk`` largest among ``causal``, a tie to the lower
    column; every causal column of a row that has no more than ``topk``.
    No sort: the ``topk``-th largest key by bisection over its 32 bits."""
    key = jnp.where(causal, _sortable(scores), jnp.uint32(0))

    def bit(i, tau):
        cand = tau | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= topk, cand, tau)

    tau = lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[:1], jnp.uint32))
    above = key > tau[:, None]
    ties = key == tau[:, None]
    room = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)
    n_ties = jnp.sum(ties, axis=-1, dtype=jnp.int32)
    first = lax.cond(
        jnp.all(n_ties <= room), lambda: ties,
        lambda: ties & (jnp.cumsum(ties.astype(jnp.int32), axis=-1)
                        <= room[:, None]))
    return causal & (above | first)


def _pack(mask, kc):
    """(rows, S) bool -> (rows, S / 8) uint8, a loop chunk of ``kc``
    columns into ``kc / 8`` bytes: bit b of byte j is column b * kc/8 + j
    of the chunk."""
    rows, S = mask.shape
    m = mask.reshape(rows, S // kc, 8, kc // 8).astype(jnp.uint8)
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, None, :, None]
    return jnp.sum(m << shifts, axis=2, dtype=jnp.uint8).reshape(rows, S // 8)


def _unpack(bits, kc=None):
    """Whole loop chunks' bytes (rows, n) -> (rows, 8 n) bool; one chunk
    of ``8 n`` columns unless ``kc`` says how many a chunk holds."""
    rows, n = bits.shape
    per = n if kc is None else kc // 8
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, None, :, None]
    return ((bits.reshape(rows, n // per, 1, per) >> shifts) & 1) \
        .astype(bool).reshape(rows, 8 * n)


def _scorer_chunk(qi, ki_c, wi):
    """(z, I) of a block's scorer queries (Hi, bq, Di) on a chunk of
    keys (kc, Di): ``z`` (Hi, bq, kc) the heads' products, ``I`` (bq,
    kc) their weighted sum behind the ReLU."""
    z = jnp.einsum("hqd,kd->hqk", qi, ki_c, precision=_HI,
                   preferred_element_type=jnp.float32)
    return z, jnp.sum(wi.T[:, :, None] * jax.nn.relu(z), axis=0)


def _head_scores(q, k_c, scale):
    """float32 scores (Hk, R, bq, kc) of a block's grouped queries
    (Hk, R, bq, D) on a chunk of keys (Hk, kc, D)."""
    return jnp.einsum("grqd,gkd->grqk", q, k_c,
                      preferred_element_type=jnp.float32) * scale


def _rows(x, start, size, axis):
    return lax.dynamic_slice_in_dim(x, start, size, axis)


def _kl_rows(on, pt, logq):
    """Per row, ``sum over the chosen of pt (log pt - logq)``."""
    return jnp.sum(jnp.where(
        on & (pt > 0), pt * (jnp.log(jnp.where(pt > 0, pt, 1.0)) - logq),
        0.0), axis=-1)


def _core_fwd(q, k, v, qi, ki, wi, S, topk, bq, kc, tile, impl):
    """Padded operands in, ``(o, L, live)`` out; ``S`` is the real
    length, ``tile`` the key width of a counted tile, ``impl`` how the
    S x S work runs (:func:`_cores_impl`)."""
    Hq, Sp, D = q.shape
    Hk = k.shape[0]
    R, nq = Hq // Hk, Sp // bq
    scale = D ** -0.5
    col = jnp.arange(Sp, dtype=jnp.int32)
    interpret = impl == "interpret"
    if impl:        # the scorer's keys as bfloat16 parts, split once
        with jax.named_scope("dsa.indexer"):
            kcat = scorer.keys(ki)

    def block(xs):
        i, qb = xs
        r0 = i * bq
        row = r0 + jnp.arange(bq, dtype=jnp.int32)
        n_c = (r0 + bq + kc - 1) // kc          # key chunks to the diagonal
        tiles = (r0 + bq + tile - 1) // tile    # key tiles to the diagonal
        qib, wib = _rows(qi, r0, bq, 1), _rows(wi, r0, bq, 0)
        causal = col[None, :] <= row[:, None]

        # 1. the scorer's row, and the choice
        def score(c, acc):
            _, ic = _scorer_chunk(qib, _rows(ki, c * kc, kc, 0), wib)
            return lax.dynamic_update_slice_in_dim(acc, ic, c * kc, 1)

        with jax.named_scope("dsa.indexer"):
            if impl:    # in VMEM: pallas/index_scorer.py
                ib = scorer.forward(qib, wib, kcat, tiles, tile,
                                    interpret=interpret)
            else:
                ib = lax.fori_loop(0, n_c, score,
                                   jnp.zeros((bq, Sp), jnp.float32))
        with jax.named_scope("dsa.select"):
            if impl:    # in VMEM, as int8 and as bits: pallas/topk_choice.py
                on8, bits = lax.cond(
                    r0 + bq > topk,
                    lambda: topk_choice.choose(ib, r0, tiles, topk, tile, kc,
                                               interpret=interpret),
                    lambda: (causal.astype(jnp.int8), _pack(causal, kc)))
                chosen = on8 != 0
            else:
                chosen = lax.cond(r0 + bq > topk,
                                  lambda: choose(ib, causal, topk),
                                  lambda: causal)
                bits = _pack(chosen, kc)
            real = chosen & (row < S)[:, None]
            live = jnp.sum(jnp.any(real.reshape(bq, Sp // tile, tile),
                                   axis=(0, 2)), dtype=jnp.int32)
        with jax.named_scope("dsa.index_loss"):
            lse_i = jax.nn.logsumexp(jnp.where(chosen, ib, _NEG), axis=-1)

        # 2. the heads' row statistics under the choice
        def stats(c, ml):
            m, l = ml
            s = _head_scores(qb, _rows(k, c * kc, kc, 1), scale)
            s = jnp.where(_rows(chosen, c * kc, kc, 1), s, _NEG)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1))
            return m2, l * jnp.exp(m - m2) + jnp.sum(
                jnp.exp(s - m2[..., None]), axis=-1)

        # 3. exact probabilities: the result, and their sum over heads
        def result(c, acc):
            o, kl = acc
            on = _rows(chosen, c * kc, kc, 1)
            s = _head_scores(qb, _rows(k, c * kc, kc, 1), scale)
            p = jnp.where(on, jnp.exp(s - lse[..., None]), 0.0)
            o = o + jnp.einsum("grqk,gkd->grqd", p.astype(v.dtype),
                               _rows(v, c * kc, kc, 1),
                               preferred_element_type=jnp.float32)
            with jax.named_scope("dsa.index_loss"):
                pt = jnp.sum(p, axis=(0, 1)) * (1.0 / Hq)
                kl = kl + _kl_rows(
                    on, pt, _rows(ib, c * kc, kc, 1) - lse_i[:, None])
            return o, kl

        if impl:        # 2. and 3. in VMEM: pallas/sparse_attention.py
            with jax.named_scope("dsa.attention"):
                o, lse, pt = kernels.forward(
                    qb, k, v, on8, tiles, tile, interpret=interpret)
            with jax.named_scope("dsa.index_loss"):
                kl = _kl_rows(chosen, pt, ib - lse_i[:, None])
        else:
            with jax.named_scope("dsa.attention"):
                m, l = lax.fori_loop(
                    0, n_c, stats, (jnp.full((Hk, R, bq), _NEG, jnp.float32),
                                    jnp.zeros((Hk, R, bq), jnp.float32)))
                lse = m + jnp.log(l)
                o, kl = lax.fori_loop(
                    0, n_c, result, (jnp.zeros((Hk, R, bq, D), jnp.float32),
                                     jnp.zeros((bq,), jnp.float32)))
        kl = jnp.sum(jnp.where(row < S, kl, 0.0))
        return o.astype(q.dtype), lse, lse_i, bits, kl, live

    o, lse, lse_i, bits, kl, live = lax.map(
        block, (jnp.arange(nq),
                q.reshape(Hk, R, nq, bq, D).transpose(2, 0, 1, 3, 4)))
    # (nq, Hk, R, bq, D) -> (Hq, Sp, D)
    o = o.transpose(1, 2, 0, 3, 4).reshape(Hq, Sp, D)
    return (o, jnp.sum(kl), jnp.sum(live)), (lse, lse_i, bits)


def _core_bwd(q, k, v, qi, ki, wi, o, lse, lse_i, bits, do, dl, S, bq, kc,
              tile, impl):
    """The gradients to q, k, v (from ``do``) and to qi, ki, wi (from
    ``dl``) of one padded sequence, from what the forward kept."""
    Hq, Sp, D = q.shape
    Hk, Hi, Di = k.shape[0], qi.shape[0], qi.shape[2]
    R, nq = Hq // Hk, Sp // bq
    scale = D ** -0.5
    f32 = jnp.float32
    do = do.astype(q.dtype)
    blocks = lambda x: x.reshape(Hk, R, nq, bq, D).transpose(2, 0, 1, 3, 4)
    with jax.named_scope("dsa.attention"):
        delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1) \
            .reshape(Hk, R, nq, bq).transpose(2, 0, 1, 3)
    interpret = impl == "interpret"
    if impl:        # the scorer's keys as bfloat16 parts, split once
        with jax.named_scope("dsa.index_loss"):
            kcat, kg = scorer.keys(ki), scorer.gradient_keys(ki)

    def block_in_vmem(carry, xs):
        """A block's gradients with every S x S array in VMEM: the
        cores' kernel, then the scorer's on the ``pt`` row it hands
        back.  The keys' scorer gradient is carried transposed, for a
        loss cotangent of 1."""
        dk, dv, dki_t = carry
        i, qb, dob, delta_b, lse_b, lse_ib, bits_b = xs
        r0 = i * bq
        tiles = (r0 + bq + tile - 1) // tile
        qib, wib = _rows(qi, r0, bq, 1), _rows(wi, r0, bq, 0)
        on = _unpack(bits_b, kc)
        with jax.named_scope("dsa.attention"):
            dq, dk, dv, pt_b = kernels.backward(
                qb, dob, lse_b, delta_b, on.astype(jnp.int8), k, v, dk, dv,
                tiles, tile, interpret=interpret)
        with jax.named_scope("dsa.index_loss"):
            if S < Sp:      # the padded rows carry no loss
                on = on & (r0 + jnp.arange(bq) < S)[:, None]
            g, dki_t = scorer.backward(
                qib, wib, lse_ib, on.astype(jnp.int8), pt_b, kcat, kg,
                dki_t, tiles, tile, interpret=interpret)
            dqi = dl * wib.T[:, :, None] * g
            dwi = dl * jnp.sum(qib * g, axis=-1).T
        return (dk, dv, dki_t), (dq, dqi, dwi)

    def block(carry, xs):
        dk, dv, dki = carry
        i, qb, dob, delta_b, lse_b, lse_ib, bits_b = xs
        r0 = i * bq
        row = r0 + jnp.arange(bq, dtype=jnp.int32)
        n_c = (r0 + bq + kc - 1) // kc
        qib, wib = _rows(qi, r0, bq, 1), _rows(wi, r0, bq, 0)
        valid = (row < S)[:, None]
        add = lambda t, u, c, ax: lax.dynamic_update_slice_in_dim(
            t, _rows(t, c * kc, kc, ax) + u, c * kc, ax)

        def attend(c, on, main):
            """A chunk's part of dq, dk, dv, and its probabilities."""
            dq, dk, dv = main
            k_c, v_c = _rows(k, c * kc, kc, 1), _rows(v, c * kc, kc, 1)
            s = _head_scores(qb, k_c, scale)
            p = jnp.where(on, jnp.exp(s - lse_b[..., None]), 0.0)
            pl = p.astype(q.dtype)
            dv_c = jnp.einsum("grqk,grqd->gkd", pl, dob,
                              preferred_element_type=f32)
            dp = jnp.einsum("grqd,gkd->grqk", dob, v_c,
                            preferred_element_type=f32)
            ds = (p * (dp - delta_b[..., None]) * scale).astype(q.dtype)
            dq = dq + jnp.einsum("grqk,gkd->grqd", ds, k_c,
                                 preferred_element_type=f32)
            dk_c = jnp.einsum("grqk,grqd->gkd", ds, qb,
                              preferred_element_type=f32)
            return (dq, add(dk, dk_c, c, 1), add(dv, dv_c, c, 1)), p

        def chunk(c, acc):
            main, (dqi, dwi, dki) = acc
            on = _unpack(_rows(bits_b, c * (kc // 8), kc // 8, 1))
            with jax.named_scope("dsa.attention"):
                main, p = attend(c, on, main)
            with jax.named_scope("dsa.index_loss"):
                ki_c = _rows(ki, c * kc, kc, 0)
                z, ic = _scorer_chunk(qib, ki_c, wib)
                pt = jnp.sum(p, axis=(0, 1)) * (1.0 / Hq)
                di = jnp.where(on & valid,
                               dl * (jnp.exp(ic - lse_ib[:, None]) - pt), 0.0)
                dwi = dwi + jnp.sum(di[None] * jax.nn.relu(z), axis=-1).T
                dz = jnp.where(z > 0, di[None] * wib.T[:, :, None], 0.0)
                dqi = dqi + jnp.einsum("hqk,kd->hqd", dz, ki_c,
                                       precision=_HI,
                                       preferred_element_type=f32)
                dki_c = jnp.einsum("hqk,hqd->kd", dz, qib, precision=_HI,
                                   preferred_element_type=f32)
            return main, (dqi, dwi, add(dki, dki_c, c, 0))

        (dq, dk, dv), (dqi, dwi, dki) = lax.fori_loop(
            0, n_c, chunk, ((jnp.zeros((Hk, R, bq, D), f32), dk, dv),
                            (jnp.zeros((Hi, bq, Di), f32),
                             jnp.zeros((bq, Hi), f32), dki)))
        return (dk, dv, dki), (dq.astype(q.dtype), dqi, dwi)

    xs = (jnp.arange(nq), blocks(q), blocks(do), delta,
          lse, lse_i, bits)
    (dk, dv, dki), (dq, dqi, dwi) = lax.scan(
        block_in_vmem if impl else block,
        (jnp.zeros((Hk, Sp, D), f32), jnp.zeros((Hk, Sp, D), f32),
         jnp.zeros((Di, Sp) if impl else (Sp, Di), f32)), xs)
    if impl:
        dki = dl * dki.T
    dq = dq.transpose(1, 2, 0, 3, 4).reshape(Hq, Sp, D)
    dqi = dqi.transpose(1, 0, 2, 3).reshape(Hi, Sp, Di)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), dqi, dki,
            dwi.reshape(Sp, Hi))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2))
def _attend(front, operands, static):
    return _attend_fwd(front, operands, static)[0]


def _attend_fwd(front, operands, static):
    out, (lse, lse_i, bits) = jax.vmap(
        lambda *made: _core_fwd(*made, *static))(*front(*operands))
    return out, (operands, out[0], lse, lse_i, bits)


def _attend_bwd(front, static, res, grads):
    S, _, bq, kc, tile, impl = static
    operands, o, lse, lse_i, bits = res
    do, dl, _ = grads
    made, pull = jax.vjp(front, *operands)
    d = jax.vmap(lambda *a: _core_bwd(*a, S, bq, kc, tile, impl))(
        *made, o, lse, lse_i, bits, do, dl)
    return (pull(d),)


_attend.defvjp(_attend_fwd, _attend_bwd)


def _cores_impl(q, k, qi, bq, tile, S_pad):
    """How the S x S work (the heads' cores, the index scorer and the
    choice between them) runs when not told: the Pallas kernels
    (``"compiled"``) in a one-device TPU program whose shapes all of
    them take (``supported`` of ``pallas/sparse_attention.py``, of
    ``pallas/index_scorer.py`` and of ``pallas/topk_choice.py``, whose
    scores are the scorer's: its queries' dtype), else the XLA loops
    (False; counted in ``pallas_fallbacks{reason}``).  One decision and
    no knob: a test passes ``impl``."""
    from ..pallas.dispatch import _compiles_here, choose_impl
    here, why, reason = _compiles_here()
    fits, shapes = kernels.supported(q, k, bq, tile, S_pad)
    fits_i, shapes_i = scorer.supported(qi, bq, tile, S_pad)
    fits_c, shapes_c = topk_choice.supported(qi.dtype, bq, tile, S_pad)
    return choose_impl(
        "sparse_indexed_attention (no knob)", "auto", "sparse_attention",
        here and fits and fits_i and fits_c,
        why="%s, %s %s %s" % (why or "one TPU device", shapes, shapes_i,
                              shapes_c),
        fallback_reason=reason or "sparse-attention-geometry")


def sparse_indexed_attention(front, operands, *, topk, q_chunk=512,
                             kv_chunk=512, impl=None):
    """``front(*operands)`` makes a batch's ``(q, k, v, qi, ki, wi)``
    (module docstring, a leading batch axis on each).  Returns ``(o (B,
    Hq, S, D) in q's dtype, L (B,) float32, live int32 (B, 2))``,
    ``live`` the ``q_chunk`` x ``kv_chunk`` score tiles with a chosen
    pair and the tiles on or under the diagonal.  What ``front`` makes
    is made again in the backward pass and not kept: between the passes
    a layer holds ``operands``, ``o``, the rows' statistics and the
    choice as bits."""
    q, k, _, qi = jax.eval_shape(front, *operands)[:4]
    S = q.shape[2]
    bq, tile, kc, Sp = plan(S, q_chunk, kv_chunk)
    if impl is None:
        impl = _cores_impl(q, k, qi, bq, tile, Sp)

    def padded(*operands):
        q, k, v, qi, ki, wi = front(*operands)
        pad = lambda x, axis: jnp.pad(x, [
            (0, Sp - S) if a == axis else (0, 0) for a in range(x.ndim)])
        return (pad(q, 2), pad(k, 2), pad(v, 2), pad(qi, 2), pad(ki, 1),
                pad(wi, 1))

    o, kl, live = _attend(padded, tuple(operands),
                          (S, int(topk), bq, kc, tile, impl))
    # tiles on or under the diagonal that hold a real row, by block
    under = sum(-(-min((i + 1) * bq, S) // tile)
                for i in range(Sp // bq) if i * bq < S)
    return o[:, :, :S], kl, jnp.stack(
        [live, jnp.full_like(live, under)], axis=-1)
