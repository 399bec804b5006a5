"""The gated delta rule in chunks (Gated DeltaNet, arXiv:2412.06464).

Per head, with a state ``S`` (Dk x Dv, float32, zero before the first
token), token t does

    S = exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S = S + k_t u_t^T;
    o_t = S^T q_t

:func:`chunk_gated_delta_rule` computes it a chunk of C tokens at a time
(the WY form, as the source's ``chunk_gated_delta_rule``): inside a
chunk the C updates are one unit-lower-triangular system, solved for all
chunks at once (:func:`unit_lower_inverse`); only the state's hand-over
from chunk to chunk is sequential (``lax.scan`` over S / C steps of
three small products), and every output follows from its chunk's
incoming state in one batched product.  Plain ``jax.numpy``: the
backward pass is jax's own (the solve has its closed-form VJP).

:func:`gated_delta_rule` is what the mixer calls: in a one-device TPU
program whose shapes the kernels take it is ``pallas/delta_rule.py``
(the same chunks with a chunk's arrays in VMEM, and a backward pass of
its own that does not run the whole forward again); everywhere else,
under a mesh and on the CPU, it is :func:`chunk_gated_delta_rule` under
``jax.checkpoint`` (its forward runs again in the backward pass).

Every exponent taken is <= 0: ``g`` <= 0, its running sum inside a
chunk only falls, and a difference is exponentiated only where the later
token's sum stands first (masked BEFORE the exponential).  Nothing is
divided by a decay.

Two gates.  ``g`` (B, H, S), one scalar a head and token (Gated
DeltaNet), is the rule above: a chunk's decays factor out of its Gram
matrix as one C x C array.  ``g`` (B, H, S, Dk), one value a KEY CHANNEL
(Kimi Delta Attention, arXiv:2510.26692), decays the state's rows each
at its own rate, ``S = Diag(exp(g_t)) S``, and the decay sits inside the
contraction: pair (i, j) of a chunk needs ``sum_d k_i[d] k_j[d]
exp(G_i[d] - G_j[d])`` (G the running sum).  Written as one product of
``k exp(G - G_ref)`` with ``k exp(G_ref - G)`` over a whole chunk it
would take exponents of both signs, so :func:`channel_decayed_products`
picks its reference rows by halves: the 16-row blocks on the diagonal
take the pair's own exponent, masked before the exponential
(elementwise, no reference); the block below the diagonal of every pair
of 16-row blocks, and then of the two 32-row blocks, is ONE matrix
product whose reference is the later block's first row, where the later
rows' ``G_i - G_ref`` and the earlier rows' ``G_ref - G_j`` are both
<= 0.  :func:`chunk_kda_delta_rule` is that rule; what follows a chunk's
triangle (the solve, ``w`` and ``u``, the hand-over, the outputs) is one
routine for both gates (:func:`_solved_chunks`).  :func:`gated_delta_rule`
picks by the gate's rank.
"""
import jax
import jax.numpy as jnp
from jax import lax

_BASE = 16      # rows solved by substitution; larger triangles by halves
# the name (``jax.ad_checkpoint.checkpoint_name``) of the float32 state every
# run of chunks starts from, as the channel-gated kernels keep it: a mixer
# that rematerializes the scan's inputs saves this and nothing else of it
RUN_STARTS = "delta_rule.run_starts"


# The solve works on triangles laid out (n, n, m), the m = batch x heads
# x chunks systems in the minor (lane) dimension: a 16 x 16 or 64 x 64
# float32 matrix in the two minor dimensions fills an eighth or half of
# a vector register's lanes, and its products a corner of the MXU; with
# the systems side by side every step is dense elementwise work, in
# exact float32.
def _substitute(a):
    """``(I + a)^-1`` for strictly lower ``a`` (b, b, m), row by row:
    row i of the inverse is ``e_i - a[i, :i] @ rows[:i]``."""
    b, _, m = a.shape
    eye = jnp.eye(b, dtype=a.dtype)[:, :, None]
    rows = [jnp.broadcast_to(eye[0], (b, m))]
    for i in range(1, b):
        done = jnp.stack(rows)                              # (i, b, m)
        rows.append(eye[i] - jnp.sum(a[i, :i, None] * done, axis=0))
    return jnp.stack(rows)


def _mm(x, y):
    """(i, j, m) x (j, k, m) -> (i, k, m): m small products side by
    side, a multiply and a sum (no MXU: see above)."""
    return jnp.sum(x[:, :, None] * y[None], axis=1)


def _inverse(a):
    """``(I + a)^-1`` of (n, n, m) by halves: with the diagonal blocks'
    inverses ``T11``, ``T22`` the block below is ``-T22 a21 T11``.  Both
    halves go down together (side by side in m)."""
    n, _, m = a.shape
    if n <= _BASE or n % 2:
        return _substitute(a)
    h = n // 2
    both = _inverse(jnp.concatenate([a[:h, :h], a[h:, h:]], axis=-1))
    t11, t22 = both[..., :m], both[..., m:]
    t21 = -_mm(_mm(t22, a[h:, :h]), t11)
    return jnp.concatenate([
        jnp.concatenate([t11, jnp.zeros_like(t11)], axis=1),
        jnp.concatenate([t21, t22], axis=1)], axis=0)


def _systems_last(a):
    return jnp.moveaxis(a.reshape((-1,) + a.shape[-2:]), 0, -1)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` (..., n, n)
    in float32: forward substitution on 16-row diagonal blocks, then
    block merges (stable where keys repeat and ``a`` is far from small;
    a Neumann product is not).  What lies on or above ``a``'s diagonal
    is taken as 0 and gets no gradient."""
    return jnp.moveaxis(_inverse(_systems_last(a)), -1, 0).reshape(a.shape)


def _inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, g):
    # dT = -T dA T, so <g, dT> = <-T^T g T^T, dA>
    tt = jnp.swapaxes(_systems_last(t), 0, 1)
    da = -_mm(_mm(tt, _systems_last(g)), tt)
    return (jnp.tril(jnp.moveaxis(da, -1, 0).reshape(t.shape), -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk=64):
    """The gated delta rule over head-major sequences.  ``q``, ``k``
    (B, Hk, S, Dk): q normalised and scaled, k normalised; ``v`` (B, Hv,
    S, Dv), Hv a multiple of Hk (value head h reads key head h // (Hv /
    Hk)); ``g`` <= 0 and ``beta`` (B, Hv, S) float32.  Returns ``o``
    (B, Hv, S, Dv) in ``v``'s dtype.

    A sequence that is no whole number of chunks is padded at its end
    with tokens that leave the state as it is (k = v = 0, beta = 0,
    g = 0); causality keeps them out of every real output.

    The state, the decays and the triangular solve are float32; the
    products take operands of ``v``'s dtype (the state is rounded for a
    product, as the source's kernels do) and accumulate in float32."""
    B, Hk, S, Dk = k.shape
    Hv, Dv = v.shape[1], v.shape[3]
    if Hv % Hk:
        raise ValueError("value heads %d not a multiple of key heads %d"
                         % (Hv, Hk))
    R, C = Hv // Hk, int(chunk)
    pad = -S % C
    if pad:
        at = lambda t, axis: jnp.pad(
            t, [(0, pad if i == axis else 0) for i in range(t.ndim)])
        q, k, v = at(q, 2), at(k, 2), at(v, 2)
        g, beta = at(g, 2), at(beta, 2)
    n = (S + pad) // C
    f32, low = jnp.float32, v.dtype
    q = q.reshape(B, Hk, n, C, Dk)
    k = k.reshape(B, Hk, n, C, Dk)
    v = v.reshape(B, Hk, R, n, C, Dv)
    beta = beta.astype(f32).reshape(B, Hk, R, n, C)
    gc = jnp.cumsum(g.astype(f32).reshape(B, Hk, R, n, C), axis=-1)

    lower = jnp.tril(jnp.ones((C, C), bool))
    # decay from token j to the later token i of a chunk, 0 above the
    # diagonal: masked before the exponential, whose argument is <= 0
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    gram = _ein("bhnid,bhnjd->bhnij", k, k)[:, :, None]
    e = jnp.exp(gc)
    o = _solved_chunks(
        jnp.tril(beta[..., None] * gram * decay, -1),
        # a key's weight seen from the chunk's incoming state
        k[:, :, None] * (beta * e)[..., None], v * beta[..., None],
        (_ein("bhnid,bhnjd->bhnij", q, k)[:, :, None] * decay).astype(low),
        (q[:, :, None] * e[..., None]).astype(low),
        # a key's weight in the state the chunk hands on, and the state's own
        (k[:, :, None] * jnp.exp(gc[..., -1:] - gc)[..., None]).astype(low),
        jnp.exp(gc[..., -1])[..., None, None], low)
    o = o.reshape(B, Hv, n * C, Dv)
    return o[:, :, :S] if pad else o


def _ein(eq, a, b, **kw):
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32, **kw)


def _solved_chunks(a, kb, vb, attn, q_in, k_out, keep, low):
    """What both gates share once a chunk's triangle ``a`` (B, Hk, R, n,
    C, C; strictly lower, float32) stands: the solve, ``w`` and ``u``
    from the keys ``kb`` and values ``vb`` as the incoming state sees
    them (both times beta), the hand-over from chunk to chunk, and the
    outputs from the scores ``attn``, the decayed queries ``q_in`` and
    the keys ``k_out`` as the outgoing state takes them.  ``keep`` is
    what a chunk leaves of the state it found, broadcast against (Dk,
    Dv): (..., 1, 1) for a scalar gate, (..., Dk, 1) for a gate a key
    channel (the state's rows).  Returns o (B, Hk, R, n, C, Dv)."""
    f32 = jnp.float32
    t = unit_lower_inverse(a)
    w = _ein("bhrnij,bhrnjd->bhrnid", t, kb).astype(low)
    u = _ein("bhrnij,bhrnjv->bhrniv", t, vb).astype(low)

    def hand_over(state, xs):
        w_c, u_c, k_c, keep_c = xs
        new = u_c - _ein("bhrcd,bhrdv->bhrcv", w_c, state)
        out = state * keep_c + _ein("bhrcd,bhrcv->bhrdv", k_c, new.astype(low))
        return out, (state.astype(low), new.astype(low))

    first = lambda x: jnp.moveaxis(x, 3, 0)
    B, Hk, R = a.shape[:3]
    _, (states, new) = lax.scan(
        hand_over, jnp.zeros((B, Hk, R, kb.shape[-1], vb.shape[-1]), f32),
        (first(w), first(u), first(k_out), first(keep)))
    o = _ein("bhrncd,nbhrdv->bhrncv", q_in, states) \
        + _ein("bhrnij,nbhrjv->bhrniv", attn, new)
    return o.astype(low)


def channel_decayed_products(q, k, G):
    """``Aq[i, j] = sum_d q_i[d] k_j[d] exp(G_i[d] - G_j[d])`` for j <= i
    and ``Ak`` (the same with k for q) for j < i, zeros elsewhere, of
    every chunk: ``q``, ``k``, ``G`` (..., C, D) float32, ``G`` the
    running sum of a gate <= 0 inside the chunk, C = 16 * 2^m.  Returns
    (Ak, Aq) (..., C, C) float32.

    No exponent > 0 is taken and nothing is divided by a decay: a
    16-row block on the diagonal takes each pair's own ``G_i - G_j``,
    masked to j <= i before the exponential; the block below the
    diagonal of a pair of s-row blocks (s = 16, 32, ...) is a product of
    ``[q; k] exp(G - G_ref)`` over the later block with ``k exp(G_ref -
    G)`` over the earlier, ``G_ref`` the later block's first row, which
    lies between every such pair."""
    C, D = G.shape[-2:]
    lead = G.shape[:-2]
    hi = lax.Precision.HIGHEST
    if C % _BASE or (C // _BASE) & (C // _BASE - 1):
        raise ValueError("a chunk of %d rows is not 16 * 2^m" % C)
    nb = C // _BASE
    blocks = lambda x: x.reshape(lead + (nb, _BASE, D))
    Gb, qb, kb = blocks(G), blocks(q), blocks(k)
    own = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((_BASE, _BASE), bool))[..., None],
        Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))
    eye = jnp.eye(nb, dtype=G.dtype)
    diag = lambda x: jnp.einsum("...bij,bc->...bicj", jnp.sum(
        x[..., :, None, :] * kb[..., None, :, :] * own, -1), eye) \
        .reshape(lead + (C, C))
    ak, aq = jnp.tril(diag(kb), -1), diag(qb)
    row = jnp.arange(C)
    s = _BASE
    while s < C:
        later = (row // s) % 2 == 1                       # (C,)
        halves = G.reshape(lead + (C // (2 * s), 2, s, D))
        ref = jnp.broadcast_to(halves[..., 1:, :1, :], halves.shape) \
            .reshape(G.shape)
        e = jnp.exp(jnp.where(later[:, None], G - ref, ref - G))
        kp = k * e
        below = later[:, None] & ((row // s)[None, :] == (row // s)[:, None] - 1)
        ak = ak + jnp.where(below, _ein("...id,...jd->...ij", kp, kp,
                                       precision=hi), 0.0)
        aq = aq + jnp.where(below, _ein("...id,...jd->...ij", q * e, kp,
                                       precision=hi), 0.0)
        s *= 2
    return ak, aq


def chunk_kda_delta_rule(q, k, v, g, beta, chunk=64):
    """The delta rule whose gate is a vector over the key channels (Kimi
    Delta Attention), over head-major sequences: ``q``, ``k`` (B, H, S,
    Dk), ``v`` (B, H, S, Dv), every head with its own keys; ``g`` <= 0
    (B, H, S, Dk) and ``beta`` (B, H, S) float32.  Token t does

        S = Diag(exp(g_t)) S;  u_t = beta_t (v_t - S^T k_t);
        S = S + k_t u_t^T;  o_t = S^T q_t

    Returns ``o`` (B, H, S, Dv) in ``v``'s dtype.  Padding, dtypes and
    the chunked form as :func:`chunk_gated_delta_rule`; the two products
    that carry the decay inside their contraction are float32
    (:func:`channel_decayed_products`), since the triangle is solved."""
    B, H, S, Dk = k.shape
    Dv = v.shape[3]
    if v.shape[1] != H or g.shape != k.shape:
        raise ValueError("a gate a key channel takes one value head a key "
                         "head and g of k's shape: k %s v %s g %s"
                         % (k.shape, v.shape, g.shape))
    C = int(chunk)
    pad = -S % C
    if pad:
        at = lambda t: jnp.pad(t, [(0, 0), (0, 0), (0, pad)]
                               + [(0, 0)] * (t.ndim - 3))
        q, k, v, g, beta = at(q), at(k), at(v), at(g), at(beta)
    n = (S + pad) // C
    f32, low = jnp.float32, v.dtype
    chunks = lambda t: t.reshape((B, H, 1, n, C) + t.shape[3:])
    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(f32))
    G = jnp.cumsum(chunks(g.astype(f32)), axis=-2)
    qf, kf = q.astype(f32), k.astype(f32)
    ak, aq = channel_decayed_products(qf, kf, G)
    e = jnp.exp(G)
    o = _solved_chunks(
        beta[..., None] * ak, kf * (beta[..., None] * e),
        v * beta[..., None], aq.astype(low), (qf * e).astype(low),
        (kf * jnp.exp(G[..., -1:, :] - G)).astype(low),
        jnp.exp(G[..., -1, :])[..., None], low)
    o = o.reshape(B, H, n * C, Dv)
    return o[:, :, :S] if pad else o


def _delta_rule_impl(q, k, v, g=None):
    """How :func:`gated_delta_rule` runs when not told: the Pallas
    kernels (``"compiled"``) in a one-device TPU program whose head
    widths fill whole lane tiles, else the ``jax.numpy`` chunks (False;
    the fallback is counted in ``pallas_fallbacks{reason}``).  The
    gate's rank says which pair: ``pallas/delta_rule.py`` for a scalar a
    head, ``pallas/kda_delta_rule.py`` for a value a key channel.  No
    knob: a test passes ``impl``."""
    from ..pallas.dispatch import _compiles_here, choose_impl
    if g is not None and g.ndim == 4:
        from ..pallas.kda_delta_rule import supported
        kernel, refused = "kda_delta_rule", "kda-geometry"
    else:
        from ..pallas.delta_rule import supported
        kernel, refused = "gated_delta_rule", "delta-rule-geometry"
    here, why, reason = _compiles_here()
    fits, shapes = supported(q, k, v)
    return choose_impl(
        kernel + " (no knob)", "auto", kernel,
        here and fits, why="%s, %s" % (why or "one TPU device", shapes),
        fallback_reason=reason or refused)


def gated_delta_rule(q, k, v, g, beta, impl=None):
    """:func:`chunk_gated_delta_rule` (``g`` (B, Hv, S)) or
    :func:`chunk_kda_delta_rule` (``g`` (B, H, S, Dk)) at chunks of 64,
    rematerialized in the backward pass.  ``impl``: None chooses
    (:func:`_delta_rule_impl`); ``"compiled"`` / ``"interpret"`` is the
    Pallas kernels under scope ``pallas.gated_delta_rule`` /
    ``pallas.kda_delta_rule``, forward and backward; False is the
    ``jax.numpy`` path under ``jax.checkpoint``."""
    if impl is None:
        impl = _delta_rule_impl(q, k, v, g)
    channel = g.ndim == 4
    if not impl:
        return jax.checkpoint(chunk_kda_delta_rule if channel
                              else chunk_gated_delta_rule)(q, k, v, g, beta)
    if channel:
        from ..pallas.kda_delta_rule import kda_delta_rule as kernels
    else:
        from ..pallas.delta_rule import gated_delta_rule as kernels
    return kernels(q, k, v, g, beta, interpret=impl == "interpret")
