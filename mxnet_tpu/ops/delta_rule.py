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
"""
import jax
import jax.numpy as jnp
from jax import lax

_BASE = 16      # rows solved by substitution; larger triangles by halves


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
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, preferred_element_type=f32)
    gram = mm("bhnid,bhnjd->bhnij", k, k)[:, :, None]
    t = unit_lower_inverse(jnp.tril(beta[..., None] * gram * decay, -1))
    into = beta * jnp.exp(gc)           # a key's weight seen from the
    w = mm("bhrnij,bhrnjd->bhrnid", t,  # chunk's incoming state
           k[:, :, None] * into[..., None]).astype(low)
    u = mm("bhrnij,bhrnjv->bhrniv", t, v * beta[..., None]).astype(low)
    attn = (mm("bhnid,bhnjd->bhnij", q, k)[:, :, None] * decay).astype(low)
    q_in = (q[:, :, None] * jnp.exp(gc)[..., None]).astype(low)
    # a key's weight in the state the chunk hands on, and the state's own
    k_out = (k[:, :, None] * jnp.exp(gc[..., -1:] - gc)[..., None]).astype(low)
    keep = jnp.exp(gc[..., -1])

    def hand_over(state, xs):
        w_c, u_c, k_c, keep_c = xs
        new = u_c - mm("bhrcd,bhrdv->bhrcv", w_c, state)
        out = state * keep_c[..., None, None] \
            + mm("bhrcd,bhrcv->bhrdv", k_c, new.astype(low))
        return out, (state.astype(low), new.astype(low))

    first = lambda a: jnp.moveaxis(a, 3, 0)
    _, (states, new) = lax.scan(
        hand_over, jnp.zeros((B, Hk, R, Dk, Dv), f32),
        (first(w), first(u), first(k_out), first(keep)))
    o = mm("bhrncd,nbhrdv->bhrncv", q_in, states) \
        + mm("bhrnij,nbhrjv->bhrniv", attn, new)
    o = o.astype(low).reshape(B, Hv, n * C, Dv)
    return o[:, :, :S] if pad else o


def _delta_rule_impl(q, k, v):
    """How :func:`gated_delta_rule` runs when not told: the Pallas
    kernels (``"compiled"``) in a one-device TPU program whose head
    widths fill whole lane tiles, else the ``jax.numpy`` chunks (False;
    the fallback is counted in ``pallas_fallbacks{reason}``).  No knob:
    a test passes ``impl``."""
    from ..pallas.delta_rule import supported
    from ..pallas.dispatch import _compiles_here, choose_impl
    here, why, reason = _compiles_here()
    fits, shapes = supported(q, k, v)
    return choose_impl(
        "gated_delta_rule (no knob)", "auto", "gated_delta_rule",
        here and fits, why="%s, %s" % (why or "one TPU device", shapes),
        fallback_reason=reason or "delta-rule-geometry")


def gated_delta_rule(q, k, v, g, beta, impl=None):
    """:func:`chunk_gated_delta_rule` at chunks of 64, rematerialized in
    the backward pass.  ``impl``: None chooses
    (:func:`_delta_rule_impl`); ``"compiled"`` / ``"interpret"`` is the
    Pallas kernels under scope ``pallas.gated_delta_rule``, forward and
    backward; False is the ``jax.numpy`` path under ``jax.checkpoint``."""
    if impl is None:
        impl = _delta_rule_impl(q, k, v)
    if not impl:
        return jax.checkpoint(chunk_gated_delta_rule)(q, k, v, g, beta)
    from ..pallas.delta_rule import gated_delta_rule as kernels
    return kernels(q, k, v, g, beta, interpret=impl == "interpret")
