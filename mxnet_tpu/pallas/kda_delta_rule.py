"""The delta rule gated per key channel (Kimi Delta Attention,
arXiv:2510.26692) as Pallas kernels (docs/KERNELS.md).

Same result as ``ops/delta_rule.py`` ``chunk_kda_delta_rule``: the
state's ROWS decay each at its own rate, ``S = Diag(exp(g_t)) S``, so a
chunk's pair (i, j) needs ``sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])``
(G the running sum of g inside the chunk) with the decay INSIDE the
contraction, where the scalar gate of ``pallas/delta_rule.py`` factors
it out as one 64 x 64 array.  What follows the triangle is that file's:
its solve (:func:`~.delta_rule._solve`) and its hand-over
(:func:`~.delta_rule._hand_over`), with what a chunk keeps of the state
a (Dk, 1) column's worth a row where the scalar gate has one number.
Every head has its own keys (32 : 32 at the cell's sizes), so nothing
is packed side by side in the lanes: a 64 x 64 triangle fills half a
register's.

How the reference rows keep every exponent <= 0 with nothing divided
by a decay (:func:`_decayed_products`): the four 16-row blocks on a
chunk's diagonal take each pair's own ``G_i - G_j``, masked to j <= i
BEFORE the exponential, a column of all blocks of the run at once
(elementwise, a lane reduction a column); the blocks (1, 0) and (3, 2)
below them are one MXU product of ``[k; q] E`` with ``k E``, ``E =
exp(-|G - G_ref|)`` and ``G_ref`` row 16 for rows 0-31, row 48 for rows
32-63 (a later row's ``G_i - G_ref`` and an earlier row's ``G_ref -
G_j`` are both <= 0 since G only falls), and the 32 x 32 block below
those a second product with ``G_ref`` row 32.  The backward takes the
same three routes back: ``d/dk_i``, ``d/dk_j`` of a product are products
of the masked cotangent with the same decayed operands, and a pair's
gradient to ``G_i`` is its ``k_i`` (or ``q_i``) times its gradient to
that operand, to ``G_j`` minus the same on the other side.

Two kernels.  The forward writes o and the float32 state every run of
chunks starts from; the backward walks the runs from the last to the
first with dS in the scratch, computes a run forward again from its
start, and emits dq, dk, dv, dbeta and dg (S, Dk).  g's running sum and
its transpose back are products with a triangle of ones inside the
kernels (float32 at ``HIGHEST``): HBM sees g and dg once each.

Arithmetic as the ``jax.numpy`` path: state, decays, running sums, the
two decayed products and the solve float32; the other products take
operands of v's dtype and accumulate in float32.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.delta_rule import RUN_STARTS
from .attention import _count_launch
from .delta_rule import (_BASE, _C, _F32, _NN, _NT, _RUN, _TN, _VMEM, _Packed,
                         _dot, _hand_over, _merge_masks, _precision, _solve,
                         _spread_matrix)

_HI = lax.Precision.HIGHEST


def supported(q, k, v):
    """Whether the kernels take these operands: one value head a key
    head, key and value heads one lane tile wide, bfloat16 or float32.
    Returns ``(ok, why)``."""
    Dk, Dv = k.shape[3], v.shape[3]
    ok = (k.shape[1] == v.shape[1] and Dk == 128 and Dv == 128
          and v.dtype in (jnp.bfloat16, jnp.float32) and q.dtype == v.dtype
          and k.dtype == v.dtype)
    return ok, ("Dk=%d Dv=%d heads=%d/%d dtype=%s; need Dk = Dv = 128, one "
                "value head a key head, bf16/f32"
                % (Dk, Dv, v.shape[1], k.shape[1], v.dtype))


class _Blocks:
    """A run's rows as 16-row blocks (blocks, 16, lanes): the masks the
    columns of the diagonal blocks are taken by."""

    def __init__(self, rows, Dk):
        nb = rows // _BASE
        self.wide, self.narrow = (nb, _BASE, Dk), (nb, _BASE, _C)
        self.row = lax.broadcasted_iota(jnp.int32, self.wide, 1)
        self.col = lax.broadcasted_iota(jnp.int32, self.narrow, 2) \
            & (_BASE - 1)

    def own_decay(self, G3, c):
        """``exp(G_i - G_c)`` of every block's rows i >= c against its
        row c, 0 above: masked before the exponential."""
        return jnp.exp(jnp.where(self.row >= c, G3 - G3[:, c:c + 1, :],
                                 -jnp.inf))


def _references(G):
    """``exp(-|G - G_ref|)`` (64, Dk) twice: ``G_ref`` row 16 for rows
    0-31 and row 48 for rows 32-63 (the blocks (1, 0) and (3, 2)), and
    row 32 for all (the 32-row block below)."""
    row = lax.broadcasted_iota(jnp.int32, G.shape, 0)
    at = lambda r: jnp.broadcast_to(G[r:r + 1], G.shape)
    ref = jnp.where(row < 2 * _BASE, at(_BASE), at(3 * _BASE))
    ea = jnp.exp(jnp.where(((row >> 4) & 1) == 1, G - ref, ref - G))
    ref = at(2 * _BASE)
    eb = jnp.exp(jnp.where(row >= 2 * _BASE, G - ref, ref - G))
    return ea, eb


def _decayed_products(m, bl, masks, qf, kf, G, chunks):
    """``Ak`` (j < i) and ``Aq`` (j <= i) of every chunk of the run
    (header), and the decayed operands the backward multiplies again.
    ``qf``, ``kf``, ``G`` (rows, Dk) float32."""
    G3, q3, k3 = (t.reshape(bl.wide) for t in (G, qf, kf))
    akd = jnp.zeros(bl.narrow, _F32)
    aqd = jnp.zeros(bl.narrow, _F32)
    for c in range(_BASE):
        t = bl.own_decay(G3, c) * k3[:, c:c + 1, :]
        at = bl.col == c
        akd = jnp.where(at, jnp.broadcast_to(
            jnp.sum(k3 * t, 2, keepdims=True), bl.narrow), akd)
        aqd = jnp.where(at, jnp.broadcast_to(
            jnp.sum(q3 * t, 2, keepdims=True), bl.narrow), aqd)
    akd, aqd = akd.reshape(-1, _C), aqd.reshape(-1, _C)
    own = (m.row >> 4) == (m.col >> 4)
    out = []
    for c in range(chunks):
        tok = slice(c * _C, (c + 1) * _C)
        ak = jnp.where(own & m.strict, akd[tok], 0.0)
        aq = jnp.where(own & m.lower, aqd[tok], 0.0)
        es = _references(G[tok])
        ops = []
        for e, mask in zip(es, masks):
            kp, qp = kf[tok] * e, qf[tok] * e
            both = _dot(jnp.concatenate([kp, qp], 0), kp, _NT, _HI)
            ak = ak + jnp.where(mask, both[:_C], 0.0)
            aq = aq + jnp.where(mask, both[_C:], 0.0)
            ops.append((e, kp, qp))
        out.append((ak, aq, ops))
    return out


def _running_sum(m, g):
    """g's running sum inside a chunk: a product with the lower
    triangle of ones (float32 at ``HIGHEST``)."""
    return _dot(m.lower.astype(_F32), g, _NN, _HI)


def _run_of_chunks(m, low, prec, refs, chunks, spread):
    """Every chunk of the run up to where the state comes in."""
    q_ref, k_ref, v_ref, g_ref, bt_ref = refs
    Dk, Dv = q_ref.shape[3], v_ref.shape[3]
    qf, kf = q_ref[0, 0].astype(_F32), k_ref[0, 0].astype(_F32)
    g = g_ref[0, 0]
    toks = [slice(c * _C, (c + 1) * _C) for c in range(chunks)]
    G = jnp.concatenate([_running_sum(m, g[tok]) for tok in toks], 0)
    bl, masks = _Blocks(chunks * _C, Dk), _merge_masks(m)
    products = _decayed_products(m, bl, masks, qf, kf, G, chunks)
    pre = []
    for c, (ak, aq, ops) in enumerate(products):
        bcol = m.cols(bt_ref[0, 0, c:c + 1, :])[0]
        bcc = m.pack([bcol])
        pre.append(dict(ak=ak, score=aq, ops=ops, bcol=bcol, bcc=bcc,
                        a=jnp.where(m.strict, bcc * ak, 0.0)))
    solved = _solve(m, [x["a"] for x in pre], spread)
    ones = jnp.ones((_C, Dv), _F32)
    out = []
    for tok, x, t in zip(toks, pre, solved):
        Gc, q_c, k_c = G[tok], qf[tok], kf[tok]
        vf = v_ref[0, 0, tok, :].astype(_F32)
        e = jnp.exp(Gc)
        f = jnp.exp(jnp.broadcast_to(Gc[_C - 1:], Gc.shape) - Gc)
        into = x["bcol"] * e
        kb, vb = (k_c * into).astype(low), (vf * x["bcol"]).astype(low)
        tl = t.astype(low)
        kv = jnp.concatenate([kb, vb], 1)
        wu = _dot(tl, kv, _NN, prec)
        out.append(dict(
            x, tok=tok, t=t, tl=tl, kv=kv, e=e, f=f, into=into, qf=q_c,
            kf=k_c, vf=vf, score_low=x["score"].astype(low),
            w=[wu[:, :Dk].astype(low)], u=[wu[:, Dk:].astype(low)],
            q_in=[(q_c * e).astype(low)], k_out=[(k_c * f).astype(low)],
            # what the chunk keeps of the state's row d, in every lane
            # of that row: the gate's column sums from the MXU
            keep=[jnp.exp(_dot(g[tok], ones, _TN, _HI))]))
    return out, (bl, masks, qf, kf, G)


def _forward_kernel(low, prec):
    def kernel(q_ref, k_ref, v_ref, g_ref, bt_ref, spread_ref, o_ref,
               start_ref, state_ref):
        refs = (q_ref, k_ref, v_ref, g_ref, bt_ref)
        chunks = q_ref.shape[2] // _C
        m = _Packed(1)

        @pl.when(pl.program_id(2) == 0)
        def _():
            state_ref[...] = jnp.zeros_like(state_ref)

        start_ref[0, 0, 0] = state_ref[...]
        states = [state_ref[...]]
        run, _ = _run_of_chunks(m, low, prec, refs, chunks, spread_ref[...])
        for x in run:
            read, new, states = _hand_over(low, prec, x, states)
            o_ref[0, 0, x["tok"], :] = (
                _dot(x["q_in"][0], read[0], _NN, prec)
                + _dot(x["score_low"], new[0], _NN, prec)).astype(o_ref.dtype)
        state_ref[...] = states[0]
    return kernel


def _backward_kernel(low, prec):
    def kernel(q_ref, k_ref, v_ref, g_ref, bt_ref, spread_ref, start_ref,
               do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbt_ref, ds_ref):
        refs = (q_ref, k_ref, v_ref, g_ref, bt_ref)
        chunks = q_ref.shape[2] // _C
        Dk, Dv = q_ref.shape[3], v_ref.shape[3]
        m = _Packed(1)
        last_row = lax.broadcasted_iota(jnp.int32, (_C, Dk), 0) == _C - 1
        lanes_sum = lambda t: jnp.sum(t, 1, keepdims=True)
        ones = jnp.ones((_C, Dv), _F32)
        own = (m.row >> 4) == (m.col >> 4)

        @pl.when(pl.program_id(2) == 0)
        def _():
            ds_ref[...] = jnp.zeros_like(ds_ref)

        # the run again, forward from the state it started with
        run, (bl, masks, qf, kf, G) = _run_of_chunks(
            m, low, prec, refs, chunks, spread_ref[...])
        states, kept = [start_ref[0, 0, 0]], []
        for x in run:
            read, new, states = _hand_over(low, prec, x, states)
            kept.append((read[0], new[0]))
        ds = ds_ref[...]
        part = [None] * chunks          # dq, dk, dG but for the diagonal
        mk_own, mq_own = [None] * chunks, [None] * chunks
        for c in reversed(range(chunks)):
            x, (read, new) = run[c], kept[c]
            tok, q_c, k_c = x["tok"], x["qf"], x["kf"]
            e, f, into, keep = x["e"], x["f"], x["into"], x["keep"][0]
            do = do_ref[0, 0, tok, :]
            dsl = ds.astype(low)
            dnew = _dot(x["score_low"], do, _TN, prec) \
                + _dot(x["k_out"][0], dsl, _NN, prec)
            dscore = jnp.where(m.lower, _dot(do, new, _NT, prec), 0.0)
            dn = dnew.astype(low)
            dq_in = _dot(do, read, _NT, prec)
            dk_out = _dot(new, dsl, _NT, prec)
            # the kept share's gradient, a row: row d of dS * S summed
            dkeep = _dot(ones, ds * read.astype(_F32) * keep, _NT, _HI)
            ds = ds * keep + _dot(x["q_in"][0], do, _TN, prec) \
                - _dot(x["w"][0], dn, _TN, prec)
            dwu = jnp.concatenate([-_dot(dn, read, _NT, prec), dnew],
                                  1).astype(low)
            dt = _dot(dwu, x["kv"], _NT, prec)
            dkv = _dot(x["tl"], dwu, _TN, prec)
            dkb, dvb = dkv[:, :Dk], dkv[:, Dk:]
            dv_ref[0, 0, tok, :] = (dvb * x["bcol"]).astype(dv_ref.dtype)
            # dA = -T^T dT T^T, below the diagonal
            t = x["t"]
            da = -jnp.where(m.strict, _dot(t, _dot(dt, t, _NT, _HI), _TN,
                                           _HI), 0.0)
            dbt = lanes_sum(dkb * k_c * e) + lanes_sum(dvb * x["vf"]) \
                + lanes_sum(da * x["ak"])
            dbt_ref[0, 0, c:c + 1, :] = m.row_of([dbt])
            dak = da * x["bcc"]
            tail = dk_out * k_c * f
            dG = dkb * k_c * into + dq_in * q_c * e - tail + jnp.where(
                last_row, jnp.sum(tail, 0, keepdims=True) + dkeep, 0.0)
            dq, dk = dq_in * e, dkb * into + dk_out * f
            # the blocks below the diagonal blocks, by their references
            xk = xq = y = 0.0
            for (E, kp, qp), mask in zip(x["ops"], masks):
                mkq = jnp.concatenate([jnp.where(mask, dak, 0.0),
                                       jnp.where(mask, dscore, 0.0)],
                                      0).astype(low)
                rows = _dot(mkq, kp.astype(low), _NN, prec)
                xk = xk + E * rows[:_C]
                xq = xq + E * rows[_C:]
                y = y + E * _dot(mkq, jnp.concatenate([kp, qp], 0)
                                 .astype(low), _TN, prec)
            part[c] = (dq + xq, dk + xk + y,
                       dG + k_c * xk + q_c * xq - k_c * y)
            mk_own[c] = jnp.where(own, dak, 0.0)
            mq_own[c] = jnp.where(own, dscore, 0.0)
        ds_ref[...] = ds
        # the diagonal blocks of the whole run, a column at a time
        G3, q3, k3 = (t.reshape(bl.wide) for t in (G, qf, kf))
        mk3 = jnp.concatenate(mk_own, 0).reshape(bl.narrow)
        mq3 = jnp.concatenate(mq_own, 0).reshape(bl.narrow)
        xk3 = jnp.zeros(bl.wide, _F32)
        xq3 = jnp.zeros(bl.wide, _F32)
        y3 = jnp.zeros(bl.wide, _F32)
        for c in range(_BASE):
            decay = bl.own_decay(G3, c)
            t = decay * k3[:, c:c + 1, :]
            at = bl.col == c
            col = lambda z: jnp.broadcast_to(jnp.sum(
                jnp.where(at, z, 0.0), 2, keepdims=True), bl.wide)
            mk, mq = col(mk3), col(mq3)
            xk3, xq3 = xk3 + mk * t, xq3 + mq * t
            y3 = jnp.where(bl.row == c, jnp.broadcast_to(jnp.sum(
                (mk * k3 + mq * q3) * decay, 1, keepdims=True), bl.wide), y3)
        xk, xq, y = (t.reshape(-1, Dk) for t in (xk3, xq3, y3))
        ones_below = m.lower.astype(_F32)
        for c in range(chunks):
            tok = run[c]["tok"]
            dq, dk, dG = part[c]
            k_c, q_c = kf[tok], qf[tok]
            dq_ref[0, 0, tok, :] = (dq + xq[tok]).astype(dq_ref.dtype)
            dk_ref[0, 0, tok, :] = (dk + xk[tok] + y[tok]).astype(
                dk_ref.dtype)
            dG = dG + k_c * xk[tok] + q_c * xq[tok] - k_c * y[tok]
            # g's running sum reaches every later token of its chunk
            dg_ref[0, 0, tok, :] = _dot(ones_below, dG, _TN, _HI)
    return kernel


def _layout(q, k, v, g, beta):
    """Pad the sequence to whole runs of chunks with tokens that leave
    the state as it is (k = v = 0, beta = 0, g = 0), and lay beta out
    (B, H, chunks, 64): a chunk's values a row."""
    B, H, S, _ = k.shape
    n = -(-S // _C)
    run = n if n <= _RUN else _RUN
    n = -(-n // run) * run
    pad = n * _C - S
    if pad:
        at = lambda t: jnp.pad(t, [(0, 0), (0, 0), (0, pad)]
                               + [(0, 0)] * (t.ndim - 3))
        q, k, v, g, beta = at(q), at(k), at(v), at(g), at(beta)
    return (q, k, v, g.astype(_F32),
            beta.astype(_F32).reshape(B, H, n, _C)), run


def _specs(args, run):
    q, _, v, _, _ = args
    B, H, S, Dk = q.shape
    Dv, T = v.shape[3], run * _C
    return dict(
        spread=_spread_matrix(_C),
        spread_spec=pl.BlockSpec((_C, (_BASE - 1) * _C),
                                 lambda b, h, s: (0, 0)),
        grid=(B, H, S // T),
        qk=lambda ix: pl.BlockSpec((1, 1, T, Dk), ix),
        v=lambda ix: pl.BlockSpec((1, 1, T, Dv), ix),
        rows=lambda ix: pl.BlockSpec((1, 1, run, _C), ix),
        starts=lambda ix: pl.BlockSpec((1, 1, 1, Dk, Dv), ix),
        scratch=[pltpu.VMEM((Dk, Dv), _F32)],
        starts_shape=jax.ShapeDtypeStruct((B, H, S // T, Dk, Dv), _F32),
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM))


# Jitted on their own, as the scalar pair's: a model's layers of one
# geometry share one trace and one lowering of the unrolled run.
@functools.partial(jax.jit, static_argnums=(1, 2))
def _run_forward(args, run, interpret):
    """o, and the float32 state every run of chunks starts from."""
    z = _specs(args, run)
    v = args[2]
    fwd4 = lambda b, h, s: (b, h, s, 0)
    fwd5 = lambda b, h, s: (b, h, s, 0, 0)
    _count_launch("kda_delta_rule")
    return pl.pallas_call(
        _forward_kernel(v.dtype, _precision(v.dtype)),
        grid=z["grid"],
        in_specs=[z["qk"](fwd4), z["qk"](fwd4), z["v"](fwd4),
                  z["qk"](fwd4), z["rows"](fwd4), z["spread_spec"]],
        out_specs=[z["v"](fwd4), z["starts"](fwd5)],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   z["starts_shape"]],
        scratch_shapes=z["scratch"], compiler_params=z["params"],
        name="kda_delta_rule_forward",
        interpret=interpret)(*args, z["spread"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _run_backward(args, starts, do, run, interpret):
    z = _specs(args, run)
    q, _, v, g, bt = args
    last = z["grid"][2] - 1
    rev4 = lambda b, h, s: (b, h, last - s, 0)
    rev5 = lambda b, h, s: (b, h, last - s, 0, 0)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    _count_launch("kda_delta_rule_bwd")
    return pl.pallas_call(
        _backward_kernel(v.dtype, _precision(v.dtype)),
        grid=z["grid"],
        in_specs=[z["qk"](rev4), z["qk"](rev4), z["v"](rev4),
                  z["qk"](rev4), z["rows"](rev4), z["spread_spec"],
                  z["starts"](rev5), z["v"](rev4)],
        out_specs=[z["qk"](rev4), z["qk"](rev4), z["v"](rev4),
                   z["qk"](rev4), z["rows"](rev4)],
        out_shape=[like(q), like(q), like(v), like(g), like(bt)],
        scratch_shapes=z["scratch"], compiler_params=z["params"],
        name="kda_delta_rule_backward",
        interpret=interpret)(*args, z["spread"], starts, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    return _rule_fwd(q, k, v, g, beta, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    args, run = _layout(q, k, v, g, beta)
    with jax.named_scope("pallas.kda_delta_rule"):
        o, starts = _run_forward(args, run, interpret)
    starts = checkpoint_name(starts, RUN_STARTS)
    return o[:, :, :k.shape[2]], (q, k, v, g, beta, starts)


def _rule_bwd(interpret, res, do):
    q, k, v, g, beta, starts = res
    S = k.shape[2]
    args, run = _layout(q, k, v, g, beta)
    pad = args[0].shape[2] - S
    if pad:
        do = jnp.pad(do, [(0, 0), (0, 0), (0, pad), (0, 0)])
    with jax.named_scope("pallas.kda_delta_rule"):
        dq, dk, dv, dg, dbeta = _run_backward(args, starts, do, run,
                                              interpret)
    dbeta = dbeta.reshape(dbeta.shape[:2] + (-1,))
    return (dq[:, :, :S], dk[:, :, :S], dv[:, :, :S],
            dg[:, :, :S].astype(g.dtype), dbeta[:, :, :S].astype(beta.dtype))


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_delta_rule(q, k, v, g, beta, interpret=False):
    """``chunk_kda_delta_rule(q, k, v, g, beta)`` (chunks of 64) as
    Pallas kernels, forward and backward; operands as there, and
    :func:`supported` says which."""
    ok, why = supported(q, k, v)
    if not ok:
        raise ValueError("pallas kda delta rule: " + why)
    return _rule(q, k, v, g, beta, bool(interpret))
