"""The chunked delta rule under a SCALAR gate a head and token (Gated
DeltaNet) as Pallas kernels (docs/KERNELS.md); a gate a key channel is
``pallas/kda_delta_rule.py``, which takes this file's solve, hand-over
and masks.  Same result as ``ops/delta_rule.py``
``chunk_gated_delta_rule`` (the WY form in chunks of 64 tokens,
arXiv:2412.06464), every array of a chunk in VMEM only: HBM sees one
read of q, k, v, g, beta and one write of o.  A grid step takes a
key head, the value heads that read it and a run of chunks; the
float32 state (Dk x Dv a value head) stays in a VMEM scratch across
the sequential grid dimension over the sequence.

Two kernels, one chunk routine (:func:`_run_of_chunks`, then
:func:`_hand_over` chunk after chunk).  The forward writes o and the
float32 state every run of chunks starts from (34 MB a layer at the
Qwen3-Next cell's sizes).  The backward
walks the runs from the last to the first with dS in the scratch: it
computes a run forward again from its start, keeping its chunks'
arrays in VMEM, then takes the run's chunks from the last to the first
and emits dq, dk, dv and the gradients of beta and of g's running sum
inside a chunk.  So the ``custom_vjp`` keeps the inputs and the run
starts, a triangle is solved once in each direction, and no whole
forward runs again.

Two value heads of one key head sit side by side in the lanes: a
64 x 64 triangle fills half a vector register's lanes, two fill it.
``[X0 | X1]`` (64, 128) times the block-diagonal ``[[Y0, 0], [0, Y1]]``
is ``[X0 Y0 | X1 Y1]``, and a product that contracts the rows of two
packed operands has the per-head results in its diagonal blocks, so no
slice ever starts inside a 128-lane tile.

Arithmetic as the ``jax.numpy`` path: state, decays, running sums and
the solve float32; products take operands of v's dtype and accumulate
in float32; every exponent <= 0 and masked before the exponential;
nothing divided by a decay.  The solve (:func:`_solve`) is forward
substitution on 16-row diagonal blocks and two block merges, float32
products at ``HIGHEST`` precision, for a run's chunks at once.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _count_launch

_C = 64          # tokens a chunk: the jax.numpy path's, the source's
_BASE = 16       # rows solved by substitution
_RUN = 8         # chunks a grid step (512 tokens)
_VMEM = 64 << 20  # a grid step keeps a run's chunk arrays between passes

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
_F32 = jnp.float32


def supported(q, k, v):
    """Whether the scalar-gate kernels take these operands: head widths
    of whole lane tiles, an even number of value heads a key head (or
    one), bfloat16 or float32.  Returns ``(ok, why)``."""
    Hk, Dk = k.shape[1], k.shape[3]
    Hv, Dv = v.shape[1], v.shape[3]
    r = Hv // Hk if Hk and Hv % Hk == 0 else 0
    ok = (Dk % 128 == 0 and Dv % 128 == 0 and (r == 1 or (r and r % 2 == 0))
          and v.dtype in (jnp.bfloat16, jnp.float32) and q.dtype == v.dtype
          and k.dtype == v.dtype)
    return ok, ("Dk=%d Dv=%d heads=%d/%d dtype=%s; need Dk, Dv %% 128 == 0, "
                "1 or an even number of value heads a key head, bf16/f32"
                % (Dk, Dv, Hv, Hk, v.dtype))


class _Packed:
    """Masks and layout moves for ``P`` heads side by side in the lanes
    of a (64, 64 P) array."""

    def __init__(self, P):
        self.P, self.W = P, P * _C
        shape = (_C, self.W)
        self.row = lax.broadcasted_iota(jnp.int32, shape, 0)
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        self.col = lane & (_C - 1)
        self.head = lane >> 6
        self.eye = self.row == self.col
        self.lower = self.row >= self.col
        self.strict = self.row > self.col
        if P > 1:
            sq = (self.W, self.W)
            self.same = (lax.broadcasted_iota(jnp.int32, sq, 0) >> 6) \
                == (lax.broadcasted_iota(jnp.int32, sq, 1) >> 6)

    def of_head(self, h):
        return self.head == h

    def cols(self, row):
        """(1, W) packed row -> a (64, 1) column a head."""
        if self.P == 1:
            return [jnp.sum(jnp.where(self.eye, row, 0.0), 1, keepdims=True)]
        return [jnp.sum(jnp.where(self.eye & self.of_head(h), row, 0.0), 1,
                        keepdims=True) for h in range(self.P)]

    def pack(self, cols):
        """A (64, 1) column a head -> (64, W), each over its lanes."""
        if self.P == 1:
            return jnp.broadcast_to(cols[0], (_C, self.W))
        wide = lambda t: jnp.broadcast_to(t, (_C, self.W))
        return jnp.where(self.of_head(0), wide(cols[0]), wide(cols[1]))

    def row_of(self, cols):
        return jnp.sum(jnp.where(self.eye, self.pack(cols), 0.0), 0,
                       keepdims=True)

    def row_sums(self, x):
        if self.P == 1:
            return [jnp.sum(x, 1, keepdims=True)]
        return [jnp.sum(jnp.where(self.of_head(h), x, 0.0), 1, keepdims=True)
                for h in range(self.P)]

    def stack(self, x):
        """(64, D) -> (W, D): the same rows under every head."""
        return x if self.P == 1 else jnp.concatenate([x] * self.P, 0)

    def fold(self, x):
        """(W, D) -> (64, D): the heads' row blocks summed."""
        return x if self.P == 1 else x[:_C] + x[_C:]

    def block_diag(self, x):
        """[X0 | X1] -> [[X0, 0], [0, X1]] (W, W)."""
        if self.P == 1:
            return x
        return jnp.where(self.same, jnp.concatenate([x, x], 0), 0.0)

    def diag_blocks(self, z):
        """(W, W) -> [Z00 | Z11]."""
        if self.P == 1:
            return z
        return jnp.where(self.of_head(0), z[:_C], z[_C:])

    def wide(self, parts):
        """``parts[h]`` a list of (64, D) -> (W, P sum D): head h's
        arrays in row block h and column block h, zeros elsewhere."""
        if self.P == 1:
            return jnp.concatenate(parts[0], 1)
        zeros = [jnp.zeros_like(a) for a in parts[0]]
        return jnp.concatenate([jnp.concatenate(parts[0] + zeros, 1),
                                jnp.concatenate(zeros + parts[1], 1)], 0)


def _dot(a, b, dims, prec):
    return lax.dot_general(a, b, dims, precision=prec,
                           preferred_element_type=_F32)


def _merge_masks(m):
    """The block below the diagonal of each pair of 16-row diagonal
    blocks, and of the two 32-row blocks."""
    masks, size = [], _BASE
    while size < _C:
        rb, cb = m.row // size, m.col // size
        masks.append(((rb & 1) == 1) & (cb == rb - 1))
        size *= 2
    return masks


def _solve(m, many, spread):
    """``(I + a)^-1`` a head for each packed strictly lower ``a``
    (64, W) of ``many`` (a run's chunks, which do not wait for each
    other here: every step is taken for all of them at once, so one
    chunk's waits are another's work).

    Forward substitution on the 16-row diagonal blocks: block b of a
    head already sits in that head's lanes 16 b .. 16 b + 15, so the
    blocks of every head and chunk lie side by side in ONE (chunks, 16,
    W) array, and a step is one rank-one update for all of them: row j
    of the inverse is final once rows < j have been taken out of it,
    and goes out of the rows below times column j of ``a`` spread over
    its block's 16 lanes.  All the spread columns come from one product
    with the 0 / 1 matrix ``spread`` (:func:`_spread_matrix`), exact in
    three bfloat16 pieces.  Then ``T21 = -T22 a21 T11`` for pairs of
    blocks, twice, as float32 products at ``HIGHEST``."""
    hi = lax.Precision.HIGHEST
    shape = (len(many), _BASE, m.W)
    # (built here: a slice of a mask array does not compile)
    block = (lax.broadcasted_iota(jnp.int32, shape[1:], 1) & (_C - 1)) >> 4
    blocks = range(_C // _BASE)
    ac = jnp.stack([
        sum(jnp.where(block == b, a[b * _BASE:(b + 1) * _BASE], 0.0)
            for b in blocks) for a in many])
    rest, columns = ac.reshape(-1, m.W), 0.0
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        columns = columns + _dot(piece, spread, _NN, lax.Precision.DEFAULT)
        rest = rest - piece.astype(_F32)
    x = ((lax.broadcasted_iota(jnp.int32, shape, 2) & (_BASE - 1))
         == lax.broadcasted_iota(jnp.int32, shape, 1)).astype(_F32)
    for j in range(_BASE - 1):
        x = x - columns[:, j * m.W:(j + 1) * m.W].reshape(shape) \
            * x[:, j:j + 1, :]
    xs = [jnp.concatenate([jnp.where(block == b, x[i], 0.0) for b in blocks],
                          0) for i in range(len(many))]
    for mask in _merge_masks(m):
        left = [_dot(x, m.block_diag(jnp.where(mask, a, 0.0)), _NN, hi)
                for x, a in zip(xs, many)]
        xs = [x - _dot(l, m.block_diag(x), _NN, hi) for x, l in zip(xs, left)]
    return xs


def _before_solve(m, prec, q, k, gcr, btr):
    """What a chunk's P value heads compute up to the triangle ``a``.
    ``q``, ``k`` (64, Dk), ``gcr`` (g's running sum inside the chunk)
    and ``btr`` (beta) packed (1, W)."""
    kk = m.stack(k)
    gram, qk = _dot(k, kk, _NT, prec), _dot(q, kk, _NT, prec)
    gcol, bcol = m.cols(gcr), m.cols(btr)
    gcc, bcc = m.pack(gcol), m.pack(bcol)
    decay = jnp.exp(jnp.where(m.lower, gcc - gcr, -jnp.inf))
    a = jnp.where(m.strict, bcc * gram * decay, 0.0)
    return dict(kk=kk, gram=gram, qk=qk, gcol=gcol, bcol=bcol, bcc=bcc,
                decay=decay, a=a)


def _after_solve(m, low, prec, x, t, q, k, vs):
    """The rest of what a chunk computes before the state comes in,
    from the solved triangle ``t``; ``vs`` a (64, Dv) a head."""
    gcol, bcol = x["gcol"], x["bcol"]
    e = [jnp.exp(c) for c in gcol]
    last = [c[_C - 1:] for c in gcol]
    f = [jnp.exp(l - c) for l, c in zip(last, gcol)]
    qf, kf = q.astype(_F32), k.astype(_F32)
    vf = [v.astype(_F32) for v in vs]
    into = [b * y for b, y in zip(bcol, e)]
    kb = [(kf * s).astype(low) for s in into]
    vb = [(v * b).astype(low) for v, b in zip(vf, bcol)]
    tl = t.astype(low)
    kv = m.wide([[y, z] for y, z in zip(kb, vb)])
    wu = _dot(tl, kv, _NN, prec)
    Dk, Dv = k.shape[1], vs[0].shape[1]
    at = lambda y, h, off, n: y[:, h * (Dk + Dv) + off:h * (Dk + Dv) + off + n]
    heads = range(m.P)
    score = x["qk"] * x["decay"]
    return dict(
        x, t=t, tl=tl, kv=kv, score=score, score_low=score.astype(low),
        e=e, f=f, into=into, keep=[jnp.exp(l) for l in last], qf=qf, kf=kf,
        vf=vf, w=[at(wu, h, 0, Dk).astype(low) for h in heads],
        u=[at(wu, h, Dk, Dv).astype(low) for h in heads],
        q_in=[(qf * y).astype(low) for y in e],
        k_out=[(kf * y).astype(low) for y in f])


def _spread_matrix(W):
    """(W, 15 W) 0 / 1: column block j takes lane 16 s + j of every
    16-lane segment s to all of that segment's lanes."""
    src, dst = np.arange(W)[:, None], np.arange(W)[None, :]
    return jnp.asarray(np.concatenate(
        [(src // _BASE == dst // _BASE) & (src % _BASE == j)
         for j in range(_BASE - 1)], 1), jnp.bfloat16)


def _run_of_chunks(m, low, prec, refs, chunks, p, spread):
    """Every chunk of the run for the ``p``-th group of P value heads,
    up to where the state comes in."""
    args = [_operands(m, refs, c, p) for c in range(chunks)]
    pre = [_before_solve(m, prec, q, k, gcr, btr)
           for _, (q, k, _, gcr, btr) in args]
    solved = _solve(m, [x["a"] for x in pre], spread)
    return [(tok, _after_solve(m, low, prec, x, t, q, k, vs), q, k)
            for (tok, (q, k, vs, _, _)), x, t in zip(args, pre, solved)]


def _geometry(v_ref):
    R, T = v_ref.shape[1], v_ref.shape[2]
    return R, 1 if R == 1 else 2, T // _C


def _hand_over(low, prec, x, states):
    """The chunk's part that waits for the incoming float32 ``states``
    (one a head): what the state reads as (rounded for its products),
    the new values, and the states handed on."""
    read = [s.astype(low) for s in states]
    new = [(u.astype(_F32) - _dot(w, s, _NN, prec)).astype(low)
           for u, w, s in zip(x["u"], x["w"], read)]
    on = [s * keep + _dot(k_out, n, _TN, prec)
          for s, keep, k_out, n in zip(states, x["keep"], x["k_out"], new)]
    return read, new, on


def _heads(m, p):
    return range(p * m.P, (p + 1) * m.P)


def _operands(m, refs, c, p):
    """Chunk ``c``'s operands for the ``p``-th group of P value heads."""
    q_ref, k_ref, v_ref, gc_ref, bt_ref = refs
    tok = slice(c * _C, (c + 1) * _C)
    lanes = slice(p * m.W, (p + 1) * m.W)
    return tok, (q_ref[0, 0, tok, :], k_ref[0, 0, tok, :],
                 [v_ref[0, h, tok, :] for h in _heads(m, p)],
                 gc_ref[0, 0, c:c + 1, lanes], bt_ref[0, 0, c:c + 1, lanes])


def _forward_kernel(low, prec):
    def kernel(q_ref, k_ref, v_ref, gc_ref, bt_ref, spread_ref, o_ref,
               start_ref, state_ref):
        spread = spread_ref[...]
        refs = (q_ref, k_ref, v_ref, gc_ref, bt_ref)
        R, P, chunks = _geometry(v_ref)
        Dv = v_ref.shape[3]
        m = _Packed(P)

        @pl.when(pl.program_id(2) == 0)
        def _():
            state_ref[...] = jnp.zeros_like(state_ref)

        start_ref[0, :, 0] = state_ref[...]
        for p in range(R // P):
            heads = _heads(m, p)
            states = [state_ref[h] for h in heads]
            for tok, x, _, _ in _run_of_chunks(m, low, prec, refs, chunks, p,
                                               spread):
                read, new, states = _hand_over(low, prec, x, states)
                local = _dot(x["score_low"], m.wide([[n] for n in new]),
                             _NN, prec)
                for i, h in enumerate(heads):
                    o_ref[0, h, tok, :] = (
                        _dot(x["q_in"][i], read[i], _NN, prec)
                        + local[:, i * Dv:(i + 1) * Dv]).astype(o_ref.dtype)
            for h, s in zip(heads, states):
                state_ref[h] = s
    return kernel


def _backward_kernel(low, prec):
    hi = lax.Precision.HIGHEST

    def kernel(q_ref, k_ref, v_ref, gc_ref, bt_ref, spread_ref, start_ref,
               do_ref, dq_ref, dk_ref, dv_ref, dgc_ref, dbt_ref, ds_ref):
        spread = spread_ref[...]
        refs = (q_ref, k_ref, v_ref, gc_ref, bt_ref)
        R, P, chunks = _geometry(v_ref)
        Dk, Dv = q_ref.shape[3], v_ref.shape[3]
        m = _Packed(P)
        last_row = lax.broadcasted_iota(jnp.int32, (_C, 1), 0) == _C - 1
        lanes_sum = lambda t: jnp.sum(t, 1, keepdims=True)
        total = lambda t: jnp.sum(lanes_sum(t), 0, keepdims=True)

        @pl.when(pl.program_id(2) == 0)
        def _():
            ds_ref[...] = jnp.zeros_like(ds_ref)

        dqk = [[jnp.zeros((_C, Dk), _F32)] * 2 for _ in range(chunks)]
        for p in range(R // P):
            # the run again, forward from the state it started with
            heads, kept = _heads(m, p), []
            states = [start_ref[0, h, 0] for h in heads]
            for _, x, q, k in _run_of_chunks(m, low, prec, refs, chunks, p,
                                             spread):
                read, new, states = _hand_over(low, prec, x, states)
                kept.append((x, read, new, q, k))
            lanes = slice(p * m.W, (p + 1) * m.W)
            ds = [ds_ref[h] for h in heads]
            for c in reversed(range(chunks)):
                x, read, new, q, k = kept[c]
                tok = slice(c * _C, (c + 1) * _C)
                qf, kf = x["qf"], x["kf"]
                dq, dk = dqk[c]
                do = [do_ref[0, h, tok, :] for h in heads]
                do_all = do[0] if P == 1 else jnp.concatenate(do, 1)
                from_o = _dot(x["score_low"], do_all, _TN, prec)
                dsl = [d.astype(low) for d in ds]
                dnew = [from_o[j * _C:(j + 1) * _C, j * Dv:(j + 1) * Dv]
                        + _dot(x["k_out"][j], dsl[j], _NN, prec)
                        for j in range(P)]
                dscore = _dot(do_all, m.wide([[n] for n in new]), _NT, prec)
                dwu, dgc_col, dbt_col = [], [], []
                for j in range(P):
                    dn = dnew[j].astype(low)
                    dq_in = _dot(do[j], read[j], _NT, prec)
                    dk_out = _dot(new[j], dsl[j], _NT, prec)
                    dkeep = total(ds[j] * read[j].astype(_F32))
                    ds[j] = ds[j] * x["keep"][j] \
                        + _dot(x["q_in"][j], do[j], _TN, prec) \
                        - _dot(x["w"][j], dn, _TN, prec)
                    dwu += [-_dot(dn, read[j], _NT, prec), dnew[j]]
                    tail = lanes_sum(dk_out * kf) * x["f"][j]
                    dgl = jnp.sum(tail, 0, keepdims=True) \
                        + dkeep * x["keep"][j]
                    dgc_col.append(lanes_sum(dq_in * qf) * x["e"][j] - tail
                                   + jnp.where(last_row, dgl, 0.0))
                    dq = dq + dq_in * x["e"][j]
                    dk = dk + dk_out * x["f"][j]
                dwu = jnp.concatenate(dwu, 1).astype(low)
                dt = _dot(dwu, x["kv"], _NT, prec)
                dkv = _dot(x["tl"], dwu, _TN, prec)
                for j, h in enumerate(heads):
                    at = lambda off, n: dkv[
                        j * _C:(j + 1) * _C,
                        j * (Dk + Dv) + off:j * (Dk + Dv) + off + n]
                    dkb, dvb = at(0, Dk), at(Dk, Dv)
                    s = lanes_sum(dkb * kf)
                    dbt_col.append(s * x["e"][j] + lanes_sum(dvb * x["vf"][j]))
                    dgc_col[j] = dgc_col[j] + s * x["into"][j]
                    dk = dk + dkb * x["into"][j]
                    dv_ref[0, h, tok, :] = (dvb * x["bcol"][j]).astype(
                        dv_ref.dtype)
                # dA = -T^T dT T^T, below the diagonal
                t = x["t"]
                da = -jnp.where(m.strict, m.diag_blocks(_dot(
                    t, _dot(dt, m.block_diag(t), _NT, hi), _TN, hi)), 0.0)
                dgram = (da * x["bcc"] * x["decay"]).astype(low)
                dqk_ = (dscore * x["decay"]).astype(low)
                moved = da * x["a"] + dscore * x["score"]
                for j, s in enumerate(m.row_sums(da * x["gram"] * x["decay"])):
                    dbt_col[j] = dbt_col[j] + s
                for j, s in enumerate(m.row_sums(moved)):
                    dgc_col[j] = dgc_col[j] + s
                dgc_ref[0, 0, c:c + 1, lanes] = m.row_of(dgc_col) \
                    - jnp.sum(moved, 0, keepdims=True)
                dbt_ref[0, 0, c:c + 1, lanes] = m.row_of(dbt_col)
                dk = dk + _dot(dgram, x["kk"], _NN, prec) + m.fold(
                    _dot(dgram, k, _TN, prec) + _dot(dqk_, q, _TN, prec))
                dq = dq + _dot(dqk_, x["kk"], _NN, prec)
                dqk[c] = [dq, dk]
            for h, d in zip(heads, ds):
                ds_ref[h] = d
        for c, (dq, dk) in enumerate(dqk):
            tok = slice(c * _C, (c + 1) * _C)
            dq_ref[0, 0, tok, :] = dq.astype(dq_ref.dtype)
            dk_ref[0, 0, tok, :] = dk.astype(dk_ref.dtype)
    return kernel


def _layout(q, k, v, g, beta):
    """Pad the sequence to whole runs of chunks with tokens that leave
    the state as it is (k = v = 0, beta = 0, g = 0), and lay g's
    running sum inside a chunk and beta out (B, Hk, chunks, R 64): a
    chunk's values for the value heads of a key head side by side."""
    B, Hk, S, _ = k.shape
    R = v.shape[1] // Hk
    n = -(-S // _C)
    run = n if n <= _RUN else _RUN
    n = -(-n // run) * run
    pad = n * _C - S
    if pad:
        at = lambda t: jnp.pad(t, [(0, 0), (0, 0), (0, pad)]
                               + [(0, 0)] * (t.ndim - 3))
        q, k, v, g, beta = at(q), at(k), at(v), at(g), at(beta)
    rows = lambda t: jnp.swapaxes(
        t.astype(_F32).reshape(B, Hk, R, n, _C), 2, 3).reshape(
            B, Hk, n, R * _C)
    gc = jnp.cumsum(g.astype(_F32).reshape(B, Hk, R, n, _C), -1)
    return (q, k, v, rows(gc), rows(beta)), run


def _unrows(t, R):
    """(B, Hk, chunks, R 64) -> (B, Hk R, chunks, 64)."""
    B, Hk, n, _ = t.shape
    return jnp.swapaxes(t.reshape(B, Hk, n, R, _C), 2, 3).reshape(
        B, Hk * R, n, _C)


def _specs(args, run):
    q, _, v, gc, _ = args
    B, Hk, S, Dk = q.shape
    R, Dv, W = v.shape[1] // Hk, v.shape[3], gc.shape[3]
    T = run * _C
    Wp = _C if R == 1 else 2 * _C
    return dict(
        spread=_spread_matrix(Wp),
        spread_spec=pl.BlockSpec((Wp, (_BASE - 1) * Wp),
                                 lambda b, h, s: (0, 0)),
        grid=(B, Hk, S // T),
        qk=lambda ix: pl.BlockSpec((1, 1, T, Dk), ix),
        v=lambda ix: pl.BlockSpec((1, R, T, Dv), ix),
        rows=lambda ix: pl.BlockSpec((1, 1, run, W), ix),
        starts=lambda ix: pl.BlockSpec((1, R, 1, Dk, Dv), ix),
        scratch=[pltpu.VMEM((R, Dk, Dv), _F32)],
        starts_shape=jax.ShapeDtypeStruct((B, Hk * R, S // T, Dk, Dv), _F32),
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM))


def _precision(dtype):
    return lax.Precision.HIGHEST if dtype == jnp.float32 \
        else lax.Precision.DEFAULT


# The two calls are jitted on their own: a model's layers of one geometry
# then share ONE trace and ONE lowering of a kernel whose run of chunks
# is unrolled (6 000 equations a layer, forward and backward).  For the
# same reason there is one forward: it always writes the run starts (34 MB
# a layer at the Qwen3-Next cell's sizes), also where no gradient reads
# them.
@functools.partial(jax.jit, static_argnums=(1, 2))
def _run_forward(args, run, interpret):
    """o, and the float32 state every run of chunks starts from (what
    the backward kernel starts its runs from)."""
    z = _specs(args, run)
    v = args[2]
    fwd4 = lambda b, h, s: (b, h, s, 0)
    fwd5 = lambda b, h, s: (b, h, s, 0, 0)
    _count_launch("gated_delta_rule")
    return pl.pallas_call(
        _forward_kernel(v.dtype, _precision(v.dtype)),
        grid=z["grid"],
        in_specs=[z["qk"](fwd4), z["qk"](fwd4), z["v"](fwd4),
                  z["rows"](fwd4), z["rows"](fwd4), z["spread_spec"]],
        out_specs=[z["v"](fwd4), z["starts"](fwd5)],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   z["starts_shape"]],
        scratch_shapes=z["scratch"], compiler_params=z["params"],
        name="gated_delta_rule_forward",
        interpret=interpret)(*args, z["spread"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _run_backward(args, starts, do, run, interpret):
    z = _specs(args, run)
    q, _, v, gc, _ = args
    last = z["grid"][2] - 1
    rev4 = lambda b, h, s: (b, h, last - s, 0)
    rev5 = lambda b, h, s: (b, h, last - s, 0, 0)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    _count_launch("gated_delta_rule_bwd")
    return pl.pallas_call(
        _backward_kernel(v.dtype, _precision(v.dtype)),
        grid=z["grid"],
        in_specs=[z["qk"](rev4), z["qk"](rev4), z["v"](rev4),
                  z["rows"](rev4), z["rows"](rev4), z["spread_spec"],
                  z["starts"](rev5), z["v"](rev4)],
        out_specs=[z["qk"](rev4), z["qk"](rev4), z["v"](rev4),
                   z["rows"](rev4), z["rows"](rev4)],
        out_shape=[like(q), like(q), like(v), like(gc), like(gc)],
        scratch_shapes=z["scratch"], compiler_params=z["params"],
        name="gated_delta_rule_backward",
        interpret=interpret)(*args, z["spread"], starts, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    return _rule_fwd(q, k, v, g, beta, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    args, run = _layout(q, k, v, g, beta)
    with jax.named_scope("pallas.gated_delta_rule"):
        o, starts = _run_forward(args, run, interpret)
    return o[:, :, :k.shape[2]], (q, k, v, g, beta, starts)


def _rule_bwd(interpret, res, do):
    q, k, v, g, beta, starts = res
    S, R = k.shape[2], v.shape[1] // k.shape[1]
    args, run = _layout(q, k, v, g, beta)
    pad = args[0].shape[2] - S
    if pad:
        do = jnp.pad(do, [(0, 0), (0, 0), (0, pad), (0, 0)])
    with jax.named_scope("pallas.gated_delta_rule"):
        dq, dk, dv, dgc, dbeta = _run_backward(args, starts, do, run,
                                               interpret)
    # g's running sum reaches every later token of its chunk
    dg = jnp.flip(jnp.cumsum(jnp.flip(_unrows(dgc, R), -1), -1), -1)
    seq = lambda t: t.reshape(t.shape[:2] + (-1,))[:, :, :S]
    return (dq[:, :, :S], dk[:, :, :S], dv[:, :, :S],
            seq(dg).astype(g.dtype), seq(_unrows(dbeta, R)).astype(beta.dtype))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, interpret=False):
    """``chunk_gated_delta_rule(q, k, v, g, beta)`` (chunks of 64) as
    Pallas kernels, forward and backward; operands as there, and
    :func:`supported` says which."""
    ok, why = supported(q, k, v)
    if not ok:
        raise ValueError("pallas gated delta rule: " + why)
    return _rule(q, k, v, g, beta, bool(interpret))
