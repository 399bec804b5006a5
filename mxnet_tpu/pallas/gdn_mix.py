"""Gated DeltaNet's convolution, SiLU and L2 norms as Pallas kernels
(docs/KERNELS.md).

Same result as ``ops/nn.py`` ``gdn_conv`` (the ``jax.numpy`` form): the
head-major ``[q; k; v]`` (B, 2 Hk + Hv, S, D) passes a causal depthwise
convolution over the sequence (K taps, zeros before position 0) and
SiLU; the first ``Hk`` heads (q) are L2-normalised and scaled by
``D ** -0.5``, the next ``Hk`` (k) L2-normalised, the rest (v) left as
they are.  float32 from the widening of the input to the one rounding
to its dtype, the taps summed oldest first, ``x * rsqrt(sum(x^2) +
1e-6)``.  Every float32 array (the widened input, the taps, the
pre-activation, the SiLU, the row sums) lives in VMEM only: HBM sees
one read of ``qkv`` and one write of q, k, v forward, and one read of
``qkv`` and of dq, dk, dv and one write of ``qkv``'s gradient backward.

A grid step is one head and a block of rows ((rows, D): tokens in the
sublanes, the head's channels in the lanes), taken ``_CHUNK`` rows at a
time in a ``fori_loop`` (a kernel's trace is one chunk long).  Which of the
three treatments a head gets follows from its index against ``Hk``; q,
k and v are three outputs, and the steps of another section's heads
leave an output's block index where the section's walk starts or ended,
so nothing is written for them (the backward reads dq, dk, dv the same
way).  The ``K - 1`` earlier rows a block needs come from a second,
16-row view of ``qkv`` at the rows before it (zeros at the first
block).  The backward walks a head's blocks from the last to the first
and a block's chunks from the last to the first: it computes a chunk's
pre-activation again in VMEM, carries the first rows of the following
chunk's pre-activation gradient (across grid steps in a scratch), and
accumulates the convolution weight's gradient in an output block
that stays resident across the head's blocks.  So the ``custom_vjp``
keeps ``qkv`` and the weight and nothing else, and no forward pass runs
again in HBM.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _count_launch

_ROWS = 4096     # rows a grid step, and rows taken at once inside a step
_CHUNK = 256     # (both swept on the chip: PERF.md, PR 33)
_HALO = 16       # rows of the second view: one bfloat16 tile
_TAIL = 8        # of which a block sees the last 8: conv_kernel <= 8
_EPS = 1e-6
_VMEM = 64 << 20
_F32 = jnp.float32


def supported(qkv, k_heads, conv_kernel):
    """Whether the kernels take these operands: a head width of whole
    lane tiles, at most 8 taps, bfloat16 or float32, q and k heads that
    leave value heads.  Returns ``(ok, why)``."""
    H, D = qkv.shape[1], qkv.shape[3]
    ok = (qkv.ndim == 4 and D % 128 == 0 and 1 <= conv_kernel <= _TAIL
          and qkv.dtype in (jnp.bfloat16, jnp.float32)
          and 0 < 2 * k_heads < H)
    return ok, ("D=%d conv_kernel=%d heads=%d (2 x %d before the values) "
                "dtype=%s; need D %% 128 == 0, conv_kernel <= %d, bf16/f32"
                % (D, conv_kernel, H, k_heads, qkv.dtype, _TAIL))


def _section(lo, n, blocks, order):
    """Index map of an array that holds heads ``lo .. lo + n - 1`` of the
    grid's: on the steps of other heads it stays on the block its own
    walk starts with (heads before) or ended with (heads after), so no
    block is fetched or written back for them."""
    def index(b, h, s):
        at = jnp.where(h < lo, order(0),
                       jnp.where(h < lo + n, order(s), order(blocks - 1)))
        return b, jnp.clip(h - lo, 0, n - 1), at, 0
    return index


def _stage(x_ref, halo_ref, xbuf, first):
    """The block's rows in float32 behind the 8 rows before them."""
    @pl.when(first)
    def _():
        xbuf[0:_TAIL] = jnp.zeros((_TAIL, xbuf.shape[1]), _F32)

    @pl.when(jnp.logical_not(first))
    def _():
        xbuf[0:_TAIL] = halo_ref[0, 0].astype(_F32)[_HALO - _TAIL:]

    xbuf[_TAIL:] = x_ref[0, 0].astype(_F32)


def _window(ext, at):
    """``_CHUNK`` rows of a chunk and the 8 rows beside it, from row
    ``at``: a tap.  Off a tile's edge it is a roll of the sublanes and
    an aligned slice (a slice at an unaligned row computes the same and
    cost the backward a tenth more: PERF.md, PR 33)."""
    if at % 8 == 0:
        return ext[at:at + _CHUNK]
    return pltpu.roll(ext, ext.shape[0] - at, 0)[:_CHUNK]


def _taps(xbuf, w_ref, K, base):
    """Rows ``base .. base + _CHUNK`` of the pre-activation, and the K
    shifted inputs (oldest first) that made it."""
    ext = xbuf[pl.ds(base, _CHUNK + _TAIL), :]
    shifted = [_window(ext, _TAIL - n) for n in range(K - 1, -1, -1)]
    pre = shifted[0] * w_ref[0, 0:1, :]
    for j in range(1, K):
        pre = pre + shifted[j] * w_ref[0, j:j + 1, :]
    return pre, shifted


def _inverse_norm(a):
    return lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + _EPS)


def _forward_kernel(K, Hk, scale):
    def kernel(x_ref, halo_ref, w_ref, q_ref, k_ref, v_ref, xbuf):
        h, s = pl.program_id(1), pl.program_id(2)
        _stage(x_ref, halo_ref, xbuf, s == 0)
        chunks = x_ref.shape[2] // _CHUNK

        def run(out_ref, unit, scaled):
            def body(i, carry):
                base = pl.multiple_of(i * _CHUNK, _CHUNK)
                pre, _ = _taps(xbuf, w_ref, K, base)
                a = pre * jax.nn.sigmoid(pre)
                if unit:
                    a = a * _inverse_norm(a)
                if scaled:
                    a = a * scale
                out_ref[0, 0, pl.ds(base, _CHUNK), :] = a.astype(out_ref.dtype)
                return carry
            lax.fori_loop(0, chunks, body, 0)

        pl.when(h < Hk)(lambda: run(q_ref, True, True))
        pl.when((h >= Hk) & (h < 2 * Hk))(lambda: run(k_ref, True, False))
        pl.when(h >= 2 * Hk)(lambda: run(v_ref, False, False))
    return kernel


def _backward_kernel(K, Hk, scale):
    def kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref,
               dx_ref, dw_ref, xbuf, later):
        h, r = pl.program_id(1), pl.program_id(2)
        T, D = x_ref.shape[2], x_ref.shape[3]
        chunks = T // _CHUNK
        # the walk is from the last block to the first, and from a
        # block's last chunk to its first; ``later`` holds the first
        # rows of the pre-activation gradient of the rows that follow
        _stage(x_ref, halo_ref, xbuf, r == pl.num_programs(2) - 1)

        @pl.when(r == 0)
        def _():
            later[...] = jnp.zeros((_TAIL, D), _F32)
            dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

        def walk(do_ref, unit, scaled):
            def body(i, carry):
                sums, after = carry
                base = pl.multiple_of((chunks - 1 - i) * _CHUNK, _CHUNK)
                pre, shifted = _taps(xbuf, w_ref, K, base)
                sig = jax.nn.sigmoid(pre)
                g = do_ref[0, 0, pl.ds(base, _CHUNK), :].astype(_F32)
                if scaled:
                    g = g * scale
                if unit:
                    a = pre * sig
                    inv = _inverse_norm(a)
                    dot = jnp.sum(g * a, axis=-1, keepdims=True)
                    g = g * inv - a * (inv * inv * inv * dot)
                dpre = g * (sig * (1.0 + pre * (1.0 - sig)))
                # through the taps: the input's gradient reads the K - 1
                # later rows' pre-activation gradients, the weight's the
                # shifted inputs
                ext = jnp.concatenate([dpre, after], axis=0)
                dx = _window(ext, K - 1) * w_ref[0, 0:1, :]
                for j in range(1, K):
                    dx = dx + _window(ext, K - 1 - j) * w_ref[0, j:j + 1, :]
                dx_ref[0, 0, pl.ds(base, _CHUNK), :] = dx.astype(dx_ref.dtype)
                sums = tuple(
                    acc + (dpre * x).reshape(_CHUNK // 8, 8, D).sum(0)
                    for acc, x in zip(sums, shifted))
                return sums, dpre[:_TAIL]

            sums, first = lax.fori_loop(
                0, chunks, body,
                ((jnp.zeros((8, D), _F32),) * K, later[...]))
            for j in range(K):
                dw_ref[0, 0, j:j + 1, :] += jnp.sum(sums[j], axis=0,
                                                    keepdims=True)
            later[...] = first

        pl.when(h < Hk)(lambda: walk(dq_ref, True, True))
        pl.when((h >= Hk) & (h < 2 * Hk))(lambda: walk(dk_ref, True, False))
        pl.when(h >= 2 * Hk)(lambda: walk(dv_ref, False, False))
    return kernel


def _rows(S):
    """Rows a grid step for a sequence of S, and S padded to whole
    steps."""
    up = lambda n, m: -(-n // m) * m
    rows = min(_ROWS, up(S, _CHUNK))
    return rows, up(S, rows)


def _specs(qkv, w, k_heads, rows, order):
    B, H, S, D = qkv.shape
    K = w.shape[1]
    blocks = S // rows
    per = rows // _HALO
    Hv = H - 2 * k_heads
    block = lambda ix: pl.BlockSpec((1, 1, rows, D), ix)
    section = lambda lo, n: block(_section(lo, n, blocks, order))
    return dict(
        grid=(B, H, blocks),
        x=block(lambda b, h, s: (b, h, order(s), 0)),
        halo=pl.BlockSpec(
            (1, 1, _HALO, D),
            lambda b, h, s: (b, h, jnp.maximum(order(s) * per - 1, 0), 0)),
        w=pl.BlockSpec((1, K, D), lambda b, h, s: (h, 0, 0)),
        q=section(0, k_heads), k=section(k_heads, k_heads),
        v=section(2 * k_heads, Hv),
        part=lambda n: jax.ShapeDtypeStruct((B, n, S, D), qkv.dtype),
        # the head dimension is walked in order: an output section's
        # block index rests between its heads' steps and the others'
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM))


# Jitted on their own, as the delta rule's: a model's layers of one
# geometry share ONE trace and ONE lowering of each kernel.
@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _run_forward(qkv, w, k_heads, rows, interpret):
    """q, k, v from ``qkv`` (B, H, S, D), S whole grid steps, and the
    weight as (H, K, D) float32."""
    H, D = qkv.shape[1], qkv.shape[3]
    z = _specs(qkv, w, k_heads, rows, lambda s: s)
    _count_launch("gdn_mix")
    return pl.pallas_call(
        _forward_kernel(w.shape[1], k_heads, D ** -0.5),
        grid=z["grid"],
        in_specs=[z["x"], z["halo"], z["w"]],
        out_specs=[z["q"], z["k"], z["v"]],
        out_shape=[z["part"](k_heads), z["part"](k_heads),
                   z["part"](H - 2 * k_heads)],
        scratch_shapes=[pltpu.VMEM((rows + _TAIL, D), _F32)],
        compiler_params=z["params"], name="gdn_mix_forward",
        interpret=interpret)(qkv, qkv, w)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _run_backward(qkv, w, dq, dk, dv, k_heads, rows, interpret):
    """The gradients of ``qkv`` and of the (B, H, K, D) float32 weight
    (a sequence's own: the caller sums them)."""
    B, H, S, D = qkv.shape
    K = w.shape[1]
    last = S // rows - 1
    z = _specs(qkv, w, k_heads, rows, lambda s: last - s)
    _count_launch("gdn_mix")
    return pl.pallas_call(
        _backward_kernel(K, k_heads, D ** -0.5),
        grid=z["grid"],
        in_specs=[z["x"], z["halo"], z["w"], z["q"], z["k"], z["v"]],
        out_specs=[z["x"],
                   pl.BlockSpec((1, 1, K, D), lambda b, h, s: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
                   jax.ShapeDtypeStruct((B, H, K, D), _F32)],
        scratch_shapes=[pltpu.VMEM((rows + _TAIL, D), _F32),
                        pltpu.VMEM((_TAIL, D), _F32)],
        compiler_params=z["params"], name="gdn_mix_backward",
        interpret=interpret)(qkv, qkv, w, dq, dk, dv)


def _pad_rows(t, pad):
    return jnp.pad(t, [(0, 0), (0, 0), (0, pad), (0, 0)]) if pad else t


def _layout(qkv, conv_weight):
    """``qkv`` padded with zero rows to whole grid steps (rows after the
    sequence reach nothing before them), and the weight (H D, K) as
    (H, K, D) float32: a tap's channels in the lanes."""
    _, H, S, D = qkv.shape
    rows, padded = _rows(S)
    w = jnp.swapaxes(conv_weight.astype(_F32).reshape(H, D, -1), 1, 2)
    return _pad_rows(qkv, padded - S), w, rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mix(qkv, conv_weight, k_heads, interpret):
    return _mix_fwd(qkv, conv_weight, k_heads, interpret)[0]


def _mix_fwd(qkv, conv_weight, k_heads, interpret):
    S = qkv.shape[2]
    x, w, rows = _layout(qkv, conv_weight)
    with jax.named_scope("pallas.gdn_mix"):
        out = _run_forward(x, w, k_heads, rows, interpret)
    return tuple(t[:, :, :S] for t in out), (qkv, conv_weight)


def _mix_bwd(k_heads, interpret, res, grads):
    qkv, conv_weight = res
    S = qkv.shape[2]
    x, w, rows = _layout(qkv, conv_weight)
    grads = [_pad_rows(t, x.shape[2] - S) for t in grads]
    with jax.named_scope("pallas.gdn_mix"):
        dx, dw = _run_backward(x, w, *grads, k_heads, rows, interpret)
    dw = jnp.swapaxes(dw.sum(0), 1, 2).reshape(conv_weight.shape)
    return dx[:, :, :S], dw.astype(conv_weight.dtype)


_mix.defvjp(_mix_fwd, _mix_bwd)


def gdn_mix(qkv, conv_weight, k_heads, interpret=False):
    """``gdn_conv(qkv, conv_weight, k_heads)`` as Pallas kernels, forward
    and backward: (q, k, v) from the head-major ``[q; k; v]`` (B, 2
    k_heads + v_heads, S, D) and the depthwise weight (channels,
    conv_kernel); :func:`supported` says which operands."""
    ok, why = supported(qkv, k_heads, conv_weight.shape[-1])
    if not ok:
        raise ValueError("pallas gdn mix: " + why)
    return _mix(qkv, conv_weight, int(k_heads), bool(interpret))
