"""Fused LayerNorm (+ optional residual-add) kernels
(docs/KERNELS.md — the ISSUE 17 registry-ranked kernel).

``telemetry.programs()`` ranks the transformer step's residual ops by
compiler-reported bytes: after attention and the matmuls, the LayerNorm
chain is the top non-matmul traffic — XLA emits mean/variance/normalize
/scale/shift as separate HBM passes plus a fourth for the preceding
residual add.  This kernel computes the whole chain in ONE pass over
VMEM row tiles: each input element is read once and written once
(forward), and the backward kernel fuses dx with the dgamma/dbeta
row-reductions via grid-sequential accumulation.

Contract (shared with the attention/quant kernels):

* dispatch rides ``MXNET_LN_IMPL`` through ``dispatch.choose_impl``
  (``auto`` = compiled kernel on TPU only; force ``pallas`` to run it
  in interpret mode anywhere — how tier-1 pins parity on CPU);
* host wrappers thread ``_count_launch`` so kernel builds land in the
  same retrace/launch witnesses as every other program;
* gradients flow through a ``jax.custom_vjp`` pair, so the symbol
  path's fwd+bwd both stay fused.  Cotangents arriving on the
  mean/inv_std outputs are NOT propagated (ops/nn.py routes here only
  when ``output_mean_var=False``, where they are structurally unused).

Rows are padded to 8-sublane tiles and the feature dim to 128 lanes;
reductions mask the padded lanes, so any (rows, features) geometry
with ``axis=-1`` is supported.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl

from .attention import _count_launch

# one (8, C_pad) f32 row tile per grid step: 8 sublanes is the native
# f32 tile height and a whole (padded) feature row must sit in VMEM for
# the single-pass row reduction
_TILE_ROWS = 8
_LANES = 128


def _ln_fwd_kernel(cols, eps, with_res):
    inv_cols = 1.0 / float(cols)

    def kernel(*refs):
        if with_res:
            x_ref, res_ref, g_ref, b_ref, o_ref, mean_ref, rstd_ref = refs
        else:
            x_ref, g_ref, b_ref, o_ref, mean_ref, rstd_ref = refs
        x = x_ref[...].astype(jnp.float32)
        if with_res:
            x = x + res_ref[...].astype(jnp.float32)
        mask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < cols
        mean = jnp.sum(jnp.where(mask, x, 0.0), axis=1,
                       keepdims=True) * inv_cols
        d = jnp.where(mask, x - mean, 0.0)
        var = jnp.sum(d * d, axis=1, keepdims=True) * inv_cols
        rstd = lax.rsqrt(var + eps)
        g = g_ref[...].astype(jnp.float32)
        b = b_ref[...].astype(jnp.float32)
        o_ref[...] = (d * rstd * g + b).astype(o_ref.dtype)
        mean_ref[...] = jnp.broadcast_to(mean, mean_ref.shape)
        rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape)

    return kernel


def _ln_bwd_kernel(cols, with_res):
    inv_cols = 1.0 / float(cols)

    def kernel(x_ref, res_ref, g_ref, mean_ref, rstd_ref, dy_ref,
               dx_ref, dg_ref, db_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            dg_ref[...] = jnp.zeros_like(dg_ref)
            db_ref[...] = jnp.zeros_like(db_ref)

        x = x_ref[...].astype(jnp.float32)
        if with_res:
            x = x + res_ref[...].astype(jnp.float32)
        mask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < cols
        mean = mean_ref[...][:, :1]
        rstd = rstd_ref[...][:, :1]
        xhat = jnp.where(mask, (x - mean) * rstd, 0.0)
        dy = jnp.where(mask, dy_ref[...].astype(jnp.float32), 0.0)
        g = g_ref[...].astype(jnp.float32)
        dxhat = dy * g
        m1 = jnp.sum(dxhat, axis=1, keepdims=True) * inv_cols
        m2 = jnp.sum(dxhat * xhat, axis=1, keepdims=True) * inv_cols
        dx = rstd * (dxhat - m1 - xhat * m2)
        dx_ref[...] = jnp.where(mask, dx, 0.0).astype(dx_ref.dtype)
        dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
        db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)

    return kernel


def _pad2(a, rows_pad, cols_pad):
    r, c = a.shape
    if r == rows_pad and c == cols_pad:
        return a
    return jnp.pad(a, ((0, rows_pad - r), (0, cols_pad - c)))


def _vec_pad(v, cols_pad):
    v = v.reshape(1, -1)
    if v.shape[1] != cols_pad:
        v = jnp.pad(v, ((0, 0), (0, cols_pad - v.shape[1])))
    return v


def _geometry(rows, cols):
    cols_pad = -(-cols // _LANES) * _LANES
    rows_pad = -(-rows // _TILE_ROWS) * _TILE_ROWS
    return rows_pad, cols_pad


def _ln_forward(eps, interpret, x2d, gamma, beta, residual):
    rows, cols = x2d.shape
    rows_pad, cols_pad = _geometry(rows, cols)
    with_res = residual is not None
    _count_launch("layernorm_fused")
    grid = (rows_pad // _TILE_ROWS,)
    row_spec = pl.BlockSpec((_TILE_ROWS, cols_pad), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, cols_pad), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((_TILE_ROWS, _LANES), lambda i: (i, 0))
    in_specs = [row_spec] + ([row_spec] if with_res else []) \
        + [vec_spec, vec_spec]
    fn = pl.pallas_call(
        _ln_fwd_kernel(cols, eps, with_res),
        grid=grid,
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, cols_pad), x2d.dtype),
            jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )
    args = [_pad2(x2d, rows_pad, cols_pad)]
    if with_res:
        args.append(_pad2(residual, rows_pad, cols_pad))
    args += [_vec_pad(gamma, cols_pad), _vec_pad(beta, cols_pad)]
    with jax.named_scope("pallas.layernorm_fused"):
        out, mean, rstd = fn(*args)
    return out[:rows, :cols], mean[:rows, 0], rstd[:rows, 0]


def _ln_backward(eps, interpret, saved, dy):
    x2d, gamma, residual, mean, rstd = saved
    rows, cols = x2d.shape
    rows_pad, cols_pad = _geometry(rows, cols)
    with_res = residual is not None
    _count_launch("layernorm_fused_bwd")
    grid = (rows_pad // _TILE_ROWS,)
    row_spec = pl.BlockSpec((_TILE_ROWS, cols_pad), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, cols_pad), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((_TILE_ROWS, _LANES), lambda i: (i, 0))
    fn = pl.pallas_call(
        _ln_bwd_kernel(cols, with_res),
        grid=grid,
        in_specs=[row_spec, row_spec, vec_spec, stat_spec, stat_spec,
                  row_spec],
        out_specs=[row_spec, vec_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, cols_pad), x2d.dtype),
            jax.ShapeDtypeStruct((1, cols_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, cols_pad), jnp.float32),
        ],
        interpret=interpret,
    )
    # padded stat rows carry rstd=0 so padded-row dx is exactly zero
    stat = jnp.zeros((rows_pad, _LANES), jnp.float32)
    mean_t = stat.at[:rows, :].set(mean.reshape(-1, 1))
    rstd_t = stat.at[:rows, :].set(rstd.reshape(-1, 1))
    res_t = _pad2(residual, rows_pad, cols_pad) if with_res \
        else jnp.zeros((rows_pad, cols_pad), x2d.dtype)
    args = (_pad2(x2d, rows_pad, cols_pad), res_t,
            _vec_pad(gamma, cols_pad), mean_t, rstd_t,
            _pad2(dy, rows_pad, cols_pad))
    with jax.named_scope("pallas.layernorm_fused_bwd"):
        dx, dg, db = fn(*args)
    dx = dx[:rows, :cols]
    dg = dg[0, :cols].astype(gamma.dtype)
    db = db[0, :cols]
    dres = dx if with_res else None
    return dx, dg, db, dres


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _layernorm(eps, interpret, x2d, gamma, beta, residual):
    return _ln_forward(eps, interpret, x2d, gamma, beta, residual)


def _layernorm_fwd(eps, interpret, x2d, gamma, beta, residual):
    out, mean, rstd = _ln_forward(eps, interpret, x2d, gamma, beta,
                                  residual)
    return (out, mean, rstd), (x2d, gamma, residual, mean, rstd)


def _layernorm_bwd_rule(eps, interpret, saved, cts):
    # cts[1]/cts[2] (mean / inv_std cotangents) are structurally unused
    # on the routed path (output_mean_var=False) — not propagated
    dx, dg, db, dres = _ln_backward(eps, interpret, saved, cts[0])
    return dx, dg, db, dres


_layernorm.defvjp(_layernorm_fwd, _layernorm_bwd_rule)


def layernorm_fused(x, gamma, beta, *, residual=None, eps=1e-5,
                    interpret=False):
    """Fused LayerNorm over the LAST axis, optionally fused with a
    preceding residual add (``x + residual`` never materializes in
    HBM).  Returns ``(out, mean, inv_std)`` — out in ``x.dtype``,
    stats in f32 with ``x.shape[:-1]`` — matching the XLA reference in
    ops/nn.py ``layer_norm`` bit-for-parity within FMA-contraction
    ulps.  Differentiable wrt x / gamma / beta / residual through the
    fused backward kernel."""
    cols = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, cols)
    r2 = residual.reshape(-1, cols) if residual is not None else None
    out, mean, rstd = _layernorm(float(eps), bool(interpret), x2,
                                 gamma.reshape(-1), beta.reshape(-1), r2)
    return (out.reshape(x.shape), mean.reshape(lead),
            rstd.reshape(lead))
