"""Fused 2-bit quantize kernel for the kvstore bucket path
(docs/KERNELS.md — the ISSUE 13 stretch kernel).

``kvstore_fused.two_bit_quantize`` is the error-feedback quantizer
shared by the bucketed kvstore step and the fused fit step:
``acc = residual + grad; q = select(acc, ±t, 0); new_res = acc - q``.
The XLA path emits that as a fusable elementwise chain; this kernel
computes both outputs in ONE pass over VMEM tiles — ``acc`` is never
materialized and each element is read once and written twice, the
minimum possible traffic for the op pair.  Dispatch rides
``MXNET_Q2BIT_IMPL`` through the same ``choose_impl`` contract as the
attention kernels; forced off-TPU by that knob it runs
``interpret=True`` (parity vs the XLA sequence is bit-exact — same
select constants, same subtract — pinned in tests/test_pallas.py).
"""
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _count_launch

# one (rows, 128) f32 tile per grid step — 8 sublanes x 128 lanes is
# the native f32 VMEM tile; 64 rows keeps the working set tiny while
# amortizing the grid-step overhead
_TILE_ROWS = 64
_LANES = 128


def _two_bit_quantize_kernel(thr_ref, res_ref, grad_ref, q_ref,
                             out_res_ref):
    t = thr_ref[0]
    acc = res_ref[...] + grad_ref[...]
    q = jnp.where(acc > t, t,
                  jnp.where(acc < -t, -t, jnp.zeros_like(acc)))
    q_ref[...] = q
    out_res_ref[...] = acc - q


def two_bit_quantize_fused(residual, grad, threshold, *,
                           interpret=False):
    """Error-feedback 2-bit quantize, one fused pass: returns
    ``(q, new_residual)`` with the exact op sequence (and therefore
    bit pattern) of ``kvstore_fused.two_bit_quantize``.  Accepts any
    shape; internally flattens and pads to (rows, 128) f32 tiles."""
    shape, dtype = grad.shape, grad.dtype
    n = 1
    for s in shape:
        n *= int(s)
    cols = _LANES
    rows = -(-n // cols)                    # ceil
    rows_pad = -(-rows // _TILE_ROWS) * _TILE_ROWS
    pad = rows_pad * cols - n

    def tile(a):
        flat = a.reshape(-1).astype(dtype)
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), dtype)])
        return flat.reshape(rows_pad, cols)

    thr = jnp.asarray(threshold, dtype).reshape(1)
    _count_launch("two_bit_quantize_fused")
    grid = (rows_pad // _TILE_ROWS,)
    spec = pl.BlockSpec((_TILE_ROWS, cols), lambda i, t: (i, 0))
    fn = pl.pallas_call(
        _two_bit_quantize_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[spec, spec], out_specs=[spec, spec]),
        out_shape=[jax.ShapeDtypeStruct((rows_pad, cols), dtype)] * 2,
        interpret=interpret,
    )
    with jax.named_scope("pallas.two_bit_quantize_fused"):
        q, new_res = fn(thr, tile(residual), tile(grad))
    return (q.reshape(-1)[:n].reshape(shape),
            new_res.reshape(-1)[:n].reshape(shape))
