"""mx.rtc — runtime kernel compilation.

Reference parity: python/mxnet/rtc.py (``CudaModule``: NVRTC-compile
CUDA source at runtime, ``get_kernel(name, signature)``, ``launch``
over grid/block dims; src/common/rtc.cc). The TPU has no user-facing
runtime C compilation — custom kernels are **Pallas** Python functions
compiled by XLA — so the module shape is preserved with Pallas as the
kernel language:

    mod = mx.rtc.PallasModule(axpy=my_axpy_kernel)
    k = mod.get_kernel("axpy", out_shape=(n,), out_dtype="float32",
                       grid=(blocks,))
    y = k.launch([a, x], mx.tpu(0))

A kernel body takes ``(*input_refs, out_ref)`` pallas Refs.  Kernels
compile for the backend they launch on; ``get_kernel(...,
interpret=True)`` runs one in pallas interpret mode instead, so the
same code tests on CPU.  ``CudaModule`` raises with guidance — CUDA
source cannot target a TPU.
"""
from __future__ import annotations

import jax

from .base import MXNetError
from .context import current_context
from .ndarray.ndarray import NDArray

__all__ = ["CudaModule", "PallasModule"]


def CudaModule(*args, **kwargs):
    raise MXNetError(
        "mx.rtc.CudaModule compiles CUDA source, which cannot target a "
        "TPU. Write the kernel as a Pallas function and wrap it in "
        "mx.rtc.PallasModule (kernel model: "
        "https://docs.jax.dev/en/latest/pallas/index.html).")


class PallasKernel:
    """A launchable Pallas kernel (the CudaKernel analog)."""

    def __init__(self, name, body, out_shape, out_dtype, grid, in_specs,
                 out_specs, interpret):
        self._name = name
        self._body = body
        self._out_shape = tuple(out_shape)
        self._out_dtype = out_dtype
        self._grid = grid
        self._in_specs = in_specs
        self._out_specs = out_specs
        self._interpret = interpret
        self._compiled = None

    def _fn(self):
        if self._compiled is None:
            from jax.experimental import pallas as pl
            import jax.numpy as jnp

            kwargs = {}
            if self._grid is not None:
                kwargs["grid"] = self._grid
            if self._in_specs is not None:
                kwargs["in_specs"] = self._in_specs
            if self._out_specs is not None:
                kwargs["out_specs"] = self._out_specs
            # analyze: ok(retrace) user-authored RTC kernel — built once per CudaKernel and counted by the jit site below
            call = pl.pallas_call(
                self._body,
                out_shape=jax.ShapeDtypeStruct(self._out_shape,
                                               jnp.dtype(self._out_dtype)),
                interpret=self._interpret, **kwargs)
            # analyze: ok(retrace) user-authored RTC kernel compiles once per CudaKernel construction (the reference's nvrtc contract)
            self._compiled = jax.jit(call)
        return self._compiled

    def launch(self, args, ctx=None, grid_dims=None, block_dims=None,
               shared_mem=0):
        """Run the kernel on NDArray/array inputs; returns an NDArray.
        ``grid_dims``/``block_dims``/``shared_mem`` are accepted for
        CudaKernel.launch signature parity — the Pallas grid is fixed at
        ``get_kernel`` time (blocks/threads are the compiler's job on
        TPU)."""
        ctx = ctx if ctx is not None else current_context()
        vals = [a._data if isinstance(a, NDArray) else a for a in args]
        out = self._fn()(*vals)
        return NDArray(out, ctx)

    def __call__(self, *args):
        return self.launch(list(args))


class PallasModule:
    """Named collection of Pallas kernels (the CudaModule analog)."""

    def __init__(self, **kernels):
        if not kernels:
            raise MXNetError("PallasModule needs at least one "
                             "name=kernel_fn pair")
        self._kernels = dict(kernels)

    def get_kernel(self, name, out_shape, out_dtype="float32", grid=None,
                   in_specs=None, out_specs=None, interpret=False):
        """Bind a kernel body to output shape/dtype (+ optional pallas
        grid/BlockSpecs); mirrors CudaModule.get_kernel(name, signature).
        ``interpret=True`` emulates the kernel (any backend) instead of
        compiling it — never inferred from where the process runs."""
        if name not in self._kernels:
            raise MXNetError("no kernel '%s' in module (have %s)"
                             % (name, sorted(self._kernels)))
        return PallasKernel(name, self._kernels[name], out_shape,
                            out_dtype, grid, in_specs, out_specs,
                            interpret)
