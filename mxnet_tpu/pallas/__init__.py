"""mx.pallas — in-repo Pallas kernel library (docs/KERNELS.md).

The paper names "NN ops lowering to XLA/Pallas" as a first-class goal;
this package holds the custom TPU kernels behind the framework's
`*_IMPL` knobs:

* :mod:`attention` — paged-KV-cache decode attention (walks the block
  table inside the kernel, online softmax, no materialized context
  tensor) and the prefill variant with the cache scatter fused into
  the same kernel.
* :mod:`quant` — fused 2-bit quantize (error-feedback residual) for
  the kvstore bucket path.
* :mod:`layernorm` — fused LayerNorm (+ optional residual add)
  forward/backward for the transformer symbol path (``MXNET_LN_IMPL``;
  the ISSUE 17 registry-ranked kernel).
* :mod:`dispatch` — the one ``auto|<kernel>|xla`` selection contract
  shared by every kernel knob (``MXNET_PAGED_ATTN_IMPL``,
  ``MXNET_Q2BIT_IMPL``, ``MXNET_LN_IMPL``),
  plus the ``pallas_kernel_launches`` / ``pallas_fallbacks``
  witnesses.

Every kernel takes ``interpret=True``, and its knob forced onto a
backend that cannot compile it says the same, so the CPU container and
tier-1 exercise the exact kernel code paths against the XLA reference
paths (the interpret-mode testing convention, docs/KERNELS.md).
Nothing else selects interpret mode: ``auto`` means the compiled
kernel or the XLA path.  Importing this package does not require a
TPU.
"""
from . import dispatch
from .dispatch import (PALLAS_FALLBACKS, PALLAS_LAUNCHES, choose_impl,
                       paged_attn_impl, use_layernorm_pallas,
                       use_paged_pallas, use_q2bit_pallas)
from . import attention
from .attention import (paged_chunk_prefill_attend, paged_decode_attend,
                        paged_prefill_attend)
from . import quant
from .quant import two_bit_quantize_fused
from . import layernorm
from .layernorm import layernorm_fused

__all__ = [
    "attention", "dispatch", "quant", "layernorm",
    "choose_impl", "paged_attn_impl", "use_paged_pallas",
    "use_q2bit_pallas", "use_layernorm_pallas",
    "paged_chunk_prefill_attend",
    "paged_decode_attend", "paged_prefill_attend",
    "two_bit_quantize_fused", "layernorm_fused",
    "PALLAS_FALLBACKS", "PALLAS_LAUNCHES",
]
