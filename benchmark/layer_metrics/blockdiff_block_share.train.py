"""Percent of the causal 512 x 512 score blocks of the same 2 L rows
that the block-diffusion flash kernels of the step's program execute,
forward and backward, all layers: the program's trace-time counters
``flash_blocks_walked`` over ``flash_blocks_causal`` for the two kernel
labels ``flash_attention_blocks`` and ``flash_attention_blocks_bwd``
ONLY (what the forward's tables keep and the backward's walk visits,
against the triangle the causal kernels would run over those rows; a
band's kernels in the same program are not this metric's).  The
backward alone is 288 / 528 = 54.5 at L = 8192.  None for a program
without the counters, or one that built no such kernel."""

KERNELS = ("flash_attention_blocks", "flash_attention_blocks_bwd")


def read(facts):
    try:
        from mxnet_tpu.pallas.dispatch import (FLASH_BLOCKS_CAUSAL,
                                               FLASH_BLOCKS_WALKED)
    except ImportError:
        return None
    of = lambda counter: sum(c.value for c in counter.children()
                             if c.label_values[0] in KERNELS)
    causal = of(FLASH_BLOCKS_CAUSAL)
    if not causal:
        return None
    return 100.0 * of(FLASH_BLOCKS_WALKED) / causal
