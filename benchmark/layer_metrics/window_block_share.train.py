"""Percent of the causal 512 x 512 score blocks that the banded flash
kernels of the step's program execute, forward and backward, all window
layers: the program's trace-time counters ``flash_blocks_walked`` over
``flash_blocks_causal`` (what the forward's tables keep and the
backward's walk visits, against the triangle the causal kernels would
run in their place).  100 says the band skips nothing.  None for a
program without the counters, or one that built no banded kernel."""


def read(facts):
    try:
        from mxnet_tpu.pallas.dispatch import (FLASH_BLOCKS_CAUSAL,
                                               FLASH_BLOCKS_WALKED)
    except ImportError:
        return None
    causal = sum(c.value for c in FLASH_BLOCKS_CAUSAL.children())
    if not causal:
        return None
    return 100.0 * sum(c.value for c in FLASH_BLOCKS_WALKED.children()) \
        / causal
