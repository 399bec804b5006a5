"""Masked rows of a block-diffusion training step (docs/OBSERVABILITY.md).

``sym.contrib.DiffusionHead`` counts, inside the step's program, the
noised rows whose token was masked beside all of them;
``models/sdar_moe.py`` hands the pair out of the graph as one output,
(2,) int32.  The fused fit step keeps a reference to that output's
device array after each launch (:func:`note`: a reference, no read), as
it does for the experts' token counts (``telemetry/moe.py``).  The gauge
is filled WHEN READ: :func:`publish` is called by whoever wants the
number now.

* ``diffusion_masked_row_share``: masked rows over noised rows, last fit
  step: the share of the head's rows that carry a loss and a gradient
  (the noise draws a probability a block, so it moves from step to
  step round one half).
"""
from .registry import REGISTRY

__all__ = ["find", "note", "publish", "MASKED_ROW_SHARE", "ROWS_NODE",
           "ROWS_OUTPUT"]

ROWS_NODE = "diffusion_masked_rows"     # the node models/sdar_moe.py ends with
ROWS_OUTPUT = ROWS_NODE + "_output"

MASKED_ROW_SHARE = REGISTRY.gauge(
    "diffusion_masked_row_share", "noised rows of the last fit step whose "
    "token was masked (they carry the loss) over all noised rows",
    unit="ratio")

_last = None    # the (2,) int32 device array of the last step


def find(symbol):
    """The index of the row counts among the symbol's outputs, or None
    for a graph that hands none out.  Looked up once a fused step is
    built."""
    names = symbol.list_outputs()
    return names.index(ROWS_OUTPUT) if ROWS_OUTPUT in names else None


def note(rows):
    """Keep the last step's counts (the device array, unread)."""
    global _last
    _last = rows


def publish():
    """Fill the gauge from the last noted counts: a device-to-host read
    of (2,) int32.  Returns ``{"masked", "rows"}`` (ints), or None when
    no step noted any."""
    if _last is None:
        return None
    import numpy as np
    masked, rows = (int(n) for n in np.asarray(_last))
    MASKED_ROW_SHARE.set(masked / rows if rows else 0.0)
    return {"masked": masked, "rows": rows}
