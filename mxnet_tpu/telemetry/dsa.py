"""Live score tiles of the sparse indexed attention (docs/OBSERVABILITY.md).

``sym.contrib.SparseIndexedAttention`` counts, inside the step's
program, the ``q_chunk`` x ``kv_chunk`` score tiles in which at least
one (query, key) pair was chosen, beside the tiles on or under the
diagonal; ``models/keye_vl2.py`` hands every layer's pair out of the
graph as one output, (layers, 2) int32.  The fused fit step keeps a
reference to that output's device array after each launch
(:func:`note`: a reference, no read), as it does for the experts' token
counts (``telemetry/moe.py``).  The gauge is filled WHEN READ:
:func:`publish` is called by whoever wants the number now.

* ``dsa_live_block_share``: live tiles over causal tiles, all layers,
  last fit step.  1.0 says a kernel that skips dead tiles would skip
  none; the chosen PAIRS' share of the causal pairs is fixed by the
  sequence and ``topk`` and says nothing about where they fall.
"""
from .registry import REGISTRY

__all__ = ["find", "note", "publish", "LIVE_BLOCK_SHARE", "TILES_NODE",
           "TILES_OUTPUT"]

TILES_NODE = "dsa_live_tiles"           # the node models/keye_vl2.py ends with
TILES_OUTPUT = TILES_NODE + "_output"

LIVE_BLOCK_SHARE = REGISTRY.gauge(
    "dsa_live_block_share", "score tiles of the sparse indexed attention "
    "that hold a chosen (query, key) pair over the tiles on or under the "
    "diagonal, all layers, last fit step", unit="ratio")

_last = None    # the (layers, 2) int32 device array of the last step


def find(symbol):
    """The index of the tile counts among the symbol's outputs, or None
    for a graph that hands none out.  Looked up once a fused step is
    built."""
    names = symbol.list_outputs()
    return names.index(TILES_OUTPUT) if TILES_OUTPUT in names else None


def note(tiles):
    """Keep the last step's counts (the device array, unread)."""
    global _last
    _last = tiles


def publish():
    """Fill the gauge from the last noted counts: a device-to-host read
    of (layers, 2) int32.  Returns ``{"live", "causal"}`` (ints, all
    layers), or None when no step noted any."""
    if _last is None:
        return None
    import numpy as np
    live, causal = (int(n) for n in np.asarray(_last).sum(axis=0))
    LIVE_BLOCK_SHARE.set(live / causal if causal else 0.0)
    return {"live": live, "causal": causal}
