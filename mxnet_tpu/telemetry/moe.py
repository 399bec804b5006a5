"""Per-expert load of the dropless expert layer (docs/OBSERVABILITY.md).

``sym.contrib.RoutedExperts`` counts, inside the step's program, the
tokens each expert got; ``models/zaya.py`` and ``models/qwen3_next.py``
hand the counts of every layer out of the graph as one output, (layers,
experts) int32.  The
fused fit step keeps a reference to that output's device array after
each launch (:func:`note`: a reference, no read), so a step costs no
host sync for it.  The gauges here are filled WHEN READ, from the last
noted counts: :func:`publish` is called by ``Module._fit_sync`` (an
existing sync boundary, like ``publish_sentinels``) and by whoever wants
the numbers now (the benchmark's readers, also after the module is
gone: the counts are a few hundred bytes and outlive it).

* ``moe_expert_tokens{layer,expert}``: tokens the expert got in the
  last step, for every expert the router scores.  With ``top_k`` > 1
  the counts are of (token, choice) pairs, ``top_k`` a token
  (``models/qwen3_next.py``), and so is every number below;
* ``moe_tokens_away{layer}``: tokens of that step whose expert is not
  held by this chip (they add 0 to the expert sublayer's result);
* ``moe_expert_load_max_over_mean``: over the held experts of all
  layers, the fullest expert's tokens over the mean: 1.0 is even
  routing, ``held experts`` is every token on one expert;
* ``moe_layers_over_size``: layers of the last step whose held pairs
  passed the smaller size of the sorted rows' buffer
  (``parallel.moe._row_buckets`` of the node's ``top_k`` and
  ``rows_slack``), so ran them a slab at a time and computed each slab
  again on the way back: 0 while the routing stays near its expected
  count, and always 0 with one size (``top_k`` 1).
"""
from .registry import REGISTRY

__all__ = ["find", "note", "publish", "EXPERT_TOKENS", "TOKENS_AWAY",
           "LOAD_MAX_OVER_MEAN", "LAYERS_OVER_SIZE", "COUNTS_NODE",
           "COUNTS_OUTPUT"]

COUNTS_NODE = "moe_expert_tokens"       # the node models/zaya.py ends with
COUNTS_OUTPUT = COUNTS_NODE + "_output"
_OP = "_contrib_RoutedExperts"

EXPERT_TOKENS = REGISTRY.gauge(
    "moe_expert_tokens", "tokens an expert got in the last fit step, "
    "labeled by `layer` and `expert` (all experts the router scores)",
    unit="tokens")
TOKENS_AWAY = REGISTRY.gauge(
    "moe_tokens_away", "tokens of the last fit step routed to an expert "
    "this chip does not hold, labeled by `layer`", unit="tokens")
LOAD_MAX_OVER_MEAN = REGISTRY.gauge(
    "moe_expert_load_max_over_mean", "fullest held expert's tokens over "
    "the mean of the held experts, all layers, last fit step",
    unit="ratio")
LAYERS_OVER_SIZE = REGISTRY.gauge(
    "moe_layers_over_size", "layers of the last fit step whose held "
    "pairs passed the smaller size of the sorted rows' buffer (they ran "
    "a slab at a time, each slab again on the way back)", unit="layers")

_last = None    # (counts device array, held_first, held_count, top_k, slack)


def find(symbol):
    """``(index of the counts among the symbol's outputs, held_first,
    held_count, top_k, rows_slack)`` for a graph that hands out its
    experts' token counts, else None.  Looked up once a fused step is
    built."""
    names = symbol.list_outputs()
    if COUNTS_OUTPUT not in names:
        return None
    for node in symbol._topo():
        if node.op is not None and node.op.name == _OP:
            first = int(node.attrs.get("held_first", 0))
            count = node.attrs.get("held_count")
            return (names.index(COUNTS_OUTPUT), first,
                    int(node.attrs["num_experts"]) - first
                    if count is None else int(count),
                    int(node.attrs.get("top_k", 1)),
                    float(node.attrs.get("rows_slack", 1.25)))
    return None


def note(counts, held_first, held_count, top_k=1, rows_slack=1.25):
    """Keep the last step's counts (the device array, unread) and what
    sizes the layers' buffer."""
    global _last
    _last = (counts, held_first, held_count, top_k, rows_slack)


def publish():
    """Fill the gauges from the last noted counts.  A device-to-host
    read of (layers, experts) int32: at a sync boundary or when the
    numbers are wanted, never per step.  Returns ``{"counts",
    "held_first", "held_count"}``, or None when no step noted any."""
    if _last is None:
        return None
    import numpy as np
    from ..parallel.moe import _row_buckets
    dev, first, n, k, slack = _last
    counts = np.asarray(dev)
    here = counts[:, first:first + n]
    for layer, row in enumerate(counts):
        for expert, tokens in enumerate(row):
            EXPERT_TOKENS.labels(layer=layer, expert=expert).set(int(tokens))
        TOKENS_AWAY.labels(layer=layer).set(
            int(row.sum() - here[layer].sum()))
    mean = float(here.mean())
    LOAD_MAX_OVER_MEAN.set(float(here.max()) / mean if mean > 0 else 0.0)
    # every token has k pairs somewhere: a layer's counts say the tokens
    size = _row_buckets(int(counts[0].sum()) // k, k, n, counts.shape[1],
                        slack)[0]
    LAYERS_OVER_SIZE.set(int((here.sum(axis=1) > size).sum()))
    return {"counts": counts, "held_first": first, "held_count": n}
