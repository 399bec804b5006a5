"""The index scorer's S x S work as a share of its roofline, in
percent: the larger of its FLOPs over the chip's bf16 peak and its bytes
over its memory bandwidth, for a step (the configuration's reference
module gives ``dsa_indexer_flops(kwargs)``: the heads' products over
every causal pair forward, over the chosen pairs backward, no recompute;
and ``dsa_indexer_bytes(kwargs)``), over the device time a step spends
under scopes ``dsa.indexer`` (the scorer's projections and its scores,
forward) and ``dsa.index_loss`` (the loss and the scorer's backward).
The scorer is float32: against the bf16 peak its products cost several
passes each, and that shows here.  None for a program without the scopes
or a reference without the counts (benchmark/dsa_time.py)."""
import dsa_time


def read(facts):
    return dsa_time.roofline_share(facts, ("dsa.indexer", "dsa.index_loss"),
                                   "dsa_indexer_flops", "dsa_indexer_bytes")
