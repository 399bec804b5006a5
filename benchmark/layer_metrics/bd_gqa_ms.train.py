"""Device milliseconds per step under the grouped-query attention's own
scopes in a block-diffusion pass (``gqa.proj``, ``gqa.norm``,
``gqa.blockdiff``: ``jax.named_scope`` names inside
``_contrib_GroupedQueryAttention``: the projections, the per-head q/k
norms WITH the rotation behind them (one checkpointed function: no
``gqa.rope`` in such a program), and the cores under the block-diffusion
mask), forward and backward.  The twin of ``gqa_ms.train`` for the
operator's other scopes.  None for a program without the scopes
(benchmark/dsa_time.py)."""
import dsa_time

SCOPES = ("gqa.proj", "gqa.norm", "gqa.blockdiff")


def read(facts):
    return dsa_time.scope_ms_per_step(facts, SCOPES)
