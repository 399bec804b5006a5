"""Device milliseconds per step under the grouped-query attention's own
scopes (``gqa.proj``, ``gqa.rope``, ``gqa.window``, ``gqa.full``:
``jax.named_scope`` names inside ``_contrib_GroupedQueryAttention``: the
projections, rotary position, and the cores of the window layers and of
the full ones), forward and backward.  None for a program without the
scopes (benchmark/dsa_time.py)."""
import dsa_time

SCOPES = ("gqa.proj", "gqa.rope", "gqa.window", "gqa.full")


def read(facts):
    return dsa_time.scope_ms_per_step(facts, SCOPES)
