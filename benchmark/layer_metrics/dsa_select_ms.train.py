"""Device milliseconds per step under scope ``dsa.select``: the choice
of each query's keys from its row of scorer scores (the ``topk``-th
largest by bisection, the tie rule, the mask packed to bits, the live
tiles counted); forward only, the backward pass reads the bits.  None
for a program without the scope (benchmark/dsa_time.py)."""
import dsa_time


def read(facts):
    return dsa_time.scope_ms_per_step(facts, ("dsa.select",))
