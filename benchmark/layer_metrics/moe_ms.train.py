"""Device milliseconds per step under operator class
``_contrib_RoutedExperts`` (router, top-1, sort and gather, the three
grouped products, weighted scatter), forward and backward.  None for a
program without the operator (benchmark/operator_time.py)."""
import operator_time


def read(facts):
    return operator_time.op_ms_per_step(facts, "_contrib_RoutedExperts")
