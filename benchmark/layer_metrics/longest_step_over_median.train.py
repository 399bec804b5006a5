"""The window's longest step over its median step, both from one entry
of ``FusedFitStep.step`` to the next (the program's step timeline; the
window's last step, which no entry has ended, left out): 1.0x in a clean
window, 10x and more in one that met a stall, whose other per-layer
numbers are then a stalled run's.  None for a program without the
timeline (benchmark/step_timeline.py)."""
import step_timeline


def read(facts):
    return step_timeline.read(facts, step_timeline.longest_over_median)
