"""Host milliseconds per step between the end of the metric's readback
and the next entry of ``FusedFitStep.step`` (``next_entry - transfer1``
of the program's step timeline; ``next_entry - rebind1`` in a step that
was not read back; mean over the window's steps that an entry ended):
``metric.reset``, the caller's loop and its placing of the next batch,
the part of the device's gap under no span of the program.  None for a
program without the timeline (benchmark/step_timeline.py)."""
import step_timeline


def read(facts):
    return step_timeline.read(facts, step_timeline.outside_ms)
