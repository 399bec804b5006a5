"""Host milliseconds per step between the end of the wait for the step
and the end of the metric's readback (``transfer1 - wait1`` of the
program's step timeline, mean over the window's steps that were read
back): the two scalar copies, after the step is done, for which the
chip waits.  Host clock only.  None for a program without the timeline
(benchmark/step_timeline.py)."""
import step_timeline


def read(facts):
    return step_timeline.read(facts, step_timeline.transfer_ms)
