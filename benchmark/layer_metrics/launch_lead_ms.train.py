"""Milliseconds per step of the device's gap that neither the readback's
transfers, nor the caller's loop, nor ``fit.prepare`` holds: for each
pair of consecutive fit-step programs on the device's ``XLA Modules``
line, the device's idle time between them (device clock) less
``dispatch0[n+1] - wait1[n]`` of the program's step timeline (host
clock), mean over the pairs whose first step was read back.  Each
difference is taken inside one clock, so the clocks' offset cancels.  It
is the wake-up of the wait plus the jit call's part before the program
starts: the part of ``fit_dispatch_ms.train`` that holds the chip.  None
for a program without the timeline, a trace without device programs or
a window of one step (benchmark/step_timeline.py)."""
import step_timeline


def read(facts):
    return step_timeline.read_launch_lead(facts)
