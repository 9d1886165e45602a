"""Seconds from a Block's parameters to a committed train state and a jitted
function, before the window opened: the program's process span
``fused_step.build``, whole (the copies and the optimizer's state, their
placement on the device, the program's wrapper).
Layer: entry points.  Source: program span."""
from chipbench import setup_spans


def read(run):
    return setup_spans.seconds(run, "step_build_s")
