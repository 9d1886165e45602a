"""Seconds of the cast walk that puts the net in the dtype it is trained in,
before the window opened: the program's process span ``amp.convert_block``.
Layer: entry points.  Source: program span."""
from chipbench import setup_spans


def read(run):
    return setup_spans.seconds(run, "amp_convert_s")
