"""Seconds giving the net's leaves their values before the window opened: the
union of the program's process spans ``gluon.param_init`` (the zeros, the
draw, the write of one leaf), those of the eager shape-resolving pass
included.
Layer: entry points.  Source: program span."""
from chipbench import setup_spans


def read(run):
    return setup_spans.seconds(run, "param_init_s")
