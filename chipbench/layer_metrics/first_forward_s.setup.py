"""Seconds of the eager pass that resolves a net's deferred shapes, before the
window opened, less the leaves initialised and the programs compiled inside
it: the program's process span ``gluon.first_forward`` less the
``gluon.param_init`` and ``jit.compile`` time it holds (0 where no shape is
deferred).
Layer: entry points.  Source: program span."""
from chipbench import setup_spans


def read(run):
    return setup_spans.seconds(run, "first_forward_s")
