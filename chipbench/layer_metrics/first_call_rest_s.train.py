"""Seconds of the step's first call that are neither tracing, lowering nor
backend compile (or cache read): the program's process span
``fused_step.first_call`` less the step's own ``jit.compile`` record -- the
key split, the analyses, the executable's load and the dispatch of step 1.
Layer: jit choke point.  Source: program span."""
from chipbench import setup_spans


def read(run):
    return setup_spans.seconds(run, "first_call_rest_s")
