"""Device time a step of the instructions a checkpointed layer runs again
in the backward pass, in a stack whose layers differ in kind: those whose
``op_name`` holds the segment ``rematted_computation``, which jax writes
below the ``checkpoint`` of a block under ``HybridBlock.recompute()``.  Layer:
model step.  Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("rematted_computation",))
