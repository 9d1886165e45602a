"""Median over the traced steps of the program's span ``fused_step.call``
less the part its child ``executor.call`` covers: the framework's own Python
around the jitted call, the PRNG key split (a jitted program of its own)
included.  From the host plane of the traced run's ``.xplane.pb``.  Layer:
entry points.  Source: program span."""
from chipbench import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    return reduced and reduced["call_self_ms"]
