"""Device time a step of the instructions whose ``op_name`` lies under ``transpose(jvp(forward))``: the backward pass, which has no scope of its own
(means over the mesh's devices; a fusion counts whole for its root's name, so
what XLA fuses into another phase's instruction is counted there).  Layer:
model step.  Source: device trace."""
from chipbench import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    if not reduced or reduced["phase_ms"] is None:
        return None
    return reduced["phase_ms"]["backward"]
