"""Device time a step of the instructions whose ``op_name`` lies under the phase scope ``optimizer``: the update and the merge of the moving statistics
(means over the mesh's devices; a fusion counts whole for its root's name, so
what XLA fuses into another phase's instruction is counted there).  Layer:
model step.  Source: device trace."""
from chipbench import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    if not reduced or reduced["phase_ms"] is None:
        return None
    return reduced["phase_ms"]["optimizer"]
