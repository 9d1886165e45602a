"""Device time a step of the instructions whose ``op_name`` lies under the phase scope ``forward`` and not under a ``transpose(`` of it: the forward pass
(means over the mesh's devices; a fusion counts whole for its root's name, so
what XLA fuses into another phase's instruction is counted there).  Layer:
model step.  Source: device trace."""
from chipbench import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    if not reduced or reduced["phase_ms"] is None:
        return None
    return reduced["phase_ms"]["forward"]
