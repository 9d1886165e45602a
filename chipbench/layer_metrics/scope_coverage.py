"""Busy device time in instructions whose ``op_name`` resolves to a phase and
at least one block or kernel segment / busy device time: the tracing's own
ratio of useful outcomes to attempts.  Layer: ops and kernels.  Source: device
trace."""
from chipbench import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    if not reduced or reduced["coverage"] is None:
        return None
    return 100.0 * reduced["coverage"]
