"""Device time a step under the kernel scope ``moe_route``, forward,
recomputed forward and backward, all ``E`` layers: the gate matmul at the
full width over all experts, sigmoid, bias, top-k, the gates' normalisation
and the bias update.  Layer: ops and kernels.  Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("moe_route",))
