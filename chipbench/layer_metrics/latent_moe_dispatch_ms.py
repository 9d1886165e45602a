"""Device time a step under the kernel scope ``moe_dispatch``, forward,
recomputed forward and backward, all ``E`` layers: ranking the assignments
by expert, gathering the held experts' latent rows into the buffer and
combining the results back by token.  Layer: ops and kernels.  Source: device
trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("moe_dispatch",))
