"""Device time a step under the kernel scope ``causal_conv1d``, forward,
recomputed forward and backward, all blocks: the mixer's depthwise causal
convolution with its bias and SiLU.  Layer: ops and kernels.  Source: device
trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("causal_conv1d",))
