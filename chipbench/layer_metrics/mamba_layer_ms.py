"""Device time a step under the block key ``mamba``, forward, recomputed
forward and backward, all ``M`` layers: the whole Mamba-2 mixer — its two
projections, the convolution, the scan and the gated grouped norm (the
layer's own norm and the residual sum run under the layer's key).  Layer:
model step.  Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("mamba",))
