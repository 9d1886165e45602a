"""Device time a step under the block key ``mamba``, forward, recomputed
forward and backward, all blocks: the whole state-space branch — its two
projections, the convolution, the scan and the gated grouped norm (the
multipliers around it and the sum with attention's branch run under the
block's own key).  Layer: model step.  Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("mamba",))
