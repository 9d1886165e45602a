"""Device time a step under the block key ``mtp``, forward, recomputed
forward and backward: the multi-token-prediction module's projection, its
body of an attention layer and a routed layer, and its own final norm (its
share of the head and loss runs under their own keys).  Layer: model step.
Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("mtp",))
