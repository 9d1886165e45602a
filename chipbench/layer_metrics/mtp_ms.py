"""Device time a step under the block key ``mtp``, forward and backward:
the multi-token-prediction module's projection and its one more block (its
share of the final norm, head and loss runs under their own keys).  Layer:
model step.  Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("mtp",))
