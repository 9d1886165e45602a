"""Device time a step under the scopes ``moe_latent_down`` and
``moe_latent_up``, forward, recomputed forward and backward, all ``E``
layers: the projection of the tokens into the experts' latent and of this
chip's gated sum back to the full width.  Layer: ops and kernels.  Source:
device trace."""
from chipbench import named_time


def read(run):
    return named_time.ms_under(run, ("moe_latent_down", "moe_latent_up"))
