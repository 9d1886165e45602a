"""The held experts' grouped ungated feed-forward in the latent against its
roofline: the least time the chip could take for the model's work a step (the
configuration's ``latent_moe_experts_work``: two products a held row under
balanced load, the weights read once a pass, never the buffer's padding or
what is run again) over the device time a step under the kernel scope
``moe_experts``, forward, recomputed forward and backward.  Layer: ops and
kernels.  Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.roofline_pct(
        run, named_time.config_work(run, "latent_moe_experts_work"),
        ("moe_experts",))
