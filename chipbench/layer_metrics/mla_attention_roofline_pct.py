"""Latent attention's kernel pair against its roofline: the least time the
chip could take for causal attention's work a step (the configuration's
``mla_attention_work``: the score-sized products at half the square, 192
wide in q and k and 128 in v, not the probabilities computed again) over
the device time a step under the kernel scope ``flash_attention``, forward
and backward together.  Layer: ops and kernels.  Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.roofline_pct(
        run, named_time.config_work(run, "mla_attention_work"),
        ("flash_attention",))
