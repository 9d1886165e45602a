"""The attention layers' kernel pair against its roofline, in a stack whose
other layers are mixers and experts: the least time the chip could take for
causal attention's work a step (the configuration's ``gqa_attention_work``:
the two forward and four backward score-sized products at half the square,
not the probabilities computed again nor the layer run twice; keys and values
moved once a group) over the device time a step under the kernel scope
``flash_attention``, forward, recomputed forward and backward together, the
repeat of the key heads included.  Layer: ops and kernels.  Source: device
trace."""
from chipbench import named_time


def read(run):
    return named_time.roofline_pct(
        run, named_time.config_work(run, "gqa_attention_work"),
        ("flash_attention",))
