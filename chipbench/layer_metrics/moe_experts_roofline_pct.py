"""The held experts' grouped SwiGLU against its roofline: the least time
the chip could take for the model's work a step (the configuration's
``moe_experts_work``: real rows under balanced load, never the buffer's
padding or a recomputation) over the device time a step under the kernel
scope ``moe_experts``, forward and backward.  Layer: ops and kernels.
Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.roofline_pct(
        run, named_time.config_work(run, "moe_experts_work"),
        ("moe_experts",))
