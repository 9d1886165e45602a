"""Seconds placing parameters, aux state, optimizer state and key on the
device, before the window opened: the program's process span
``fused_step.place``, a part of ``step_build_s.setup``.  Host time until
``jax.device_put`` returns; a copy still in flight lands in the first call.
Layer: entry points.  Source: program span."""
from chipbench import setup_spans


def read(run):
    return setup_spans.seconds(run, "state_place_s")
