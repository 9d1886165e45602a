"""The chunked state-space scan of the pattern's ``M`` layers against its
roofline: the least time the chip could take for the scan's work a step (the
configuration's ``ssm_scan_work``: the chunked form's four products at the
published chunk, heads, groups and state, the masked half of a diagonal
block, padding and anything run twice not counted; x, B, C, y, Δ and their
gradients moved once) over the device time a step under the kernel scope
``ssd_scan``, forward, recomputed forward and backward together, whichever
side of the dispatch runs.  Layer: ops and kernels.  Source: device trace."""
from chipbench import named_time


def read(run):
    return named_time.roofline_pct(
        run, named_time.config_work(run, "ssm_scan_work"), ("ssd_scan",))
