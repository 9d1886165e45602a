"""Model FLOP/s utilisation: the FLOPs the forward and backward passes
require per sample (the configuration's ``flops_per_sample``) x samples/s
over chips x the published bf16 peak.  An end-to-end utilisation, not a
kernel's roofline share.  Layer: model step.  Source: host clock."""
from chipbench import stats


def read(run):
    if not run["peaks"]:
        return None
    return (100.0 * run["flops_per_sample"] * stats.samples_per_s(run)
            / (run["chips"] * run["peaks"]["bf16_flops_per_s"]))
