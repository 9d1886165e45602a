"""The toy sizes go through the real configuration's code."""
from chipbench.configs.nemotron3_super_120b import (  # noqa: F401
    build, flops_per_sample, gqa_attention_work, latent_moe_experts_work,
    make_batch, n_classes, reference, ssm_scan_work, uniform_loss)
