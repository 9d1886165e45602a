"""The toy sizes go through the real configuration's code."""
from chipbench.configs.joyai_llm_flash import (  # noqa: F401
    build, flops_per_sample, make_batch, mla_attention_work,
    moe_experts_work, n_classes, reference, uniform_loss)
