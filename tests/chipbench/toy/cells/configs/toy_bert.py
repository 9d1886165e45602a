"""The toy sizes go through the real configuration's code."""
from chipbench.configs.bert_base import (  # noqa: F401
    build, flops_per_sample, make_batch, n_classes)
