"""Operator library: registry + op definitions lowering to XLA/Pallas.

TPU-native counterpart of the reference's ``src/operator`` (~200 kLoC of
C++/CUDA kernels behind an NNVM registry — SURVEY.md §2.1).  Here each op
is a pure JAX function registered with metadata (name, aliases,
differentiability); "FCompute" becomes "emit XLA" and the backward pass is
derived with ``jax.vjp`` instead of hand-registered FGradient nodes.
"""
from .registry import (
    Op,
    register,
    get_op,
    list_ops,
    invoke,
    clear_caches,
    cache_stats,
)
from . import bulking  # noqa: F401  (lazy eager segments / op bulking)
from . import elemwise  # noqa: F401  (registration side effects)
from . import reduce_ops  # noqa: F401
from . import shape_ops  # noqa: F401
from . import index_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import moe_ops  # noqa: F401  (rope, moe_route, moe_ffn)
from . import ssm_ops  # noqa: F401  (causal_conv1d, ssd_scan)
from . import linalg_ops  # noqa: F401
from . import random_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import control_flow  # noqa: F401
from . import sort_ops  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import quantization_ops  # noqa: F401
from . import sparse_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import image_ops  # noqa: F401
from . import init_ops  # noqa: F401
from . import ref_aliases  # noqa: F401  (must import LAST: aliases
#                            resolve against every registered op above)

# Python-callback custom op (reference src/operator/custom/): op named
# "Custom" with op_type kwarg, matching nd.Custom(..., op_type=...)
from ..operator import custom as _custom_invoke


@register("Custom", bulkable=False)  # user callbacks may be impure:
def Custom(*inputs, op_type=None, **kwargs):  # never defer them
    return _custom_invoke(*inputs, op_type=op_type, **kwargs)
