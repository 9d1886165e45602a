"""incubator_mxnet_tpu — a TPU-native deep learning framework.

A from-scratch re-design of the capabilities of Apache MXNet (incubating)
for TPU hardware: JAX/XLA is the kernel generator and async runtime,
``jax.sharding`` + ``shard_map`` over a device ``Mesh`` is the distribution
substrate, and Pallas provides hand-written TPU kernels for the hot paths.

The public API mirrors the reference framework's Python surface
(``mx.nd``, ``mx.sym``, ``mx.gluon``, ``mx.autograd``, ``mx.optimizer``,
``mx.kvstore``, ``mx.io``) so that users of the reference can switch with
minimal friction, while the internals are idiomatic TPU-first designs —
not a port.  Reference: /root/reference (Apache MXNet), surveyed in
SURVEY.md at the repo root.

Typical use::

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, autograd, gluon

    x = nd.ones((2, 3), ctx=mx.tpu())
    with autograd.record():
        y = (x * 2).sum()
    y.backward()
"""

# first: this module's import reads the clock the process trace starts
# at (trace.py), before JAX or anything else of the package is imported
from . import trace
from .libinfo import __version__  # single source of truth


def _join_distributed_from_env():
    """Join the multi-process coordination service when launched by
    tools/launch.py (MXT_COORDINATOR / MXT_NUM_WORKERS / MXT_WORKER_ID —
    the role the ps-lite scheduler env plays for ``import mxnet`` in the
    reference).  Must run before ANY jax backend touch, hence at the top
    of the package import; PS-transport workers (MXT_SERVERS set) don't
    need a jax-level process group.
    """
    import os
    n = int(os.environ.get("MXT_NUM_WORKERS", "1"))
    coord = os.environ.get("MXT_COORDINATOR")
    if n <= 1 or not coord or os.environ.get("MXT_SERVERS"):
        return
    if os.environ.get("MXT_WORKER_ID_FROM_MPI") and \
            "MXT_WORKER_ID" not in os.environ:
        # mpi launcher (tools/launch.py launch_mpi): rank-dependent vars
        # can't ride mpirun -x, so derive the id from the MPI/PMI env
        for var in ("OMPI_COMM_WORLD_RANK", "PMIX_RANK", "PMI_RANK",
                    "SLURM_PROCID"):
            if var in os.environ:
                os.environ["MXT_WORKER_ID"] = os.environ[var]
                break
        else:
            raise RuntimeError(
                "MXT_WORKER_ID_FROM_MPI is set but no MPI rank variable "
                "(OMPI_COMM_WORLD_RANK/PMIX_RANK/PMI_RANK/SLURM_PROCID) "
                "is present")
    import jax
    try:
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=n,
            process_id=int(os.environ["MXT_WORKER_ID"]))
    except RuntimeError:
        pass  # backend already up (user initialized it themselves)


_join_distributed_from_env()


def _install_fork_handlers():
    """Fork safety for multiprocessing DataLoader workers (reference
    src/initialize.h:39-86 LibraryInitializer fork handlers): a forked
    child must not inherit the parent's engine lock state or reuse its
    PRNG stream."""
    import os

    def _after_fork_child():
        try:
            from . import engine
            engine.reset_engine()
        except Exception:  # mxlint: allow-broad-except(post-fork reinit is best-effort; a failure must not kill the child)
            pass
        try:
            from . import random as _random
            _random.seed(int.from_bytes(os.urandom(4), "little"))
        except Exception:  # mxlint: allow-broad-except(post-fork reseed is best-effort; a failure must not kill the child)
            pass

    if hasattr(os, "register_at_fork"):
        os.register_at_fork(after_in_child=_after_fork_child)


_install_fork_handlers()

from . import base
from .base import MXNetError
from . import error
from . import fault
from . import libinfo
from . import log
from . import checkpoint
from .context import (Context, cpu, gpu, tpu, current_context, num_gpus,
                      num_tpus, gpu_memory_info, tpu_memory_info,
                      memory_summary)
from . import engine
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd
from . import random
from . import initializer
from . import init  # alias namespace like mx.init
from . import optimizer
from .optimizer import lr_scheduler
from . import symbol
from . import symbol as sym
from . import model
from . import module
from . import module as mod
from . import gluon
from . import kvstore
from . import kvstore as kv
from . import io
from . import recordio
from . import image
from . import parallel
from . import models
from . import profiler
from . import runtime
from . import amp
from . import contrib
from . import operator
from . import subgraph
from . import numpy as np  # mx.np NumPy-compatible namespace
from . import numpy_extension as npx
from . import callback
from . import monitor
from . import visualization as viz
from . import test_utils
from . import util
from . import library
from . import rtc
from . import executor_cache
from . import deploy
from . import serving
from .util import is_np_array, set_np, reset_np
from .attribute import AttrScope
from .name import NameManager

# Convenience re-exports matching the reference's top level (mx.nd.array,
# mx.metric, ...).
from .gluon import metric


def tpu_context_available():
    """True when a real TPU backend is attached to this process."""
    return num_tpus() > 0


# last: the package's import, from trace.py's clock reading to here
trace.record_process_span("process.import",
                          jax_preloaded=trace.JAX_PRELOADED)
