"""Device context abstraction.

TPU-native counterpart of the reference ``Context`` (include/mxnet/base.h:90-116
and python/mxnet/context.py).  A ``Context`` names a logical device
(``cpu()``, ``gpu()``, ``tpu()``); it resolves lazily to a concrete JAX
device.  ``tpu()`` and ``gpu()`` name *the accelerator*, which is the
process's default JAX backend: the TPU chips wherever JAX has a TPU
backend, and the CPU devices only in a process whose default backend is
the CPU (the test harness runs under ``JAX_PLATFORMS=cpu``), so code
written for ``tpu()`` runs there unchanged (the ``check_consistency``
bridge — reference python/mxnet/test_utils.py:1428).  A process that has
a TPU backend cannot land on the CPU through ``tpu()``; a backend that
fails to initialize raises, and so does a ``device_id`` beyond the
devices present.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus",
           "num_tpus", "gpu_memory_info", "tpu_memory_info",
           "memory_summary", "child_tpu_chips"]

_context_stack = threading.local()


def _local(devs):
    """Only this process's devices: in multi-controller mode
    (jax.distributed) an array must live on an addressable device."""
    mine = [d for d in devs if d.process_index == jax.process_index()]
    return mine or list(devs)


def _count(platform: str) -> int:
    return sum(d.platform == platform for d in _local(jax.devices()))


class Context:
    """A logical device: ``Context('tpu', 0)``.

    devtypes mirror the reference enum (cpu=1, gpu=2, cpu_pinned=3,
    cpu_shared=5) with tpu added as the first-class accelerator type.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devstr2type:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ---------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devstr2type[self.device_type]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- resolution to a physical JAX device ------------------------------
    @property
    def jax_device(self):
        """The concrete jax.Device this context maps to.

        cpu→the host's CPU devices; tpu/gpu→the default backend's
        devices (so reference scripts that say ``mx.gpu(0)`` run on the
        TPU chip).  Never wraps: ``tpu(3)`` on a one-chip host raises.
        """
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = _local(jax.devices("cpu"))
        else:
            devs = _local(jax.devices())
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self}: device_id {self.device_id} is out of range — "
                f"this process has {len(devs)} {devs[0].platform} "
                f"device(s)")
        return devs[self.device_id]

    def empty_cache(self):
        """Release cached device memory back to the platform.

        The reference frees the GPU pool (storage per-device release);
        under PJRT, buffers are freed eagerly when unreferenced, so this
        only triggers a GC-level sweep.
        """
        import gc

        gc.collect()

    def __enter__(self):
        if not hasattr(_context_stack, "contexts"):
            _context_stack.contexts = []
        _context_stack.contexts.append(self)
        return self

    def __exit__(self, *exc):
        _context_stack.contexts.pop()


def current_context() -> Context:
    """The innermost ``with ctx:`` context, defaulting to cpu(0).

    Matches reference semantics (python/mxnet/context.py current_context):
    default context is cpu; ops placed explicitly via ctx args.
    """
    stack = getattr(_context_stack, "contexts", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    return _count("gpu")


def num_tpus() -> int:
    return _count("tpu")


def child_tpu_chips(env=None):
    """How many TPU chips a subprocess started with ``env`` (default:
    this process's environment) finds, or None where its backend is
    not a TPU.  A chip belongs to one process at a time, so a parent
    that hands chips to children (serving replicas, launched workers)
    must not ask JAX itself: the question goes to a short-lived child.
    ``JAX_PLATFORMS=cpu`` answers without one."""
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() == "cpu":
        return None
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
        env=dict(env), capture_output=True, text=True, timeout=300)
    if probe.returncode != 0:
        raise RuntimeError(
            "a subprocess cannot initialize its JAX backend (does this "
            "process, or another one, hold the chip?): "
            + " | ".join(probe.stderr.strip().splitlines()[-3:]))
    platform, count = probe.stdout.split()[-2:]
    return int(count) if platform == "tpu" else None


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes on an accelerator (reference
    python/mxnet/context.py:279 gpu_memory_info over cudaMemGetInfo).

    TPU mapping: PJRT ``device.memory_stats()`` — the HBM-pool statistics
    the reference's GPUPooledStorageManager tracked (SURVEY.md §2.1
    storage row).  Falls back to (0, 0) on backends that expose no
    stats (the virtual-CPU test harness).
    """
    devs = [d for d in _local(jax.devices()) if d.platform != "cpu"] \
        or _local(jax.devices())
    if not 0 <= device_id < len(devs):
        raise ValueError(
            f"device_id {device_id} out of range (have {len(devs)})")
    dev = devs[device_id]
    stats = dev.memory_stats() or {}
    total = stats.get("bytes_limit", 0)
    used = stats.get("bytes_in_use", 0)
    return (total - used, total)


def tpu_memory_info(device_id: int = 0):
    return gpu_memory_info(device_id)


def memory_summary(device_id: int = 0):
    """Human-readable device-memory report (the storage-profiler hook of
    reference storage_profiler.cc, surfaced Python-side)."""
    devs = _local(jax.devices())
    if not 0 <= device_id < len(devs):
        raise ValueError(
            f"device_id {device_id} out of range (have {len(devs)})")
    dev = devs[device_id]
    stats = dev.memory_stats() or {}
    lines = [f"device {dev}"]
    for k in sorted(stats):
        lines.append(f"  {k}: {stats[k]}")
    if not stats:
        lines.append("  (backend exposes no memory statistics)")
    return "\n".join(lines)
