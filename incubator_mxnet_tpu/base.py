"""Base utilities: errors, dtype tables, registries.

TPU-native counterpart of the reference's ``python/mxnet/base.py`` (ctypes
library loading is replaced by direct JAX usage — there is no dlopen step)
and of dmlc-core's parameter/registry machinery.
"""
from __future__ import annotations

import os
import threading
import numpy as onp

import jax.numpy as jnp
from .locks import named_lock

__all__ = [
    "MXNetError",
    "classproperty",
    "string_types",
    "numeric_types",
    "integer_types",
    "dtype_np_to_jax",
    "dtype_from_any",
    "dtype_name",
    "registry",
]

string_types = (str,)
numeric_types = (float, int, onp.generic)
integer_types = (int, onp.integer)


class MXNetError(RuntimeError):
    """Framework error type (reference: python/mxnet/base.py MXNetError)."""


class classproperty:
    def __init__(self, f):
        self.f = f

    def __get__(self, obj, owner):
        return self.f(owner)


# ---------------------------------------------------------------------------
# dtypes
# ---------------------------------------------------------------------------

# Canonical dtype table.  The reference enumerates dtypes in
# include/mxnet/base.h via mshadow type flags; here the canonical identity is
# the numpy dtype object and bfloat16 is first-class (TPU native compute type).
_DTYPE_NAMES = {
    "float32": jnp.float32,
    "float64": jnp.float64,
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "uint8": jnp.uint8,
    "uint16": jnp.uint16,
    "uint32": jnp.uint32,
    "uint64": jnp.uint64,
    "int8": jnp.int8,
    "int16": jnp.int16,
    "int32": jnp.int32,
    "int64": jnp.int64,
    "bool": jnp.bool_,
}


def dtype_np_to_jax(dtype):
    return jnp.dtype(dtype)


def dtype_from_any(dtype):
    """Accept a string name, numpy dtype, python type, or jax dtype."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        if dtype not in _DTYPE_NAMES:
            raise TypeError(f"unknown dtype name {dtype!r}")
        return jnp.dtype(_DTYPE_NAMES[dtype])
    return jnp.dtype(dtype)


def dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


# ---------------------------------------------------------------------------
# Generic name->object registry (reference: dmlc Registry / mxnet.registry)
# ---------------------------------------------------------------------------

class _Registry:
    """A simple name registry with alias support.

    Mirrors the role of ``python/mxnet/registry.py`` in the reference: a
    decorator-based name→class table used for optimizers, initializers,
    metrics, losses and data iterators.
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: dict[str, object] = {}
        self._lock = named_lock("base.registry")

    def register(self, obj=None, name: str | None = None):
        def do(o):
            key = (name or o.__name__).lower()
            with self._lock:
                self._entries[key] = o
            return o

        if obj is None:
            return do
        return do(obj)

    def alias(self, *names):
        def do(o):
            with self._lock:
                for n in names:
                    self._entries[n.lower()] = o
            return o

        return do

    def get(self, name: str):
        try:
            return self._entries[name.lower()]
        except KeyError:
            raise KeyError(
                f"{self.kind} {name!r} is not registered "
                f"(known: {sorted(self._entries)})"
            ) from None

    def find(self, name: str):
        return self._entries.get(name.lower())

    def create(self, name, *args, **kwargs):
        if isinstance(name, str):
            return self.get(name)(*args, **kwargs)
        return name  # already an instance

    def list(self):
        return sorted(self._entries)

    def __contains__(self, name):
        return name.lower() in self._entries


_REGISTRIES: dict[str, _Registry] = {}


def registry(kind: str) -> _Registry:
    if kind not in _REGISTRIES:
        _REGISTRIES[kind] = _Registry(kind)
    return _REGISTRIES[kind]


def get_env(name: str, default, dtype=str):
    """dmlc::GetEnv equivalent: typed environment variable lookup.

    The reference reads ~90 MXNET_* env vars at point of use
    (docs/static_site/src/pages/api/faq/env_var.md); we honour the same
    convention under both MXNET_* and MXTPU_* prefixes.
    """
    for candidate in (name, name.replace("MXNET_", "MXTPU_")):
        val = os.environ.get(candidate)
        if val is not None:
            if dtype is bool:
                return val not in ("0", "false", "False", "")
            return dtype(val)
    return default


def resolve_chunk_steps(chunk_steps=None):
    """K for the whole-loop-compiled training path (fuse_loop.py):
    an explicit value wins, else ``MXNET_TRAIN_CHUNK_STEPS`` (default
    1 — the per-step fused path).  Single point of truth for the env
    fallback and the >= 1 validation shared by Trainer,
    FusedTrainStep, ChunkedTrainLoop and DevicePrefetchRing."""
    k = int(chunk_steps if chunk_steps is not None
            else get_env("MXNET_TRAIN_CHUNK_STEPS", 1, int))
    if k < 1:
        raise ValueError(f"chunk_steps must be >= 1, got {k}")
    return k
