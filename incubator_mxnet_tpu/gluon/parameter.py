"""Parameter and ParameterDict (reference python/mxnet/gluon/parameter.py)."""
from __future__ import annotations

import math
import re
import threading

import jax.numpy as jnp

from .. import trace
from ..base import dtype_from_any
from ..context import Context, current_context
from ..ndarray import NDArray
from .. import initializer as init_mod

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


class DeferredInitializationError(RuntimeError):
    """Parameter accessed before shape inference completed."""


# Thread-local map Parameter -> NDArray installed during hybridize tracing /
# functional apply, so ``param.data()`` yields tracer-backed arrays inside a
# jit trace (the CachedOp mechanism — see block.py).
_trace_state = threading.local()


def _trace_map():
    return getattr(_trace_state, "map", None)


class _TraceParams:
    def __init__(self, mapping):
        self.mapping = mapping

    def __enter__(self):
        self._prev = getattr(_trace_state, "map", None)
        _trace_state.map = self.mapping
        return self

    def __exit__(self, *exc):
        _trace_state.map = self._prev


class Parameter:
    """A weight/bias/aux tensor with lazy shape inference and grad buffer.

    Reference: gluon/parameter.py Parameter — deferred initialization
    (shape dims of 0 resolved at first forward), grad_req write/add/null,
    lr_mult/wd_mult consumed by the optimizer.
    """

    def __init__(self, name="param", grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype_from_any(dtype) or jnp.float32
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._data: NDArray | None = None
        self._deferred_init_args = None
        self._ctx = None

    # -- shape ------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is not None:
            # merge: 0 / -1 dims are unknown
            assert len(self._shape) == len(new_shape), \
                f"shape mismatch for {self.name}: {self._shape} vs {new_shape}"
            merged = []
            for a, b in zip(self._shape, new_shape):
                if a in (0, -1):
                    merged.append(b)
                elif b in (0, -1) or a == b:
                    merged.append(a)
                else:
                    raise ValueError(
                        f"shape mismatch for {self.name}: {self._shape} vs {new_shape}")
            new_shape = tuple(merged)
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._data._grad = None
                self._data._grad_req = None
            else:
                self._data.attach_grad(req)

    def _shape_complete(self):
        return self._shape is not None and all(s > 0 for s in self._shape)

    # -- init -------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=init_mod.Uniform,
                   force_reinit=False):
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = current_context()
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0]  # single logical device; sharding handles multi-chip
        self._ctx = ctx
        if not self._shape_complete():
            if self.allow_deferred_init:
                self._deferred_init_args = (init, ctx, default_init)
                return
            raise DeferredInitializationError(
                f"Parameter {self.name} has unknown shape {self._shape}")
        self._finish_init(init, ctx, default_init)

    def _nbytes(self):
        return math.prod(self._shape) * jnp.dtype(self.dtype).itemsize

    def _finish_init(self, init, ctx, default_init=init_mod.Uniform):
        initializer = init or self.init or default_init()
        if isinstance(initializer, str):
            initializer = init_mod.create(initializer)
        elif isinstance(initializer, type):
            initializer = initializer()
        # the one place a leaf gets its value: the zeros, the draw, the
        # write (host time as it runs; what the draw dispatched and did
        # not wait for lands in whoever reads the leaf first)
        with trace.process_span(
                "gluon.param_init", name=self.name, bytes=self._nbytes(),
                initializer=type(initializer).__name__,
                deferred=self._deferred_init_args is not None):
            data = NDArray(jnp.zeros(self._shape, self.dtype), ctx=ctx)
            initializer(self.name, data)
            self._data = data
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req)
            self._deferred_init_args = None

    def _finish_deferred_init(self):
        if self._deferred_init_args is None:
            return
        if not self._shape_complete():
            raise DeferredInitializationError(
                f"Parameter {self.name} still has unknown shape {self._shape}")
        init, ctx, default_init = self._deferred_init_args
        self._finish_init(init, ctx, default_init)

    # -- access -----------------------------------------------------------
    def _check_and_get(self):
        if self._data is None:
            if self._deferred_init_args is not None:
                raise DeferredInitializationError(
                    f"Parameter {self.name} deferred; run a forward pass or "
                    f"provide in_units/in_channels")
            raise RuntimeError(
                f"Parameter {self.name} has not been initialized; call "
                f".initialize() first")
        return self._data

    def data(self, ctx=None) -> NDArray:
        tm = _trace_map()
        if tm is not None and self in tm:
            return tm[self]
        return self._check_and_get()

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None) -> NDArray:
        d = self._check_and_get()
        if d.grad is None:
            raise RuntimeError(f"Parameter {self.name} has grad_req='null'")
        return d.grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        return [self._ctx or current_context()]

    def zero_grad(self):
        d = self._check_and_get()
        d.zero_grad()

    def set_data(self, data):
        d = self._check_and_get()
        if isinstance(data, NDArray):
            data = data.data
        d._set_data(jnp.asarray(data, d.data.dtype))

    def reset_ctx(self, ctx):
        self._ctx = ctx
        if self._data is not None:
            self._data = self._data.as_in_context(ctx)
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req)

    def cast(self, dtype):
        self.dtype = dtype_from_any(dtype)
        if self._data is not None:
            had_grad = self._data._grad is not None
            self._data = NDArray(self._data.data.astype(self.dtype),
                                 ctx=self._ctx)
            if had_grad:
                self._data.attach_grad(self._grad_req)

    def var(self):
        from .. import symbol
        return symbol.var(self.name, shape=self._shape, dtype=self.dtype)

    def __repr__(self):
        return (f"Parameter {self.name} (shape={self._shape}, "
                f"dtype={jnp.dtype(self.dtype).name})")


class Constant(Parameter):
    """Non-trainable constant parameter (reference parameter.py Constant)."""

    def __init__(self, name, value=None):
        if value is None:
            name, value = "const", name
        if isinstance(value, NDArray):
            value_nd = value
        else:
            value_nd = NDArray(value)
        super().__init__(name=name, grad_req="null", shape=value_nd.shape,
                         dtype=value_nd.data.dtype,
                         init=init_mod.Constant(0))
        self._value = value_nd

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        if self._data is not None and not force_reinit:
            return
        self._ctx = ctx or current_context()
        self._data = self._value.as_in_context(self._ctx)


class ParameterDict:
    """Ordered name→Parameter mapping with bulk ops (reference
    parameter.py ParameterDict).  Returned by ``Block.collect_params``."""

    def __init__(self, prefix="", shared=None):
        self.prefix = prefix
        self._params: dict[str, Parameter] = {}
        self._shared = shared

    def __repr__(self):
        body = "\n".join(f"  {v}" for v in self._params.values())
        return f"ParameterDict(\n{body}\n)"

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def get(self, name, **kwargs):
        """Create-or-retrieve (reference ParameterDict.get)."""
        full = self.prefix + name
        if full in self._params:
            param = self._params[full]
            if "shape" in kwargs and kwargs["shape"] is not None:
                param.shape = kwargs["shape"] if not isinstance(
                    kwargs["shape"], int) else (kwargs["shape"],)
            return param
        if self._shared is not None and full in self._shared:
            param = self._shared[full]
        else:
            param = Parameter(full, **kwargs)
        self._params[full] = param
        return param

    def update(self, other):
        for k, v in other.items():
            self._params[k] = v

    def _add(self, name, param):
        self._params[name] = param

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        with trace.process_span("gluon.initialize") as sp:
            done = []
            for p in self.values():
                had = p._data is not None and not force_reinit
                p.initialize(init=init, ctx=ctx, force_reinit=force_reinit)
                if not had and p._data is not None:
                    done.append(p)
            sp.set(leaves=len(done), bytes=sum(p._nbytes() for p in done))

    def zero_grad(self):
        for p in self.values():
            if p.grad_req != "null" and p._data is not None:
                p.zero_grad()

    def setattr(self, name, value):
        for p in self.values():
            setattr(p, name, value)

    def reset_ctx(self, ctx):
        for p in self.values():
            p.reset_ctx(ctx)

    def save(self, filename, strip_prefix=""):
        from .. import ndarray as nd
        arrays = {}
        for name, p in self.items():
            key = name[len(strip_prefix):] if name.startswith(strip_prefix) else name
            arrays[key] = p.data()
        nd.save(filename, arrays)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from .. import ndarray as nd
        loaded = nd.load(filename)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self.items():
            if name in loaded:
                if p._data is None:
                    p.shape = loaded[name].shape
                    p.initialize(ctx=ctx)
                p.set_data(loaded[name])
            elif not allow_missing:
                raise KeyError(f"parameter {name} missing in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(self.keys())
            if extra:
                raise KeyError(f"extra parameters in {filename}: {sorted(extra)}")
