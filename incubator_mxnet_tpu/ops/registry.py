"""Op registry and the eager invoke path.

Re-designs the reference's NNVM op registry + imperative invoke
(575 ``NNVM_REGISTER_OP`` sites, include/mxnet/op_attr_types.h:125-332;
``Imperative::Invoke`` src/imperative/imperative.cc:49-130) for XLA:

* An ``Op`` is a *pure JAX function* plus metadata.  Shape/dtype inference
  (reference FInferShape/FInferType) falls out of JAX abstract evaluation,
  so there are no per-op inference functions to register.
* Eager execution wraps the function in ``jax.jit`` per static-kwarg
  signature — the analog of the reference pushing an FCompute closure to
  the engine, except XLA fuses the op internally and PJRT makes it async.
* When autograd is recording, the forward runs under ``jax.vjp`` and the
  residual-holding vjp closure is stored on the tape (the analog of
  FGradient + the autograd graph in imperative.cc:204 RecordOp).
"""
from __future__ import annotations

import functools
import inspect
import threading

import jax
import numpy as _onp

from .. import profiler as _profiler
from . import bulking as _bulking
from ..locks import named_lock

__all__ = ["Op", "register", "get_op", "list_ops", "invoke",
           "clear_caches", "cache_stats"]

_OPS: dict[str, "Op"] = {}
_lock = named_lock("ops.registry")


def _hashable(v):
    if isinstance(v, list):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


class Op:
    """A registered operator.

    Parameters
    ----------
    name : canonical op name (reference op names kept where sensible)
    fn : pure function ``fn(*arrays, **static_params) -> array | tuple``
    differentiable : False for integer/discrete outputs (argmax, one_hot...)
    num_inputs : informational; varargs ops pass -1
    """

    def __init__(self, name, fn, differentiable=True, num_inputs=-1,
                 aliases=(), jittable=True, bulkable=None,
                 inplace_identity=None):
        self.name = name
        self.fn = fn
        self.differentiable = differentiable
        self.num_inputs = num_inputs
        self.aliases = tuple(aliases)
        # inplace_identity=<input index>: the output is (a view of) that
        # input's buffer — the reference's FInplaceIdentity registration
        # (elemwise_op_common.h).  memlint's op-level aliasing credit
        # trusts ops/ref_aliases.IDENTITY_ALIASES, which a unit test
        # cross-checks against this metadata in both directions.
        self.inplace_identity = inplace_identity
        # jittable=False: data-dependent output shape (boolean_mask et
        # al.) — runs eagerly on concrete arrays, like the reference's
        # imperative-only FComputeEx ops; tracing raises a shape error
        self.jittable = jittable
        # bulkable=False opts a jittable op out of deferred segments
        # (ops/bulking.py) — needed for ops whose fn runs impure Python
        # (Custom callbacks) where deferring would reorder side effects
        self.bulkable = jittable if bulkable is None else bulkable
        self._jit_cache: dict = {}
        self._aval_cache: dict = {}
        try:
            sig = inspect.signature(fn)
            self._has_varargs = any(
                p.kind is inspect.Parameter.VAR_POSITIONAL
                for p in sig.parameters.values())
            self._sig = None if self._has_varargs else sig
        except (TypeError, ValueError):
            self._has_varargs = True
            self._sig = None

    def jitted(self, kwarg_names: tuple):
        if not self.jittable:
            return self.fn
        jfn = self._jit_cache.get(kwarg_names)
        if jfn is None:
            # per-op jits ride the unified choke point too (sentinel
            # site op:{name} via Executor's instrument, persistent
            # compile cache init): eager dispatch is usually the FIRST
            # thing a process compiles, and it must hit the
            # persistent cache like every other surface.  This
            # path runs once per (op, kwarg-name set), never per call.
            # Eager-path inputs are live NDArray chunk values the
            # caller reads after the op, so nothing is donated
            # (in-place NDArray ops reuse buffers via Array.at inside
            # XLA instead).
            from .. import executor_cache as _xc
            jfn = self._jit_cache[kwarg_names] = _xc.Executor(
                self.fn, f"op:{self.name}",
                static_argnames=kwarg_names).jfn
        return jfn

    def __call__(self, *arrays, **kwargs):
        """Raw call on jax arrays (no NDArray wrapping, no autograd)."""
        kwargs = {k: _hashable(v) for k, v in kwargs.items()}
        return self.jitted(tuple(sorted(kwargs)))(*arrays, **kwargs)

    def __repr__(self):
        return f"Op({self.name})"


def register(name, differentiable=True, num_inputs=-1, aliases=(),
             jittable=True, bulkable=None, inplace_identity=None):
    """Decorator: register a pure JAX function as an operator."""

    def deco(fn):
        op = Op(name, fn, differentiable=differentiable,
                num_inputs=num_inputs, aliases=aliases, jittable=jittable,
                bulkable=bulkable, inplace_identity=inplace_identity)
        with _lock:
            _OPS[name] = op
            for a in aliases:
                _OPS[a] = op
        return op

    return deco


def get_op(name: str) -> Op:
    try:
        return _OPS[name]
    except KeyError:
        raise KeyError(f"operator {name!r} is not registered") from None


def list_ops():
    return sorted(set(_OPS))


def _current_amp_policy():
    """Bound once on first use: invoke() is the per-op hot path and must
    not pay a module lookup per call when AMP is off."""
    global _current_amp_policy
    from ..amp.amp import current_policy
    _current_amp_policy = current_policy
    return current_policy()


def invoke(op: "Op | str", *inputs, out=None, **kwargs):
    """Execute an op on NDArrays with autograd integration.

    The eager path of the framework — counterpart of
    ``Imperative::Invoke`` (reference src/imperative/imperative.cc:98).
    """
    from .. import autograd
    from ..ndarray import NDArray, _wrap_outputs

    if isinstance(op, str):
        op = get_op(op)

    def _is_array(v):
        return isinstance(v, (NDArray, jax.Array, _onp.ndarray))

    if op._sig is not None and any(not _is_array(x) for x in inputs):
        # Positional static params (MXNet style, e.g. swapaxes(x, 0, 2)):
        # bind to the op signature and shunt non-arrays into kwargs so
        # jit treats them as static instead of tracing them.
        try:
            bound = op._sig.bind(*inputs, **kwargs)
        except TypeError:
            bound = None
        if bound is not None:
            new_inputs, new_kwargs = [], {}
            for pname, val in bound.arguments.items():
                param = op._sig.parameters[pname]
                if param.kind is inspect.Parameter.VAR_KEYWORD:
                    new_kwargs.update(val)
                else:
                    new_kwargs[pname] = val
            # split: leading positional arrays stay positional while the
            # remainder go by keyword (jit supports array kwargs)
            for pname in list(bound.arguments):
                val = new_kwargs.get(pname)
                if _is_array(val):
                    new_inputs.append(new_kwargs.pop(pname))
                else:
                    break
            inputs, kwargs = tuple(new_inputs), new_kwargs
    kw_arrays = {k: v for k, v in kwargs.items() if _is_array(v)}
    kwargs = {k: _hashable(v) for k, v in kwargs.items() if k not in kw_arrays}
    all_in = list(inputs) + list(kw_arrays.values())
    kw_names = tuple(kw_arrays)
    n_pos = len(inputs)

    # AMP: an active CastPolicy (amp.convert_block) casts floating inputs
    # per the op lists — the eager-path analog of the reference's
    # ReducePrecision graph pass (contrib/amp/amp.py convert_symbol).
    _pol = _current_amp_policy()
    recording = autograd.is_recording()

    # Op bulking (ops/bulking.py): outside recording/AMP/out=, a jittable
    # op joins the thread's deferred segment instead of dispatching — the
    # segment compiles as ONE XLA program at the next sync point
    # (reference engine bulk segments, graph_executor.cc InitOpSegs).
    if (op.bulkable and out is None and _pol is None and not recording
            and _bulking.enabled()):
        res = _bulking.defer(op, all_in, n_pos, kw_names, kwargs)
        if res is not _bulking.NOT_DEFERRED:
            return _wrap_outputs(res, inputs if inputs else all_in)

    raw = [x.data if isinstance(x, NDArray) else x for x in all_in]
    if _pol is not None:
        raw = _pol.cast_args(op.name, raw)
    need_grad = (
        recording
        and op.differentiable
        and any(isinstance(x, NDArray) and x._in_graph() for x in all_in)
    )
    if need_grad:
        static = kwargs

        def fn(*arrs):
            return op.fn(*arrs[:n_pos],
                         **dict(zip(kw_names, arrs[n_pos:])), **static)

        out_data, vjp_fn = jax.vjp(fn, *raw)
    else:
        jfn = op.jitted(tuple(sorted(kwargs)))
        out_data = jfn(*raw[:n_pos], **dict(zip(kw_names, raw[n_pos:])),
                       **kwargs)
        vjp_fn = None
    _profiler.record_eager_dispatch()  # both branches are per-op dispatches

    outputs = _wrap_outputs(out_data, inputs if inputs else all_in, out=out)
    if need_grad:
        nd_inputs = [x for x in all_in if isinstance(x, NDArray)]
        input_slots = [i for i, x in enumerate(all_in)
                       if isinstance(x, NDArray)]
        autograd._record(op, vjp_fn, all_in, nd_inputs, input_slots,
                         outputs, fn=fn)
    return outputs


def clear_caches():
    """Drop every ``Op._jit_cache`` / abstract-eval cache and the
    bulking segment trace cache.

    Gives tests (tests/conftest.py) and long-lived servers a way to
    release compiled executables and guarantee no jit-cache state leaks
    across test modules.  Returns the number of entries dropped."""
    n = 0
    with _lock:
        ops = set(_OPS.values())
    for op in ops:
        n += len(op._jit_cache) + len(op._aval_cache)
        op._jit_cache.clear()
        op._aval_cache.clear()
    n += _bulking.clear_trace_cache()
    return n


def cache_stats():
    """Introspection over the compiled-executable caches: per-op jit
    entries, abstract-eval entries, and bulking trace-cache size."""
    with _lock:
        ops = set(_OPS.values())
    per_op = {op.name: len(op._jit_cache) for op in ops if op._jit_cache}
    return {
        "op_jit_entries": sum(per_op.values()),
        "op_aval_entries": sum(len(op._aval_cache) for op in ops),
        "ops_with_jit_cache": len(per_op),
        "bulk_trace_entries": _bulking.trace_cache_stats()["entries"],
        "per_op_jit_entries": per_op,
    }


def describe_op(op: "Op | str"):
    """Declarative parameter reflection (reference §5.6:
    dmlc::Parameter/DMLC_DECLARE_FIELD auto-exposes every op's params,
    defaults and docs to all frontends).  Here the op's Python signature
    IS the declaration; this returns it as structured metadata:
    {"name", "doc", "inputs": [...], "params": {name: {"default", "kind"}}}.
    """
    import inspect as _ins
    if isinstance(op, str):
        op = get_op(op)
    info = {"name": op.name, "doc": (op.fn.__doc__ or "").strip(),
            "differentiable": op.differentiable, "aliases": list(op.aliases),
            "inputs": [], "params": {}}
    if op._sig is None:
        info["inputs"] = ["*args"]
        return info
    for pname, p in op._sig.parameters.items():
        if p.kind is _ins.Parameter.VAR_KEYWORD:
            continue
        if p.default is _ins.Parameter.empty:
            info["inputs"].append(pname)
        else:
            info["params"][pname] = {
                "default": p.default,
                "kind": type(p.default).__name__
                if p.default is not None else "optional",
            }
    return info


def list_op_docs():
    """{op_name: describe_op(...)} over the whole registry (the analog of
    the reference's generated op-doc tables)."""
    return {name: describe_op(name) for name in list_ops()}
