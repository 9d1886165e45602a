"""``mx.np``: NumPy-compatible array namespace.

Reference: python/mxnet/numpy/ (14.5 kLoC of `_npi.*` wrappers over
src/operator/numpy/).  TPU design: ``mx.np.ndarray`` IS the framework
NDArray (one data plane) and the function namespace delegates straight
to jnp — jax.numpy already implements NumPy semantics on XLA, so the
reference's 26.8 kLoC of NumPy-semantics kernels collapse into this
dispatch layer.  Autograd still applies: functions route through the op
registry when an op exists, else wrap jnp directly (recorded via the
generic ``_jnp_call`` vjp path).
"""
from __future__ import annotations

import builtins as _bi
import functools

import numpy as _onp
import jax
import jax.numpy as _jnp

from ..base import dtype_from_any as _dtype_from_any
from ..context import current_context
from ..ndarray import NDArray as ndarray  # mx.np.ndarray IS NDArray
from ..ndarray import NDArray as _ND
from .. import autograd as _autograd

pi = _jnp.pi
e = _jnp.e
inf = _jnp.inf
nan = _jnp.nan
newaxis = None

float32 = _onp.float32
float64 = _onp.float64
float16 = _onp.float16
int32 = _onp.int32
int64 = _onp.int64
int8 = _onp.int8
uint8 = _onp.uint8
bool_ = _onp.bool_


def _wrap_fn(jnp_fn):
    """Lift a jnp function to NDArray in/out with autograd recording."""

    @functools.wraps(jnp_fn)
    def fn(*args, **kwargs):
        # the vjp below covers ALL positional args; record the true
        # argument slot of each NDArray so backward() maps cotangents
        # correctly when scalars precede arrays (np.subtract(1.0, x)).
        # Sequence args (np.concatenate([a, b])) unwrap one level deep
        # with compound (slot, index) addresses.
        nd_inputs, nd_slots, raw = [], [], []
        for i, a in enumerate(args):
            if isinstance(a, _ND):
                nd_inputs.append(a)
                nd_slots.append(i)
                raw.append(a.data)
            elif isinstance(a, (list, tuple)) and _bi.any(
                    isinstance(e, _ND) for e in a):
                for j, e in enumerate(a):
                    if isinstance(e, _ND):
                        nd_inputs.append(e)
                        nd_slots.append((i, j))
                raw.append(type(a)(
                    e.data if isinstance(e, _ND) else e for e in a))
            else:
                raw.append(a)

        # NB: _bi.any — the delegated namespace below shadows several
        # builtins (np.any/all/sum/...) in this module's globals, and a
        # bare any() here recursed through its own wrapper
        recording = _autograd.is_recording() and _bi.any(
            a._in_graph() for a in nd_inputs)
        def call(*xs):
            res = jnp_fn(*xs, **kwargs)
            # normalize list outputs (jnp.split et al.) to tuples so the
            # vjp's primal structure matches the tuple cotangent seed
            # backward() builds (jax.vjp requires exact pytree match)
            return tuple(res) if isinstance(res, list) else res
        if recording:
            try:
                out, vjp = jax.vjp(call, *raw)
            except TypeError:
                out, vjp = call(*raw), None
        else:
            out, vjp = call(*raw), None
        if isinstance(out, (tuple, list)):
            outs = tuple(_ND(o) for o in out)
        else:
            outs = _ND(out)
        if vjp is not None:
            out_tuple = outs if isinstance(outs, tuple) else (outs,)

            def tape_vjp(seed):
                if isinstance(outs, tuple) and not isinstance(seed, tuple):
                    seed = (seed,)
                return vjp(seed)

            _autograd._record(None, tape_vjp, args, nd_inputs,
                              nd_slots, out_tuple, fn=call)
        return outs

    return fn


# Expose the bulk of the numpy namespace by delegation
_DELEGATED = [
    "abs", "absolute", "add", "all", "amax", "amin", "any", "arange_like",
    "arccos", "arccosh", "arcsin", "arcsinh", "arctan", "arctan2", "arctanh",
    "argmax", "argmin", "argsort", "around", "atleast_1d", "atleast_2d",
    "broadcast_arrays", "cbrt", "ceil", "clip", "column_stack",
    "concatenate", "copysign", "cos", "cosh", "cross", "cumprod", "cumsum",
    "deg2rad", "degrees", "diag", "diag_indices", "diagonal", "diff",
    "divide", "dot", "dsplit", "dstack", "ediff1d", "einsum", "equal", "exp",
    "expand_dims", "expm1", "flip", "fliplr", "flipud", "floor",
    "floor_divide", "fmax", "fmin", "fmod", "greater", "greater_equal",
    "heaviside", "histogram", "hsplit", "hstack", "hypot", "insert",
    "interp", "invert", "isfinite", "isinf", "isnan", "kron", "lcm",
    "gcd", "less", "less_equal", "log", "log10", "log1p", "log2",
    "logaddexp", "logical_and", "logical_not", "logical_or", "logical_xor",
    "matmul", "maximum", "mean", "median", "min", "max", "minimum", "mod",
    "moveaxis", "multiply", "nan_to_num", "nanargmax", "nanargmin",
    "nancumsum", "nanmax", "nanmean", "nanmin", "nanprod", "nanstd",
    "nansum", "nanvar", "negative", "not_equal", "outer", "percentile",
    "polyval", "positive", "power", "prod", "ptp", "quantile", "rad2deg",
    "radians", "ravel", "reciprocal", "remainder", "repeat", "reshape",
    "rint", "broadcast_to", "roll", "rot90", "round", "searchsorted", "sign", "sin", "sinh",
    "sort", "split", "sqrt", "square", "squeeze", "stack", "std",
    "subtract", "sum", "swapaxes", "take", "take_along_axis", "tan", "tanh",
    "tensordot", "tile", "trace", "transpose", "tril", "triu",
    "true_divide", "trunc", "unique", "unravel_index", "vdot", "vsplit",
    "vstack", "var", "where", "count_nonzero", "nonzero", "delete",
    "pad", "flatnonzero", "meshgrid", "average", "bincount", "corrcoef",
    "correlate", "cov", "digitize", "divmod", "float_power", "frexp",
    "inner", "isclose", "isneginf", "isposinf", "ldexp", "nanmedian",
    "nanpercentile", "nanquantile", "signbit", "sinc", "spacing",
]

_g = globals()
for _name in _DELEGATED:
    if hasattr(_jnp, _name) and _name not in _g:
        _g[_name] = _wrap_fn(getattr(_jnp, _name))
fix = _wrap_fn(_jnp.trunc)  # numpy.fix rounds toward zero: trunc


class _Linalg:
    def __getattr__(self, name):
        return _wrap_fn(getattr(_jnp.linalg, name))


class _FFT:
    def __getattr__(self, name):
        return _wrap_fn(getattr(_jnp.fft, name))


linalg = _Linalg()
fft = _FFT()


class _NPRandom:
    """mx.np.random — eager samplers over the global key stream."""

    def __getattr__(self, name):
        from .. import random as _gr

        jr_fn = getattr(jax.random, name, None)

        def fn(*args, size=None, **kwargs):
            key = _gr.next_key()
            if name == "uniform":
                low, high = (args + (0.0, 1.0))[:2]
                return _ND(jax.random.uniform(
                    key, _as_shape(size), minval=low, maxval=high))
            if name in ("normal", "randn"):
                loc, scale = (args + (0.0, 1.0))[:2] if name == "normal" \
                    else (0.0, 1.0)
                shape = _as_shape(size) if name == "normal" else tuple(args)
                return _ND(loc + scale * jax.random.normal(key, shape))
            if name == "randint":
                low = args[0]
                high = args[1] if len(args) > 1 else None
                if high is None:
                    low, high = 0, low
                return _ND(jax.random.randint(key, _as_shape(size), low, high))
            if name == "choice":
                return _ND(jax.random.choice(
                    key, args[0].data if isinstance(args[0], _ND) else args[0],
                    shape=_as_shape(size), **kwargs))
            if jr_fn is None:
                raise AttributeError(f"np.random.{name}")
            return _ND(jr_fn(key, *args, **kwargs))

        return fn

    @staticmethod
    def seed(s):
        from .. import random as _gr
        _gr.seed(s)


def _as_shape(size):
    if size is None:
        return ()
    if isinstance(size, int):
        return (size,)
    return tuple(size)


random = _NPRandom()


# creation ops need ctx placement
def array(obj, dtype=None, ctx=None):
    return _ND(obj, ctx=ctx or current_context(), dtype=dtype)


def asarray(obj, dtype=None):
    if isinstance(obj, _ND):
        return obj.astype(dtype) if dtype else obj
    return array(obj, dtype=dtype)


def zeros(shape, dtype="float32", ctx=None, order="C"):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return _ND(_jnp.zeros(shape, _dtype_from_any(dtype)),
               ctx=ctx or current_context())


def ones(shape, dtype="float32", ctx=None, order="C"):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return _ND(_jnp.ones(shape, _dtype_from_any(dtype)),
               ctx=ctx or current_context())


def full(shape, fill_value, dtype=None, ctx=None):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return _ND(_jnp.full(shape, fill_value,
                         _dtype_from_any(dtype) if dtype else None),
               ctx=ctx or current_context())


def empty(shape, dtype="float32", ctx=None):
    return zeros(shape, dtype, ctx)


def zeros_like(a, dtype=None):
    return _ND(_jnp.zeros_like(a.data if isinstance(a, _ND) else a,
                               dtype=_dtype_from_any(dtype) if dtype else None))


def ones_like(a, dtype=None):
    return _ND(_jnp.ones_like(a.data if isinstance(a, _ND) else a,
                              dtype=_dtype_from_any(dtype) if dtype else None))


def full_like(a, fill_value, dtype=None):
    return _ND(_jnp.full_like(a.data if isinstance(a, _ND) else a, fill_value))


def arange(start, stop=None, step=1, dtype=None, ctx=None):
    return _ND(_jnp.arange(start, stop, step,
                           _dtype_from_any(dtype) if dtype else None),
               ctx=ctx or current_context())


def linspace(start, stop, num=50, endpoint=True, retstep=False, dtype=None,
             axis=0, ctx=None):
    out = _jnp.linspace(start, stop, num, endpoint=endpoint, retstep=retstep,
                        dtype=_dtype_from_any(dtype) if dtype else None,
                        axis=axis)
    if retstep:
        return _ND(out[0]), float(out[1])
    return _ND(out, ctx=ctx or current_context())


def logspace(start, stop, num=50, endpoint=True, base=10.0, dtype=None,
             ctx=None):
    return _ND(_jnp.logspace(start, stop, num, endpoint, base))


def eye(N, M=None, k=0, dtype="float32", ctx=None):
    return _ND(_jnp.eye(N, M, k, _dtype_from_any(dtype)))


def identity(n, dtype="float32", ctx=None):
    return eye(n, dtype=dtype, ctx=ctx)


def may_share_memory(a, b):
    if isinstance(a, _ND) and isinstance(b, _ND):
        return a._chunk is b._chunk
    return False


def shares_memory(a, b):
    return may_share_memory(a, b)
