"""Neural-network ops: conv, pooling, norm layers, softmax, dropout.

TPU-native counterpart of reference ``src/operator/nn/`` (19.4 kLoC + cuDNN
and MKL-DNN wrappers — SURVEY.md §2.1).  Every op lowers to XLA HLO
(conv_general_dilated, reduce_window, dot_general) so the MXU does the
FLOPs; layout is kept NCHW to match the reference's default data layout,
with XLA free to relayout internally for the systolic array.
"""
import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax import nn as jnn

from .. import profiler as _profiler
from ..locks import named_lock
from .registry import register

# BatchNorm batch-stat algorithm, fixed at import: compiled traces are
# cached (registry Op._jit_cache), so a runtime-mutable knob would be
# silently ignored by already-traced callers.  Tests monkeypatch the
# module attribute instead.
_BN_STATS_MODE = os.environ.get("MXNET_BN_STATS", "onepass")


def _pair(v, n):
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    return v if len(v) == n else v * n


# ---------------------------------------------------------------------------
# FullyConnected / dense
# ---------------------------------------------------------------------------

@register("FullyConnected", aliases=("fully_connected", "dense"))
def fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    """y = x @ W^T + b with reference layout W:(num_hidden, in_units)
    (reference src/operator/nn/fully_connected.cc)."""
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    # no explicit preferred_element_type: the MXU accumulates bf16
    # matmuls in fp32 internally, and an explicit f32 output breaks the
    # transpose rule (fp32 cotangent vs bf16 primal under jax.grad)
    y = lax.dot_general(
        x, weight,
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())))
    y = y.astype(x.dtype)
    if bias is not None and not no_bias:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# Convolution
# ---------------------------------------------------------------------------

@register("Convolution", aliases=("conv", "convolution"))
def convolution(x, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False,
                layout=None):
    """N-D convolution, weight (O, I/group, *K) in the default NCHW
    layout or (O, *K, I/group) for layout="NHWC" (reference layout
    parameter semantics, src/operator/nn/convolution.cc — the
    reference's NHWC path is its cuDNN fp16 fast path; here it is the
    channel-minor layout the Pallas fused-block kernels read).

    Lowers to a single conv_general_dilated — XLA's conv already does
    implicit im2col + MXU-tiled matmul, subsuming the reference's cuDNN
    algo selection.
    """
    nd = x.ndim - 2
    stride = _pair(stride or 1, nd)
    dilate = _pair(dilate or 1, nd)
    pad = _pair(pad or 0, nd)
    if layout is not None and layout.endswith("C") and nd >= 1:
        spatial = "DHW"[3 - nd:]
        dn_str = (f"N{spatial}C", f"O{spatial}I", f"N{spatial}C")
    else:
        spatial = "DHW"[3 - nd:]
        dn_str = (f"NC{spatial}", f"OI{spatial}", f"NC{spatial}")
    dn = lax.conv_dimension_numbers(x.shape, weight.shape, dn_str)
    # no explicit preferred_element_type (see fully_connected note)
    y = lax.conv_general_dilated(
        x, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    y = y.astype(x.dtype)
    if bias is not None and not no_bias:
        bshape = ((1,) * (nd + 1) + (-1,)
                  if layout is not None and layout.endswith("C")
                  else (1, -1) + (1,) * nd)
        y = y + bias.reshape(bshape)
    return y


@register("Deconvolution", aliases=("deconvolution",))
def deconvolution(x, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=None, num_group=1,
                  no_bias=True, layout=None):
    """Transposed convolution (reference src/operator/nn/deconvolution.cc)."""
    nd = x.ndim - 2
    stride = _pair(stride or 1, nd)
    pad = _pair(pad or 0, nd)
    dilate = _pair(dilate or 1, nd)
    adj = _pair(adj or 0, nd)
    kernel = weight.shape[2:]
    # conv_transpose with IOHW kernel: weight layout (in, out/group, *K)
    pads = []
    for k, s, p, a, d in zip(kernel, stride, pad, adj, dilate):
        eff_k = (k - 1) * d + 1
        pads.append((eff_k - 1 - p, eff_k - 1 - p + a))
    y = lax.conv_transpose(
        x, weight, strides=stride, padding=pads,
        rhs_dilation=dilate,
        dimension_numbers=lax.conv_dimension_numbers(
            x.shape, weight.shape,
            ("NCHW", "OIHW", "NCHW") if nd == 2 else
            (("NCW", "OIW", "NCW") if nd == 1 else ("NCDHW", "OIDHW", "NCDHW"))),
        transpose_kernel=True)
    y = y.astype(x.dtype)
    if bias is not None and not no_bias:
        y = y + bias.reshape((1, -1) + (1,) * nd)
    return y


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

@register("Pooling", aliases=("pooling",))
def pooling(x, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, count_include_pad=True, pooling_convention="valid",
            layout=None):
    """Max/avg/sum/lp pooling via reduce_window (reference nn/pooling.cc;
    layout="NHWC" puts channels minor, matching the conv layout knob)."""
    nd = x.ndim - 2
    nhwc = layout is not None and layout.endswith("C")
    if global_pool:
        axes = tuple(range(1, x.ndim - 1)) if nhwc \
            else tuple(range(2, x.ndim))
        if pool_type == "max":
            out = jnp.max(x, axis=axes, keepdims=True)
        else:
            out = jnp.mean(x, axis=axes, keepdims=True)
        return out
    kernel = _pair(kernel, nd)
    stride = _pair(stride or kernel, nd)
    pad = _pair(pad or 0, nd)
    if nhwc:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = ((0, 0),) + tuple((p, p) for p in pad) + ((0, 0),)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    import numpy as _np
    if pool_type == "max":
        # init must be a SCALAR (python/numpy), not a jax array constant:
        # reduce_window with an array init breaks reverse-mode
        # linearization; a typed numpy scalar keeps int8 pooling exact
        init = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                else _np.dtype(x.dtype).type(jnp.iinfo(x.dtype).min))
        return lax.reduce_window(x, init, lax.max, window, strides, pads)
    zero = (0.0 if jnp.issubdtype(x.dtype, jnp.floating)
            else _np.dtype(x.dtype).type(0))
    # sum/avg pooling accumulates in f32 for bf16/f16 inputs
    # (graphlint GL-PREC001: reduce_window accumulates in the operand
    # dtype, and a big window in bf16 saturates — ~88% relative error
    # at 64x64); the result returns in x.dtype, matching the
    # fused-epilogue convention of the other low-precision ops
    low_acc = (jnp.issubdtype(x.dtype, jnp.floating)
               and jnp.finfo(x.dtype).bits < 32)
    xs = x.astype(jnp.float32) if low_acc else x
    summed = lax.reduce_window(xs, zero, lax.add, window, strides, pads)
    if pool_type == "sum":
        return summed.astype(x.dtype) if low_acc else summed
    if count_include_pad or all(p == 0 for p in pad):
        denom = 1.0
        for k in kernel:
            denom *= k
        out = summed / denom
        return out.astype(x.dtype) if low_acc else out
    counts = lax.reduce_window(jnp.ones_like(xs), 0.0, lax.add, window,
                               strides, pads)
    out = summed / counts
    return out.astype(x.dtype) if low_acc else out


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

@register("BatchNorm", aliases=("batch_norm",))
def batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-5,
               momentum=0.9, fix_gamma=False, use_global_stats=False,
               axis=1, output_mean_var=False, training=False):
    """BatchNorm (reference src/operator/nn/batch_norm.cc).

    Pure function: in training mode returns (out, new_moving_mean,
    new_moving_var); the stateful moving-average update is applied by the
    gluon layer (reference mutates aux states in place).
    """
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    reduce_axes = tuple(i for i in range(x.ndim) if i != (axis % x.ndim))
    bshape = [1] * x.ndim
    bshape[axis % x.ndim] = x.shape[axis % x.ndim]
    bshape = tuple(bshape)
    # mixed precision: statistics accumulate in fp32 (a bf16 sum over a
    # batch*H*W reduction loses too many bits), but the normalize/affine
    # math stays in x.dtype — scale/shift per channel is a fused
    # elementwise epilogue and upcasting the whole activation to fp32
    # doubles its VMEM footprint for no accuracy win (VERDICT r2 Weak #2).
    if training and not use_global_stats:
        mean = jnp.mean(x, axis=reduce_axes, dtype=jnp.float32)
        if _BN_STATS_MODE == "twopass":
            # numerically safest: E[(x-mu)^2].  The broadcast-subtract
            # materializes an fp32 copy of the activation in the vjp —
            # measured as the dominant HBM traffic of the bf16 train
            # step on v5e, so one-pass is the default.
            var = jnp.mean(
                jnp.square(x.astype(jnp.float32) - mean.reshape(bshape)),
                axis=reduce_axes)
        else:
            # one-pass E[x^2] - mu^2 (same form as flax BatchNorm): no
            # fp32 activation-sized tensor exists fwd or bwd.  For bf16
            # x the square is rounded to bf16 before the f32-accumulated
            # sum (~2^-9 relative per element, averaged out over the
            # batch*spatial reduction); cancellation needs |mu| >> sigma,
            # which post-conv activations don't exhibit.  fp32 and bf16
            # parity with two-pass is covered in tests.
            meansq = jnp.mean(jnp.square(x), axis=reduce_axes,
                              dtype=jnp.float32)
            var = jnp.maximum(meansq - jnp.square(mean), 0.0)
        new_mean = (momentum * moving_mean
                    + (1 - momentum) * mean.astype(moving_mean.dtype))
        new_var = (momentum * moving_var
                   + (1 - momentum) * var.astype(moving_var.dtype))
        # y = (x - mean) * rsqrt(var+eps) * gamma + beta, folded to
        # y = x * scale + bias with scale/bias computed once in fp32
        rstd = lax.rsqrt(var + eps)
        scale = (gamma.astype(jnp.float32) * rstd).astype(x.dtype)
        bias = (beta.astype(jnp.float32)
                - mean * gamma.astype(jnp.float32) * rstd).astype(x.dtype)
        out = x * scale.reshape(bshape) + bias.reshape(bshape)
        return out, new_mean, new_var
    scale = (gamma.astype(jnp.float32) * lax.rsqrt(
        moving_var.astype(jnp.float32) + eps)).astype(x.dtype)
    bias = (beta.astype(jnp.float32)
            - moving_mean.astype(jnp.float32) * gamma.astype(jnp.float32)
            * lax.rsqrt(moving_var.astype(jnp.float32) + eps)).astype(x.dtype)
    return x * scale.reshape(bshape) + bias.reshape(bshape)


@register("LayerNorm", aliases=("layer_norm",))
def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    """LayerNorm (reference src/operator/nn/layer_norm.cc) — a single fused
    XLA subgraph, or the hand-fused Pallas kernel for the common
    trailing-axis case on TPU (ops/pallas_kernels.fused_layer_norm)."""
    def xla(x, gamma, beta):
        xf = x.astype(jnp.float32) if x.dtype != jnp.float32 else x
        mean = jnp.mean(xf, axis=axis, keepdims=True)
        var = jnp.var(xf, axis=axis, keepdims=True)
        x_hat = (xf - mean) * lax.rsqrt(var + eps)
        shape = [1] * x.ndim
        shape[axis % x.ndim] = x.shape[axis % x.ndim]
        out = x_hat * gamma.reshape(shape) + beta.reshape(shape)
        return out.astype(x.dtype)

    if isinstance(axis, int) and axis in (-1, x.ndim - 1) and gamma.ndim == 1:
        from . import pallas_kernels as pk
        return pk.dispatch(
            functools.partial(pk.fused_layer_norm, eps=float(eps)),
            xla, x, gamma, beta)
    return xla(x, gamma, beta)


@register("GroupNorm", aliases=("group_norm",))
def group_norm(x, gamma, beta, num_groups=1, eps=1e-5):
    n, c = x.shape[:2]
    g = num_groups
    y = x.astype(jnp.float32).reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, y.ndim))
    mean = jnp.mean(y, axis=axes, keepdims=True)
    var = jnp.var(y, axis=axes, keepdims=True)
    y = (y - mean) * lax.rsqrt(var + eps)
    y = y.reshape(x.shape)
    shape = (1, c) + (1,) * (x.ndim - 2)
    return (y * gamma.reshape(shape) + beta.reshape(shape)).astype(x.dtype)


@register("InstanceNorm", aliases=("instance_norm",))
def instance_norm(x, gamma, beta, eps=1e-3):
    axes = tuple(range(2, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return (y * gamma.reshape(shape) + beta.reshape(shape)).astype(x.dtype)


@register("L2Normalization", aliases=("l2_normalization",))
def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, x.ndim))
    norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True) + eps)
    return x / norm


@register("RMSNorm", aliases=("rms_norm",))
def rms_norm(x, gamma, axis=-1, eps=1e-6):
    """TPU-era addition (not in the reference): used by the transformer
    stack.  Trailing-axis case runs the fused Pallas kernel on TPU
    (pallas_kernels.fused_rms_norm), like LayerNorm/softmax."""
    def xla(x, gamma):
        # same contract as the kernel: fp32 statistics and scale, the
        # result in x's dtype
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
        return (xf * lax.rsqrt(ms + eps)
                * gamma.astype(jnp.float32)).astype(x.dtype)

    if axis in (-1, x.ndim - 1):
        from . import pallas_kernels as pk
        # a gain of more than one axis (a grouped norm: x (..., groups,
        # width) under a gain (groups, width)) is the composition's
        return pk.dispatch(functools.partial(pk.fused_rms_norm, eps=eps),
                           xla, x, gamma,
                           unless="gain_shape" if gamma.ndim > 1 else None)
    return xla(x, gamma)


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------

@register("softmax")
def softmax(x, axis=-1, temperature=None, length=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        # length has x's shape minus `axis` (reference use_length semantics,
        # softmax-inl.h): build the valid mask along that axis explicitly
        ax = axis % x.ndim
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        idx = jnp.arange(x.shape[ax]).reshape(shape)
        mask = idx < jnp.expand_dims(length, ax)
        x = jnp.where(mask, x, -jnp.inf)
    if isinstance(axis, int):
        from . import pallas_kernels as pk
        return pk.dispatch(functools.partial(pk.fused_softmax, axis=axis),
                           functools.partial(jnn.softmax, axis=axis), x)
    return jnn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(x, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jnn.log_softmax(x, axis=axis)


@register("softmin")
def softmin(x, axis=-1):
    return jnn.softmax(-x, axis=axis)


def _zero_cotangent(x):
    """Zero cotangent matching JAX's rules (float0 for integer inputs)."""
    if jnp.issubdtype(x.dtype, jnp.floating) or jnp.issubdtype(
            x.dtype, jnp.complexfloating):
        return jnp.zeros_like(x)
    import numpy as _onp
    return _onp.zeros(x.shape, jax.dtypes.float0)


def _loss_norm(grad, label, grad_scale, ignore_label, use_ignore,
               normalization):
    if normalization == "batch":
        grad = grad / grad.shape[0]
    elif normalization == "valid" and use_ignore:
        valid = jnp.sum(label != ignore_label)
        grad = grad / jnp.maximum(valid, 1).astype(grad.dtype)
    elif normalization == "valid":
        grad = grad / grad.shape[0]
    return grad * grad_scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _softmax_output(data, label, grad_scale, ignore_label, use_ignore,
                    multi_output, normalization):
    axis = 1 if multi_output else -1
    return jnn.softmax(data, axis=axis)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output, normalization):
    axis = 1 if multi_output else -1
    p = jnn.softmax(data, axis=axis)
    return p, (p, label)


def _softmax_output_bwd(grad_scale, ignore_label, use_ignore, multi_output,
                        normalization, res, g):
    # Loss layer: the incoming cotangent is ignored (reference
    # src/operator/softmax_output-inl.h backward) — backward() on the
    # executor injects the cross-entropy gradient directly.
    del g
    p, label = res
    axis = 1 if multi_output else -1
    nclass = p.shape[axis]
    if label.ndim == p.ndim:       # soft / one-hot labels
        onehot = label.astype(p.dtype)
        ilabel = jnp.argmax(label, axis=axis)
    else:
        ilabel = label.astype(jnp.int32)
        onehot = jnn.one_hot(ilabel, nclass, axis=axis, dtype=p.dtype)
    grad = p - onehot
    if use_ignore:
        mask = (ilabel != ignore_label)
        grad = grad * jnp.expand_dims(mask, axis).astype(p.dtype)
    grad = _loss_norm(grad, ilabel, grad_scale, ignore_label, use_ignore,
                      normalization)
    return grad, _zero_cotangent(label)


_softmax_output.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", aliases=("softmax_output",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1,
                   use_ignore=False, multi_output=False, normalization="null",
                   **_ignored):
    """Forward = softmax; the symbol-API loss op (reference
    src/operator/softmax_output.cc).  The registered vjp ignores the
    incoming cotangent and injects the cross-entropy gradient, so
    ``Executor.backward()`` with implicit head ones matches the reference."""
    return _softmax_output(data, label, float(grad_scale), int(ignore_label),
                           bool(use_ignore), bool(multi_output), normalization)


def _make_regression_output(name, fwd_fn, grad_fn):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(data, label, grad_scale):
        return fwd_fn(data)

    def core_fwd(data, label, grad_scale):
        return fwd_fn(data), (data, label)

    def core_bwd(grad_scale, res, g):
        del g
        data, label = res
        lbl = label.astype(data.dtype).reshape(data.shape)
        # reference src/operator/regression_output-inl.h: grad is scaled by
        # grad_scale / num_output where num_output = per-sample output count
        num_output = max(data.size // data.shape[0], 1)
        grad = grad_fn(fwd_fn(data), lbl) * (grad_scale / num_output)
        return grad, _zero_cotangent(label)

    core.defvjp(core_fwd, core_bwd)

    def op(data, label, grad_scale=1.0, **_ignored):
        return core(data, label, float(grad_scale))

    op.__name__ = name
    op.__doc__ = (f"{name}: symbol-API regression loss layer (reference "
                  "src/operator/regression_output-inl.h); vjp injects the "
                  "loss gradient, normalized by batch size.")
    return op


register("LinearRegressionOutput", aliases=("linear_regression_output",))(
    _make_regression_output("LinearRegressionOutput",
                            lambda d: d, lambda o, l: o - l))
register("MAERegressionOutput", aliases=("mae_regression_output",))(
    _make_regression_output("MAERegressionOutput",
                            lambda d: d, lambda o, l: jnp.sign(o - l)))
register("LogisticRegressionOutput", aliases=("logistic_regression_output",))(
    _make_regression_output("LogisticRegressionOutput",
                            jnn.sigmoid, lambda o, l: o - l))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _make_loss_core(data, grad_scale, valid_thresh, normalization):
    return data


def _make_loss_fwd(data, grad_scale, valid_thresh, normalization):
    return data, data


def _make_loss_bwd(grad_scale, valid_thresh, normalization, data, g):
    del g
    grad = jnp.full(data.shape, grad_scale, data.dtype)
    if normalization == "batch":
        grad = grad / data.shape[0]
    elif normalization == "valid":
        # reference src/operator/make_loss-inl.h:108: divide by the count
        # of elements above valid_thresh
        valid = jnp.sum(data > valid_thresh).astype(data.dtype)
        grad = grad / jnp.maximum(valid, 1.0)
    return (grad,)


_make_loss_core.defvjp(_make_loss_fwd, _make_loss_bwd)


@register("MakeLoss", aliases=("make_loss",))
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null",
              **_ignored):
    """Treat any symbol as a loss head (reference src/operator/make_loss.cc):
    forward is identity, backward seeds grad_scale (batch- or
    valid-count-normalized), ignoring the incoming cotangent."""
    return _make_loss_core(data, float(grad_scale), float(valid_thresh),
                           normalization)


@register("SoftmaxActivation")
def softmax_activation(x, mode="instance"):
    if mode == "channel":
        return jnn.softmax(x, axis=1)
    return jnn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


# ---------------------------------------------------------------------------
# Dropout (key is an explicit input — functional PRNG)
# ---------------------------------------------------------------------------

_masks = {}
_masks_lock = named_lock("ops.dropout_masks")


def dropout_masks(reset=False):
    """``{signature: {elements, generator, kept_bytes}}`` of every mask
    :func:`dropout` was traced to draw so far: the mask's ``elements``
    (its broadcast shape under ``axes``), the ``generator`` of its bits
    and the ``kept_bytes`` it holds from the forward pass to the backward
    (one ``pred`` an element).  Written where the op's body is traced, so
    it counts signatures and not calls (a jitted op is traced once for
    equal shapes).  The ``dropout_masks`` provider of
    ``profiler.dumps()``."""
    with _masks_lock:
        out = {sig: dict(mask) for sig, mask in sorted(_masks.items())}
        if reset:
            _masks.clear()
    return out


_profiler.register_stats_provider("dropout_masks", dropout_masks)


@register("Dropout", aliases=("dropout",))
def dropout(x, key, p=0.5, mode="training", axes=()):
    """Inverted dropout whose mask is drawn ONCE: 32-bit words from XLA's
    ``RngBitGenerator`` (an instruction of its own, which XLA neither
    fuses nor duplicates) under the integer compare
    ``bits < round((1 - p) * 2**32)``, kept as a ``pred`` behind an
    optimization barrier, so the forward select and the backward pass's
    (autodiff's ``where(keep, g / (1 - p), 0)``) read one array.  Without
    the barrier XLA fuses the mask's producer into every consumer — with
    threefry bits that was the rounds again in the prologue of each
    gradient matmul (PERF.md §6, PR 33); a ``jax.custom_vjp`` alone does
    not stop it.  ``key`` is the op's threefry key (two words, as
    ``random.next_key`` gives it); the generator's four are that key
    twice, the layout of ``jax.random.key(seed, impl="rbg")``.  The mask
    is a function of the key on one backend, not equal across backends
    (``random.py``)."""
    if p <= 0.0 or mode != "training":
        return x + 0
    shape = list(x.shape)
    for a in axes:
        shape[a] = 1
    _, bits = lax.rng_bit_generator(jnp.concatenate([key, key]),
                                    tuple(shape), dtype=jnp.uint32)
    keep_below = min(round((1.0 - p) * 2 ** 32), 2 ** 32 - 1)
    keep = lax.optimization_barrier(bits < jnp.uint32(keep_below))
    with _masks_lock:
        _masks["x".join(map(str, shape)) + f" p{p:g} {x.dtype}"] = {
            "elements": keep.size, "generator": "rng_bit_generator",
            "kept_bytes": keep.nbytes}
    return jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))


@register("Activation", aliases=("activation",))
def activation(x, act_type="relu"):
    fns = {"relu": jnn.relu, "sigmoid": jnn.sigmoid, "tanh": jnp.tanh,
           "softrelu": jnn.softplus, "softsign": jnn.soft_sign,
           "gelu": jnn.gelu, "silu": jnn.silu, "swish": jnn.silu,
           "mish": lambda v: v * jnp.tanh(jnn.softplus(v)),
           "log_sigmoid": jnn.log_sigmoid}
    return fns[act_type](x)


@register("LRN", aliases=("lrn",))
def lrn(x, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (reference src/operator/nn/lrn.cc)."""
    sq = jnp.square(x)
    half = nsize // 2
    pad = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (x.ndim - 2))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(nsize))
    return x / jnp.power(knorm + alpha * acc / nsize, beta)


# ---------------------------------------------------------------------------
# Attention (TPU-era: backs the transformer stack; reference has only
# contrib BERT-era fused ops, src/operator/contrib/transformer.cc)
# ---------------------------------------------------------------------------

@register("dot_product_attention")
def dot_product_attention(q, k, v, mask=None, scale=None, causal=False):
    """(B, H, T, D) scaled dot-product attention, the one entry point:
    the blockwise Pallas kernel pair (``pallas_kernels.flash_attention``,
    no (T, S) tensor in HBM, forward or backward) where
    :func:`pallas_kernels.dispatch` routes it, else this XLA composition
    with float32 scores.  The kernel takes dense and causal attention
    over bfloat16 / float32 operands; an explicit ``mask`` (a
    key-padding mask included) and float16 (Mosaic loads no float16
    vector on a v5e) take the composition.  ``k`` and ``v`` may be (B,
    Hkv, S, D) with ``H % Hkv == 0`` (grouped-query attention: query head
    ``i`` reads key head ``i // (H / Hkv)``)."""
    from . import pallas_kernels as pk
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5

    def xla(q, k, v):
        k, v = pk.repeat_kv_heads(q, k, v)
        logits = jnp.einsum("bhtd,bhsd->bhts", q, k,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            t, s = logits.shape[-2:]
            cm = jnp.tril(jnp.ones((t, s), bool))
            logits = jnp.where(cm, logits, -jnp.inf)
        if mask is not None:
            logits = jnp.where(mask.astype(bool), logits, -jnp.inf)
        probs = jnn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhts,bhsd->bhtd", probs, v)

    if mask is not None:
        unless = "mask"
    elif not (q.dtype == k.dtype == v.dtype
              and q.dtype in (jnp.bfloat16, jnp.float32)):
        unless = "dtype"
    else:
        unless = None
    return pk.dispatch(
        functools.partial(pk.flash_attention, sm_scale=scale, causal=causal),
        xla, q, k, v, unless=unless)


# ---------------------------------------------------------------------------
# spatial-transformer family (reference src/operator/bilinear_sampler.cc,
# grid_generator.cc, spatial_transformer.cc) and UpSampling
# ---------------------------------------------------------------------------

def _bilinear_taps(data, xs, ys):
    """Gather the 4 bilinear taps of NCHW data at pixel coords (xs, ys)
    (flattened per batch); out-of-range taps contribute zero (reference
    BilinearSampler border semantics).  Returns taps + fractional
    weights."""
    n, c, h, w = data.shape
    x0 = jnp.floor(xs)
    y0 = jnp.floor(ys)

    def tap(yi, xi):
        inside = ((xi >= 0) & (xi <= w - 1)
                  & (yi >= 0) & (yi <= h - 1))        # (N, P)
        xi_c = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        yi_c = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        flat = data.reshape(n, c, h * w)
        idx = (yi_c * w + xi_c)[:, None, :]           # (N, 1, P)
        vals = jnp.take_along_axis(flat, jnp.broadcast_to(
            idx, (n, c, idx.shape[-1])), axis=2)      # (N, C, P)
        return vals * inside[:, None, :]

    return (tap(y0, x0), tap(y0, x0 + 1), tap(y0 + 1, x0),
            tap(y0 + 1, x0 + 1), xs - x0, ys - y0)


@register("BilinearSampler", aliases=("bilinear_sampler",))
def bilinear_sampler(data, grid):
    """data (N,C,H,W), grid (N,2,Ho,Wo) with x=grid[:,0], y=grid[:,1] in
    [-1,1] → (N,C,Ho,Wo) (reference src/operator/bilinear_sampler.cc)."""
    n, c, h, w = data.shape
    ho, wo = grid.shape[2], grid.shape[3]
    gx = grid[:, 0].reshape(n, -1).astype(jnp.float32)
    gy = grid[:, 1].reshape(n, -1).astype(jnp.float32)
    xs = (gx + 1.0) * (w - 1) / 2.0
    ys = (gy + 1.0) * (h - 1) / 2.0
    v00, v01, v10, v11, fx, fy = _bilinear_taps(
        data.astype(jnp.float32), xs, ys)
    fx = fx[:, None, :]
    fy = fy[:, None, :]
    out = (v00 * (1 - fx) * (1 - fy) + v01 * fx * (1 - fy)
           + v10 * (1 - fx) * fy + v11 * fx * fy)
    return out.reshape(n, c, ho, wo).astype(data.dtype)


@register("GridGenerator", aliases=("grid_generator",))
def grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    """Affine (N,6) → sampling grid (N,2,H,W); warp passes flow through
    (reference src/operator/grid_generator.cc)."""
    h, w = int(target_shape[0]), int(target_shape[1])
    if transform_type == "warp":
        # data is (N,2,H,W) optical flow added to the identity grid,
        # normalized to [-1,1]
        n, _, h, w = data.shape
        xs = jnp.arange(w, dtype=jnp.float32)[None, :]
        ys = jnp.arange(h, dtype=jnp.float32)[:, None]
        gx = (data[:, 0] + xs) * 2.0 / max(w - 1, 1) - 1.0
        gy = (data[:, 1] + ys) * 2.0 / max(h - 1, 1) - 1.0
        return jnp.stack([gx, gy], axis=1)
    n = data.shape[0]
    theta = data.reshape(n, 2, 3).astype(jnp.float32)
    ys, xs = jnp.meshgrid(jnp.linspace(-1, 1, h), jnp.linspace(-1, 1, w),
                          indexing="ij")
    ones = jnp.ones_like(xs)
    base = jnp.stack([xs, ys, ones], axis=0).reshape(3, -1)  # (3, H*W)
    out = jnp.einsum("nij,jp->nip", theta, base)             # (N,2,H*W)
    return out.reshape(n, 2, h, w)


@register("SpatialTransformer", aliases=("spatial_transformer",))
def spatial_transformer(data, loc, target_shape=(0, 0),
                        transform_type="affine", sampler_type="bilinear"):
    """STN: affine params → grid → bilinear sample (reference
    src/operator/spatial_transformer.cc)."""
    grid = grid_generator.fn(loc, "affine", target_shape)
    return bilinear_sampler.fn(data, grid)


@register("UpSampling", aliases=("upsampling",))
def upsampling(*args, scale=2, sample_type="nearest", num_filter=0,
               num_args=1):
    """Nearest/bilinear upsampling (reference src/operator/upsampling.cc);
    multiple inputs are upsampled to the first one's scaled size and
    concatenated on channels."""
    outs = []
    data0 = args[0]
    th, tw = data0.shape[2] * scale, data0.shape[3] * scale
    for d in args[:max(1, num_args)]:
        if sample_type == "nearest":
            r_h, r_w = th // d.shape[2], tw // d.shape[3]
            out = jnp.repeat(jnp.repeat(d, r_h, axis=2), r_w, axis=3)
        else:
            out = jax.image.resize(
                d.astype(jnp.float32),
                (d.shape[0], d.shape[1], th, tw), method="bilinear"
            ).astype(d.dtype)
        outs.append(out)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


@register("log_sigmoid")
def log_sigmoid(x):
    return jnn.log_sigmoid(x)


@register("masked_softmax")
def masked_softmax(data, mask, axis=-1, temperature=1.0):
    """softmax over positions where mask is True (reference
    src/operator/nn/softmax.cc masked_softmax)."""
    logits = data / temperature
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(mask.astype(bool), logits.astype(jnp.float32), neg)
    out = jnn.softmax(logits, axis=axis)
    return (out * mask.astype(out.dtype)).astype(data.dtype)


@register("LeakyReLU", num_inputs=-1)
def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25,
              lower_bound=0.125, upper_bound=0.334, training=False):
    """Parametric activation family (reference src/operator/leaky_relu.cc
    LeakyReLU: act_type in leaky/elu/gelu/selu/prelu/rrelu)."""
    from jax import nn as jnn
    if act_type == "leaky":
        return jnn.leaky_relu(data, negative_slope=slope)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "gelu":
        return jnn.gelu(data, approximate=False)
    if act_type == "selu":
        return jnn.selu(data)
    if act_type == "prelu":
        if gamma is None:
            raise ValueError("LeakyReLU(act_type='prelu') needs gamma")
        shape = [1] * data.ndim
        if data.ndim > 1:
            shape[1] = gamma.size
        g = gamma.reshape(shape)
        return jnp.where(data > 0, data, g * data)
    if act_type == "rrelu":
        # eval mode: the reference uses the mean slope; train-mode random
        # slopes need an explicit key — use leaky with the mean
        mean_slope = (lower_bound + upper_bound) / 2.0
        return jnn.leaky_relu(data, negative_slope=mean_slope)
    raise ValueError(f"unknown act_type {act_type!r}")


@register("SyncBatchNorm", aliases=("_contrib_SyncBatchNorm",))
def sync_batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-3,
                    momentum=0.9, fix_gamma=False, use_global_stats=False,
                    ndev=1, key=None, output_mean_var=False, training=False):
    """Cross-device BatchNorm (reference contrib/sync_batch_norm.cc).

    TPU-first: inside pjit/shard_map with the batch axis sharded, the
    jnp.mean reductions in batch_norm lower to XLA all-reduces over the
    mesh automatically, so plain BatchNorm IS sync-BN under GSPMD — this
    op exists for API parity and single-process use (where it equals
    BatchNorm; the reference's ndev/key coordination fields are accepted
    and unused).
    """
    return batch_norm.fn(x, gamma, beta, moving_mean, moving_var, eps=eps,
                         momentum=momentum, fix_gamma=fix_gamma,
                         use_global_stats=use_global_stats,
                         output_mean_var=output_mean_var, training=training)


@register("softmax_xent", num_inputs=2)
def softmax_xent(logits, labels):
    """Fused softmax cross-entropy over the trailing axis: per-row
    logsumexp(logits) - logits[label] in one Pallas pass on TPU, the
    XLA formulation elsewhere (gated here like the other pallas-backed
    ops; the kernel itself always runs in tests via interpret mode).
    The softmax probabilities never hit HBM — the memory bottleneck of
    big-vocab LM training (reference loss_binary_op.cc recast
    blockwise).  Output dtype follows logits like the log_softmax+pick
    formulation."""
    from . import pallas_kernels as pk

    def xla(logits, lbl):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        # pick(mode='clip') semantics, same as the Pallas kernel: padding
        # labels like -1 clamp to a valid row instead of wrapping
        safe = jnp.clip(lbl, 0, logits.shape[-1] - 1)
        return -jnp.take_along_axis(lp, safe[:, None], axis=-1)[:, 0]

    out = pk.dispatch(pk.fused_softmax_xent, xla, logits,
                      labels.astype(jnp.int32))
    return out.astype(logits.dtype)


# ---------------------------------------------------------------------------
# im2col / col2im (reference src/operator/nn/im2col.h surfaced as ops)
# ---------------------------------------------------------------------------

def _im2col_impl(x, kernel, stride, dilate, pad):
    nd_sp = x.ndim - 2
    kernel = _pair(kernel, nd_sp)
    stride = _pair(stride or 1, nd_sp)
    dilate = _pair(dilate or 1, nd_sp)
    pad = _pair(pad or 0, nd_sp)
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=tuple(kernel), window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=("NCHW", "OIHW", "NCHW") if nd_sp == 2 else
        ("NCW", "OIW", "NCW"))
    # (N, C*K, *out_spatial) -> (N, C*K, L), reference layout
    return patches.reshape(patches.shape[0], patches.shape[1], -1)


@register("im2col", num_inputs=1)
def im2col(x, kernel=None, stride=None, dilate=None, pad=None):
    """Unfold conv patches to columns: (N,C,*sp) -> (N, C*prod(k), L)
    (reference im2col.h; channel-major patch layout)."""
    return _im2col_impl(x, kernel, stride, dilate, pad)


@register("col2im", num_inputs=1)
def col2im(col, output_size=None, kernel=None, stride=None, dilate=None,
           pad=None):
    """Fold columns back with overlap-add — exactly im2col's adjoint,
    realized through its transpose (reference col2im in im2col.h)."""
    import numpy as _onp
    n = col.shape[0]
    kernel = _pair(kernel, len(output_size))
    c = col.shape[1] // int(_onp.prod(kernel))
    shape = (n, c) + tuple(output_size)
    zero = jnp.zeros(shape, col.dtype)
    _, vjp = jax.vjp(
        lambda x: _im2col_impl(x, kernel, stride, dilate, pad), zero)
    (out,) = vjp(col)
    return out


@register("softmax_cross_entropy", num_inputs=2)
def softmax_cross_entropy(data, label):
    """Total cross-entropy of softmax(data) vs integer labels, summed
    over the batch into a scalar; differentiable in data like the
    reference (loss_binary_op.cc:30 + SoftmaxCrossEntropyGrad)."""
    lp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    lbl = jnp.clip(label.astype(jnp.int32), 0, data.shape[-1] - 1)
    picked = jnp.take_along_axis(lp, lbl[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked).reshape((1,))


@register("IdentityAttachKLSparseReg", num_inputs=1)
def identity_attach_kl_sparse_reg(x, sparseness_target=0.1, penalty=0.001,
                                  momentum=0.9):
    """Identity forward; backward adds the KL-sparseness penalty
    gradient  penalty * (-t/rho + (1-t)/(1-rho))  where rho is the mean
    activation (reference identity_attach_KL_sparse_reg-inl.h:109).
    Functional form uses the batch mean (the reference's moving average
    is an aux state; ``momentum`` is accepted for signature parity)."""

    @jax.custom_vjp
    def _identity(v):
        return v

    def _fwd(v):
        return v, jnp.mean(v, axis=0)

    def _bwd(rho, g):
        rho = jnp.clip(rho, 1e-6, 1 - 1e-6)
        reg = penalty * (-sparseness_target / rho
                         + (1 - sparseness_target) / (1 - rho))
        return (g + reg,)

    _identity.defvjp(_fwd, _bwd)
    return _identity(x)


@register("BatchNorm_v1", num_inputs=5)
def batch_norm_v1(x, gamma, beta, moving_mean, moving_var, eps=1e-3,
                  momentum=0.9, fix_gamma=True, use_global_stats=False,
                  output_mean_var=False, training=False):
    """Legacy BatchNorm_v1 (reference batch_norm_v1.cc) — axis-1 only,
    served by the modern implementation."""
    return batch_norm.fn(x, gamma, beta, moving_mean, moving_var, eps=eps,
                         momentum=momentum, fix_gamma=fix_gamma,
                         use_global_stats=use_global_stats,
                         output_mean_var=output_mean_var,
                         training=training)
