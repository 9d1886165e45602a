"""Hand-written Pallas TPU kernels for bandwidth-bound hot ops.

The reference hand-fuses these with NVRTC-generated CUDA (softmax
src/operator/nn/softmax-inl.h, layernorm src/operator/nn/layer_norm.cc —
both memory-bound rowwise reductions) and has no flash attention (it
predates it). The TPU-native design keeps XLA as the default fuser and
reaches for Pallas only where a manual schedule beats it:

* ``fused_softmax``   — one VMEM-resident pass per row block, fused
  max/exp/sum, custom fused backward.
* ``fused_layer_norm``— single pass mean/rstd + affine, backward kernel
  emitting dx and per-block dgamma/dbeta partials.
* ``flash_attention`` — blockwise attention, O(T) memory: a forward kernel
  (one pass with a plain softmax where a head's keys fit one block, the
  online rescale beyond) and a backward kernel that recomputes the
  probabilities from the saved row log-sum-exp; no T×T tensor in HBM.

Kernels run in interpret mode off-TPU so CPU tests exercise identical
code paths; wrappers pad to TPU tile boundaries ((8,128) f32) and mask.
Whether an op routes to its kernel is decided by the platform and the
shape alone (:func:`dispatch`): ``MXNET_USE_PALLAS`` ∈ {"0","1","auto"}
gates it from the op layer (auto = where the computation lowers for a
TPU), and rows wider than ``_MAX_COLS`` ride the XLA formulation.  That every kernel compiles
under Mosaic at real widths is pinned by tests/test_tpu_compile.py
(described-topology compiles) and proven on the chip by
``chip_smoke.py``'s ``kernels`` phase.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import profiler as _profiler
from ..locks import named_lock

__all__ = ["fused_softmax", "fused_layer_norm", "flash_attention",
           "dispatch", "route", "kernel_name", "kernel_routes",
           "attention_plans",
           "repeat_kv_heads",
           "interpret_mode",
           "gspmd_trace", "fused_softmax_xent", "fused_rms_norm"]

_NEG_INF = -1e30


def interpret_mode() -> bool:
    """Pallas interpret mode: on unless running on a real TPU backend.
    A backend that fails to initialize raises here — it is never read
    as "no TPU"."""
    return jax.default_backend() != "tpu"


_trace = threading.local()


@contextlib.contextmanager
def gspmd_trace(over_mesh=True):
    """Mark what is traced inside as a program GSPMD partitions over a
    mesh (``jit`` with mesh shardings).  A Mosaic kernel cannot be
    partitioned automatically — jax refuses the program and asks for a
    ``shard_map`` around the call — so in here :func:`dispatch` routes
    every op to its XLA composition, which GSPMD partitions natively."""
    was = getattr(_trace, "gspmd", False)
    _trace.gspmd = was or bool(over_mesh)
    try:
        yield
    finally:
        _trace.gspmd = was


_FORCED = ("1", "true", "on")


def _flag():
    return os.environ.get("MXNET_USE_PALLAS", "auto").lower()


def route(unless=None):
    """What :func:`dispatch` decides for a call whose op gave ``unless``:
    ``"kernel"`` or ``"xla:<why>"``."""
    if unless is not None:
        return "xla:" + unless
    if _flag() in ("0", "false", "off"):
        return "xla:flag"
    if _flag() in _FORCED:
        return "kernel"
    if jax.default_backend() != "tpu":
        return "xla:no_tpu"
    if getattr(_trace, "gspmd", False):
        return "xla:gspmd"
    return "kernel"


def dispatch(kernel, xla, *args, unless=None):
    """Route one op call to ``kernel(*args)`` (a Pallas wrapper) or to
    ``xla(*args)`` (its XLA composition, same contract), from what can
    be observed:

    * ``unless``: what the op itself saw in this call that the kernel
      does not take (``"mask"``, ``"dtype"`` ...) — the composition;
    * ``MXNET_USE_PALLAS`` '0' forces the composition, '1' the kernel
      (interpreted off-TPU — how the CPU tests reach the kernels);
    * 'auto' (default): a process without a TPU backend, and a program
      GSPMD partitions over a mesh (:func:`gspmd_trace`), get the
      composition; a process with one gets BOTH, under
      ``lax.platform_dependent``, and the lowering picks — the kernel
      where the computation is placed on a TPU, the composition where
      it is placed on the host's CPU (``mx.cpu()`` arrays on a TPU
      machine: Mosaic cannot lower there).

    Every decision is counted by kernel name and reason
    (:func:`kernel_routes`), when the call is traced.
    """
    chosen, name = route(unless), kernel_name(kernel)
    with _routes_lock:
        _routes[name][chosen] += 1
    with jax.named_scope(name):
        if chosen != "kernel":
            return xla(*args)
        if _flag() in _FORCED:
            return kernel(*args)
        return jax.lax.platform_dependent(*args, tpu=kernel, default=xla)


_routes = collections.defaultdict(collections.Counter)
_routes_lock = named_lock("ops.kernel_routes")


def kernel_routes(reset=False):
    """``{kernel name: {route: calls}}`` of every :func:`dispatch` decision
    so far: ``kernel``, or ``xla:<why>`` (``flag``, ``no_tpu``, ``gspmd``,
    or what the op gave as ``unless``).  Counted where an op's body is
    traced, so a jitted op counts once for each distinct signature it is
    traced with, not once a run.  The ``kernel_routes`` provider of
    ``profiler.dumps()``."""
    with _routes_lock:
        out = {name: dict(routes) for name, routes in sorted(_routes.items())}
        if reset:
            _routes.clear()
    return out


_profiler.register_stats_provider("kernel_routes", kernel_routes)


def kernel_name(kernel):
    """The name an op's kernel is known by in a trace: its wrapper's own
    (``functools.partial`` unwrapped) less a leading ``_`` and ``fused_``
    -- ``fused_layer_norm`` -> ``layer_norm``.  :func:`dispatch` runs
    whichever side it picks under this ``jax.named_scope``, so the XLA
    composition is found under the same name as the kernel it stands in
    for, and each ``pl.pallas_call`` below is named ``<it>_fwd`` /
    ``<it>_bwd``."""
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    name = getattr(kernel, "__name__", "kernel").strip("<>_")
    return name.removeprefix("fused_")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_rows_cols(x2d, row_mult, col_mult):
    rows, cols = x2d.shape
    pr, pc = _round_up(rows, row_mult), _round_up(cols, col_mult)
    if (pr, pc) != (rows, cols):
        x2d = jnp.pad(x2d, ((0, pr - rows), (0, pc - cols)))
    return x2d, rows, cols


# ======================================================================
# fused softmax
# ======================================================================

_BLOCK_ROWS = 256
_MAX_COLS = 16384  # one row must fit VMEM; beyond this fall back to XLA


def _softmax_fwd_kernel(x_ref, o_ref, *, n_cols):
    x = x_ref[:].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < n_cols, x, _NEG_INF)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    o_ref[:] = (e / s).astype(o_ref.dtype)


def _softmax_bwd_kernel(y_ref, g_ref, o_ref):
    y = y_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    inner = jnp.sum(y * g, axis=-1, keepdims=True)
    o_ref[:] = (y * (g - inner)).astype(o_ref.dtype)


# Bytes one copy of a row-wise kernel's blocks may take, counted as
# f32.  Mosaic double-buffers every pipelined block and the kernels keep
# two or three block-sized f32 temporaries, so what the compiler is
# asked for is four times this — 32 MiB of a v5e core's 128; under its
# 16 MiB default the _MAX_COLS-wide rows do not compile.
_VMEM_BUDGET = 8 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=4 * _VMEM_BUDGET)


def _rowwise_block(rows_p, cols_p, n_buffers):
    """Row-block size honoring the VMEM budget: wide rows shrink the
    block so n_buffers f32 blocks of (block_r, cols_p) stay inside
    VMEM (at _MAX_COLS=16384 a fixed 256-row block would need ~16 MB
    per buffer and fail Mosaic compilation on real TPUs)."""
    by_budget = _VMEM_BUDGET // (cols_p * 4 * n_buffers)
    block_r = max(8, min(_BLOCK_ROWS, by_budget) // 8 * 8)
    return min(block_r, _round_up(rows_p, 8))


def _rowwise_call(kernel, name, out_dtype, n_inputs, x2d_list):
    rows_p, cols_p = x2d_list[0].shape
    block_r = _rowwise_block(rows_p, cols_p, n_inputs + 1)
    spec = pl.BlockSpec((block_r, cols_p), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows_p, cols_p), out_dtype),
        grid=(pl.cdiv(rows_p, block_r),),
        in_specs=[spec] * n_inputs,
        out_specs=spec,
        interpret=interpret_mode(),
        compiler_params=_COMPILER_PARAMS,
        name=name,
    )(*x2d_list)


def _col_partial(a):
    """Column sums of a (block_r, cols) tile kept as EIGHT sublane rows:
    Mosaic wants the last two block dims divisible by (8, 128), so a
    per-row-block partial is an (8, cols) tile the caller sums, never a
    (1, cols) one.  Folding rows onto sublanes is plain vector adds —
    no cross-sublane reduce in the kernel."""
    return a.reshape(a.shape[0] // 8, 8, a.shape[1]).sum(axis=0)


def _row_valid(block_r, n_rows):
    """(block_r, 1) mask of the rows of this grid step that exist in
    the unpadded input: a ragged last block reads whatever lies past
    the array, and column sums must not see it."""
    row = pl.program_id(0) * block_r + jax.lax.broadcasted_iota(
        jnp.int32, (block_r, 1), 0)
    return row < n_rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def fused_softmax(x, axis=-1):
    """Numerically-stable softmax as a single Pallas pass per row block
    (reference softmax FCompute, src/operator/nn/softmax-inl.h)."""
    return _fused_softmax_impl(x, axis)


def _fused_softmax_impl(x, axis):
    if x.shape[axis] > _MAX_COLS or x.ndim == 0:
        return jax.nn.softmax(x, axis=axis)
    moved = jnp.moveaxis(x, axis, -1)
    lead = moved.shape[:-1]
    x2d = moved.reshape(-1, moved.shape[-1])
    x2d_p, rows, cols = _pad_rows_cols(x2d, 8, 128)
    out = _rowwise_call(
        functools.partial(_softmax_fwd_kernel, n_cols=cols),
        "softmax_fwd", x.dtype, 1, [x2d_p])
    out = out[:rows, :cols].reshape(*lead, cols)
    return jnp.moveaxis(out, -1, axis)


def _fused_softmax_fwd(x, axis):
    y = _fused_softmax_impl(x, axis)
    return y, y


def _fused_softmax_bwd(axis, y, g):
    if y.shape[axis] > _MAX_COLS:
        inner = jnp.sum(y * g, axis=axis, keepdims=True)
        return (y * (g - inner),)
    ym = jnp.moveaxis(y, axis, -1)
    gm = jnp.moveaxis(g, axis, -1)
    lead = ym.shape[:-1]
    y2d, rows, cols = _pad_rows_cols(ym.reshape(-1, ym.shape[-1]), 8, 128)
    g2d, _, _ = _pad_rows_cols(gm.reshape(-1, gm.shape[-1]), 8, 128)
    dx = _rowwise_call(_softmax_bwd_kernel, "softmax_bwd", y.dtype, 2,
                       [y2d, g2d])
    dx = dx[:rows, :cols].reshape(*lead, cols)
    return (jnp.moveaxis(dx, -1, axis),)


fused_softmax.defvjp(_fused_softmax_fwd, _fused_softmax_bwd)


# ======================================================================
# fused layer norm (normalize over the last axis)
# ======================================================================

def _ln_fwd_kernel(x_ref, gamma_ref, beta_ref, o_ref, mean_ref, rstd_ref,
                   *, n_cols, eps):
    x = x_ref[:].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < n_cols
    xv = jnp.where(valid, x, 0.0)
    mean = jnp.sum(xv, axis=-1, keepdims=True) / n_cols
    diff = jnp.where(valid, x - mean, 0.0)
    var = jnp.sum(diff * diff, axis=-1, keepdims=True) / n_cols
    rstd = jax.lax.rsqrt(var + eps)
    xhat = diff * rstd
    g = gamma_ref[:].astype(jnp.float32)
    b = beta_ref[:].astype(jnp.float32)
    o_ref[:] = (xhat * g + b).astype(o_ref.dtype)
    mean_ref[:] = mean.astype(jnp.float32)
    rstd_ref[:] = rstd.astype(jnp.float32)


def _ln_bwd_kernel(x_ref, g_ref, gamma_ref, mean_ref, rstd_ref,
                   dx_ref, dgamma_ref, dbeta_ref, *, n_rows, n_cols):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    gamma = gamma_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = (col < n_cols) & _row_valid(x.shape[0], n_rows)
    xhat = jnp.where(valid, (x - mean) * rstd, 0.0)
    gv = jnp.where(valid, g, 0.0)
    # dx = rstd * (gγ − mean(gγ) − xhat·mean(gγ·xhat))
    ggam = gv * gamma
    m1 = jnp.sum(ggam, axis=-1, keepdims=True) / n_cols
    m2 = jnp.sum(ggam * xhat, axis=-1, keepdims=True) / n_cols
    dx = (ggam - m1 - xhat * m2) * rstd
    dx_ref[:] = jnp.where(valid, dx, 0.0).astype(dx_ref.dtype)
    # per-row-block partials, reduced across blocks by the caller
    dgamma_ref[:] = _col_partial(gv * xhat)
    dbeta_ref[:] = _col_partial(gv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, gamma, beta, eps=1e-5):
    """LayerNorm over the trailing axis in one fused pass (reference
    LayerNormCompute, src/operator/nn/layer_norm.cc)."""
    y, _, _ = _ln_fwd(x, gamma, beta, eps)
    return y


def _ln_fwd(x, gamma, beta, eps):
    lead = x.shape[:-1]
    cols = x.shape[-1]
    x2d = x.reshape(-1, cols)
    x2d_p, rows, _ = _pad_rows_cols(x2d, 8, 128)
    rows_p, cols_p = x2d_p.shape
    gamma_p = jnp.pad(gamma.astype(x.dtype), (0, cols_p - cols))
    beta_p = jnp.pad(beta.astype(x.dtype), (0, cols_p - cols))
    block_r = _rowwise_block(rows_p, cols_p, 2)  # x block + y block
    grid = (pl.cdiv(rows_p, block_r),)
    row_spec = pl.BlockSpec((block_r, cols_p), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, cols_p), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, n_cols=cols, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((rows_p, cols_p), x.dtype),
                   jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows_p, 1), jnp.float32)),
        grid=grid,
        in_specs=[row_spec, vec_spec, vec_spec],
        out_specs=(row_spec, stat_spec, stat_spec),
        interpret=interpret_mode(),
        compiler_params=_COMPILER_PARAMS,
        name="layer_norm_fwd",
    )(x2d_p, gamma_p.reshape(1, -1), beta_p.reshape(1, -1))
    return y[:rows, :cols].reshape(*lead, cols), mean, rstd


def _fused_ln_fwd(x, gamma, beta, eps):
    y, mean, rstd = _ln_fwd(x, gamma, beta, eps)
    return y, (x, gamma, mean, rstd)


def _fused_ln_bwd(eps, res, g):
    x, gamma, mean, rstd = res
    lead = x.shape[:-1]
    cols = x.shape[-1]
    x2d = x.reshape(-1, cols)
    g2d = g.reshape(-1, cols)
    x2d_p, rows, _ = _pad_rows_cols(x2d, 8, 128)
    g2d_p, _, _ = _pad_rows_cols(g2d, 8, 128)
    rows_p, cols_p = x2d_p.shape
    gamma_p = jnp.pad(gamma.astype(jnp.float32), (0, cols_p - cols))
    block_r = _rowwise_block(rows_p, cols_p, 3)  # x + g + dx blocks
    n_blocks = pl.cdiv(rows_p, block_r)
    row_spec = pl.BlockSpec((block_r, cols_p), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, cols_p), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    part_spec = pl.BlockSpec((8, cols_p), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    dx, dgamma_part, dbeta_part = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, n_rows=rows, n_cols=cols),
        out_shape=(jax.ShapeDtypeStruct((rows_p, cols_p), x.dtype),
                   jax.ShapeDtypeStruct((8 * n_blocks, cols_p),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((8 * n_blocks, cols_p),
                                        jnp.float32)),
        grid=(n_blocks,),
        in_specs=[row_spec, row_spec, vec_spec, stat_spec, stat_spec],
        out_specs=(row_spec, part_spec, part_spec),
        interpret=interpret_mode(),
        compiler_params=_COMPILER_PARAMS,
        name="layer_norm_bwd",
    )(x2d_p, g2d_p, gamma_p.reshape(1, -1), mean, rstd)
    dx = dx[:rows, :cols].reshape(*lead, cols)
    dgamma = dgamma_part.sum(axis=0)[:cols].astype(gamma.dtype)
    dbeta = dbeta_part.sum(axis=0)[:cols].astype(gamma.dtype)
    return dx, dgamma, dbeta


fused_layer_norm.defvjp(_fused_ln_fwd, _fused_ln_bwd)


# ======================================================================
# flash attention: softmax(QKᵀ·scale)·V block by block, forward and
# backward, no (T, S) score tensor in HBM
# ======================================================================

# Query rows and keys of one block, and the scores one grid step works
# on: a step takes as many heads together as that allows (a step costs
# ~0.35 us whatever it holds, and short sequences would pay it for
# little work).  Measured on a v5e at (32, 12, 512, 64) bfloat16, forward
# + backward of one layer with its head transposes (my chip runs, PR 28):
# 512 x 512 blocks with 2 / 4 heads a step 1.27 / 1.23 ms, the XLA
# composition 4.75; with (T, D) heads, 1 / 2 / 4 heads 2.06 / 1.93 / 1.87,
# 256 x 512 blocks 2.19, 512 x 256 2.29, 256 x 256 2.79 (two key blocks:
# the online rescale).  No length was found below which the kernels
# lose: at (32, 12, 128, 64) both sides sit on the ~0.25 ms floor of one
# dispatch, at (64, 12, 256, 64) 1.75 against 2.59.
# A long causal call wants another schedule than that one-block call
# (_AttnPlan).  One layer at (2, 32, 4096, 192 / 128) causal bfloat16, the
# forward and the backward kernel's call alone, ms (my chip runs, PR 31):
# one head a step on the 8 x 8 grid of every block pair (the parent)
# 4.81 + 7.29; a grid over the 36 live pairs alone 4.14 + 7.17 (a dead
# step cost ~0.37 us forward, under 0.1 us backward); heads a step for each
# kernel apart, 4 forward and 2 backward, 3.51 + 6.78 (2 forward: 3.65;
# 8: 3.30; 2 heads both ways on the parent's grid: 4.25 + 6.87); the causal
# select on the 8 diagonal pairs only 3.41 + 6.76; the next head's scores
# issued before a head's softmax 3.15 + 6.72 (the same order in the
# backward bought 0.03: left out); 4 heads backward under the 38 MiB that
# takes 3.15 + 6.56.  In the step: 3.12 + 6.53 (4.75 + 7.11 before).
_ATTN_BQ = 512
_ATTN_BK = 512
_ATTN_SCORES = 4 * 512 * 512

_NN = (((1,), (0,)), ((), ()))      # a · b
_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


def _attn_block(t, cap):
    """``(block, padded length)`` along a sequence: lengths pad to the
    128-lane tile, and the block is the largest multiple of 128 up to
    ``cap`` that divides the padded length."""
    tp = _round_up(t, 128)
    return max(b for b in range(128, min(cap, tp) + 1, 128)
               if tp % b == 0), tp


def _attn_dot(a, b, dims):
    """An MXU dot with float32 accumulation: operands enter in their own
    dtype, float32 ones at ``Precision.HIGHEST`` (the float32 reference
    step must agree with the host to 1e-3)."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                   else None))


# Both kernels work on TRANSPOSED heads and scores: q, k, v, the output
# and every gradient are (D, T) a head, a block of scores is (keys,
# queries).  A row's maximum and sum then run down the sublanes —
# elementwise over vregs, on the VPU — where a (queries, keys) tile
# reduces every row across its 128 lanes on the XLU; the row statistics
# come out one a lane, which is how the log-sum-exp is stored; every dot
# but the scores' takes its operands as they lie; and no array in HBM has
# a head's width (64) as its minor dimension, which the (8, 128) tiling
# pads to 128 lanes — (B, H, D, T) is also the layout XLA itself picks for
# a (B, H, T, D) bfloat16 array, so no layout copy surrounds the calls.

def _heads_t(x):
    """(B, H, T, D) → (B·H, D, T)."""
    b, h, t, d = x.shape
    return jnp.swapaxes(x, 2, 3).reshape(b * h, d, t)


def _heads_back(xt, lead):
    """(B·H, D, T) → (B, H, T, D)."""
    return jnp.swapaxes(xt.reshape(*lead, *xt.shape[1:]), 2, 3)


def _attn_scores_t(q_ref, k_ref, h, scale):
    """The scaled (D, queries) block of head ``h`` (the scale folded into
    it, in its own dtype) and its float32 scores against the key block,
    (keys, queries), unmasked."""
    qt = (q_ref[h] * scale).astype(q_ref.dtype)
    return qt, _attn_dot(k_ref[h], qt, _TN)


def _attn_mask(st, qi, kb, causal, t_kv):
    """The scores of block (qi, kb) with those of keys past the unpadded
    length and, if causal, after their query at ``_NEG_INF``."""
    shape = bk, bq = st.shape
    key = kb * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    mask = key < t_kv if t_kv % bk else None
    if causal:
        ahead = key <= qi * bq + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = ahead if mask is None else mask & ahead
    return jnp.where(mask, st, _NEG_INF)


def _attn_masked(qi, kb, bq, bk, causal, t_kv, n_q, n_kv):
    """Whether block (qi, kb) holds a score to mask.  A dense call: where
    the keys are padded (every block pays, or none).  A causal call of
    one block: always.  A causal call of several blocks: a scalar of the
    grid step, true where the block straddles the diagonal, and on the
    last key block where that one is padded; every other live block is
    dense and costs no select."""
    if not causal:
        return bool(t_kv % bk)
    if n_q == n_kv == 1:
        return True
    masked = kb * bk + bk - 1 > qi * bq
    return masked | (kb == n_kv - 1) if t_kv % bk else masked


def _masked_or_dense(masked, step):
    """``step(True)`` or ``step(False)`` by ``masked``, a bool of the
    trace or a scalar of the grid step (then both bodies are compiled and
    a step runs one)."""
    if isinstance(masked, bool):
        step(masked)
    else:
        pl.when(masked)(lambda: step(True))
        pl.when(jnp.logical_not(masked))(lambda: step(False))


def _attn_pair(tables, q_axis):
    """(qi, kb) of this grid step: read from the live pairs' tables where
    the grid runs over those (axis 1), else the two inner grid axes
    (q blocks on ``q_axis``)."""
    if tables:
        qi_tab, kb_tab = tables
        return qi_tab[pl.program_id(1)], kb_tab[pl.program_id(1)]
    ids = None, pl.program_id(1), pl.program_id(2)
    return ids[q_axis], ids[3 - q_axis]


def _attn_last_kb(qi, bq, bk, n_kv, live):
    """The last key block q block ``qi`` meets: on the live grid the one
    its diagonal crosses."""
    if not live:
        return n_kv - 1
    return jnp.minimum(n_kv - 1, ((qi + 1) * bq - 1) // bk)


def _flash_fwd_kernel(*refs, scale, causal, t_kv, n_q, n_kv, live):
    """Grid (head groups, q blocks, key blocks), or (head groups, live
    pairs) ordered by q block then key block.  One key block: a plain
    softmax in one pass.  Several: the online rescale, with the running
    maximum, sum and accumulator in scratch across a q block's keys."""
    tables, refs = (refs[:2], refs[2:]) if live else ((), refs)
    q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch = refs
    hb = q_ref.shape[0]
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    qi, kb = _attn_pair(tables, 1)
    masked = _attn_masked(qi, kb, bq, bk, causal, t_kv, n_q, n_kv)

    def scores_t(h, masked):
        st = _attn_scores_t(q_ref, k_ref, h, scale)[1]
        return _attn_mask(st, qi, kb, causal, t_kv) if masked else st

    def pv_t(pt, h):                        # (D, queries)
        return _attn_dot(v_ref[h], pt.astype(v_ref.dtype), _NN)

    def finish(h, acc_t, m, l):
        o_ref[h] = (acc_t * (1.0 / l)).astype(o_ref.dtype)
        lse_ref[h] = m + jnp.log(l)

    if n_kv == 1:
        def one_pass(masked):
            for h in range(hb):
                st = scores_t(h, masked)
                m = jnp.max(st, axis=0, keepdims=True)
                pt = jnp.exp(st - m)
                finish(h, pv_t(pt, h), m,
                       jnp.sum(pt, axis=0, keepdims=True))

        _masked_or_dense(masked, one_pass)
        return

    m_scr, l_scr, acc_scr = scratch

    @pl.when(kb == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def rescale(masked):
        st = scores_t(0, masked)
        for h in range(hb):
            # the next head's scores are issued before this head's
            # softmax: a dot for the MXU beside the VPU's work
            ahead = scores_t(h + 1, masked) if h + 1 < hb else None
            m_prev = m_scr[h]
            m = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m)
            pt = jnp.exp(st - m)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(pt, axis=0, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + pv_t(pt, h)
            m_scr[h] = m
            st = ahead

    _masked_or_dense(masked, rescale)

    @pl.when(kb == _attn_last_kb(qi, bq, bk, n_kv, live))
    def _():
        for h in range(hb):
            finish(h, acc_scr[h], m_scr[h], l_scr[h])


def _flash_bwd_kernel(*refs, scale, causal, t_kv, n_q, n_kv, live):
    """Grid (head groups, key blocks, q blocks), or (head groups, live
    pairs) ordered by key block then q block: the probabilities of a
    block are recomputed from the saved row log-sum-exp; dk and dv add up
    over a key block's q blocks, dq over the key blocks in a block that
    holds the head's whole dq (resident in VMEM until the head group
    changes)."""
    tables, refs = (refs[:2], refs[2:]) if live else ((), refs)
    (q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
     dq_ref, dk_ref, dv_ref, *scratch) = refs
    hb = q_ref.shape[0]
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    qi, kb = _attn_pair(tables, 2)
    masked = _attn_masked(qi, kb, bq, bk, causal, t_kv, n_q, n_kv)
    dt = q_ref.dtype
    cols = pl.ds(pl.multiple_of(qi * bq, bq), bq)
    dq_acc = scratch.pop(0) if n_kv > 1 else None
    dk_acc, dv_acc = scratch if n_q > 1 else (None, None)
    # the first q block a key block meets: on the live grid the one the
    # diagonal crosses it in
    first_qi = (kb * bk) // bq if live else 0

    if n_q > 1:
        @pl.when(qi == first_qi)
        def _():
            dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
            dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def block(masked):
        for h in range(hb):
            qt, st = _attn_scores_t(q_ref, k_ref, h, scale)
            if masked:
                st = _attn_mask(st, qi, kb, causal, t_kv)
            do_t = do_ref[h]
            pt = jnp.exp(st - lse_ref[h])
            delta = jnp.sum(do_t.astype(jnp.float32)
                            * o_ref[h].astype(jnp.float32),
                            axis=0, keepdims=True)
            dst = (pt * (_attn_dot(v_ref[h], do_t, _TN) - delta)).astype(dt)
            dv_t = _attn_dot(do_t, pt.astype(dt), _NT)
            dk_t = _attn_dot(qt, dst, _NT)      # qt carries the scale
            dq_t = _attn_dot(k_ref[h], dst, _NN) * scale
            if n_q == 1:
                dk_ref[h] = dk_t.astype(dk_ref.dtype)
                dv_ref[h] = dv_t.astype(dv_ref.dtype)
            else:
                dk_acc[h] += dk_t
                dv_acc[h] += dv_t
            if n_kv == 1:
                dq_ref[h, :, cols] = dq_t.astype(dq_ref.dtype)
            else:
                @pl.when(kb == 0)
                def _():
                    dq_acc[h, :, cols] = dq_t

                @pl.when(kb > 0)
                def _():
                    dq_acc[h, :, cols] += dq_t

    _masked_or_dense(masked, block)

    if n_q > 1:
        @pl.when(qi == n_q - 1)
        def _():
            dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
    if n_kv > 1:
        @pl.when(kb == _attn_last_kb(qi, bq, bk, n_kv, live))
        def _():
            for h in range(hb):
                dq_ref[h, :, cols] = dq_acc[h, :, cols].astype(dq_ref.dtype)


# The VMEM a call asks Mosaic for: the rows' limit as a rule, more (of a
# v5e core's 128 MiB) where the heads a step that ``_ATTN_SCORES`` allows
# need it.  A kernel's bill is its pipelined blocks twice (Mosaic double
# buffers them) and its scratch once, a head, and Mosaic's own scratch
# beside them: 1.3-1.5 MiB whatever the heads (described compiles, PR 31:
# the backward at (64, 192 / 128, 4,096) needs 10.29 / 19.05 / 36.55 MiB
# with 1 / 2 / 4 heads a step for a bill of 8.78 MiB a head).
_ATTN_VMEM = 4 * _VMEM_BUDGET
_ATTN_VMEM_MOST = 64 * 1024 * 1024
_ATTN_VMEM_OWN = 2 * 1024 * 1024


class _AttnPlan:
    """The schedule of one attention call over (B·H, D, T) operands:
    blocks, which (q block, key block) pairs get a grid step, and the
    heads a step takes in each kernel."""

    def __init__(self, qt, kt, vt, causal):
        self.bh, self.d, self.tq = qt.shape
        # v, the output and their gradients have a width of their own
        # (latent attention: 192 in q and k, 128 in v)
        self.dv = vt.shape[1]
        # under causal no query sees a key past the last query: those get
        # no block (their dk and dv are zero)
        self.tk = min(kt.shape[2], self.tq) if causal else kt.shape[2]
        self.bq, self.tqp = bq, _ = _attn_block(self.tq, _ATTN_BQ)
        self.bk, self.tkp = bk, _ = _attn_block(self.tk, _ATTN_BK)
        self.n_q, self.n_kv = self.tqp // bq, self.tkp // bk
        # the pairs that hold a score, by q block then key block; where
        # some do not (causal, several blocks each way) the grid runs
        # over these alone
        self.pairs = [(i, j) for i in range(self.n_q)
                      for j in range(self.n_kv)
                      if not causal or j * bk < (i + 1) * bq]
        self.live = len(self.pairs) < self.n_q * self.n_kv
        self.diagonal = sum(causal and j * bk + bk - 1 > i * bq
                            for i, j in self.pairs)
        item = qt.dtype.itemsize
        d, dv, tqp = self.d, self.dv, self.tqp
        # a head's q-side and key-side blocks: (q, o, lse) and (k, v);
        # the (1, block) float32 row statistics take eight sublanes
        q_side = item * (d + dv) * bq + 32 * bq
        k_side = item * (d + dv) * bk
        self.hb_fwd, self.vmem_fwd = self._heads(
            2 * (q_side + k_side)
            + (4 * dv * bq + 64 * bq if self.n_kv > 1 else 0))
        # the backward: do beside them, dk and dv out, the head's whole dq
        # out and (over several key blocks) its float32 sum
        self.hb_bwd, self.vmem_bwd = self._heads(
            2 * (q_side + item * dv * bq + 2 * k_side + item * d * tqp)
            + (4 * d * tqp if self.n_kv > 1 else 0)
            + (4 * (d + dv) * bk if self.n_q > 1 else 0))

    def _heads(self, per_head):
        """Heads a step of a kernel whose blocks and scratch take
        ``per_head`` bytes a head, and the VMEM limit it is compiled
        with: as many heads as ``_ATTN_SCORES`` allows, that divide B·H
        and fit ``_ATTN_VMEM_MOST``; one where not even one fits (then
        Mosaic refuses the call, beyond ~120k keys at width 64)."""
        most = min(_ATTN_SCORES // (self.bq * self.bk),
                   (_ATTN_VMEM_MOST - _ATTN_VMEM_OWN) // per_head)
        heads = max(n for n in range(1, max(1, most) + 1)
                    if self.bh % n == 0)
        return heads, min(_ATTN_VMEM_MOST, max(
            _ATTN_VMEM, _round_up(heads * per_head + _ATTN_VMEM_OWN,
                                  1024 * 1024)))

    def keys(self, xt):
        """The keys (or values) some query sees, in whole blocks."""
        return _fit_t(_fit_t(xt, self.tk), self.tkp)

    def stats(self):
        groups_fwd, groups_bwd = self.bh // self.hb_fwd, self.bh // self.hb_bwd
        return {"heads_fwd": self.hb_fwd, "heads_bwd": self.hb_bwd,
                "grid_steps_fwd": groups_fwd * len(self.pairs),
                "grid_steps_bwd": groups_bwd * len(self.pairs),
                "pairs": self.n_q * self.n_kv,
                "live_pairs": len(self.pairs),
                "diagonal_pairs": self.diagonal}

    def call(self, kernel, name, hb, vmem, q_axis, specs, out_shape, scratch,
             *operands):
        """The ``pallas_call`` of one kernel at ``hb`` heads a step under
        a VMEM limit of ``vmem`` bytes.
        ``specs(q_map, k_map)`` gives ``(in_specs, out_specs)`` from the
        index maps of a q-side and a key-side block; a rectangular grid
        has its q blocks on ``q_axis``, the live grid is ordered so that
        axis runs inside the other."""
        if self.live:
            order = sorted(self.pairs, key=lambda p: p[::-1]) \
                if q_axis == 2 else self.pairs
            tables = [jnp.asarray(t, jnp.int32) for t in zip(*order)]
            grid = (self.bh // hb, len(order))
            semantics = "parallel", "arbitrary"
            q_map = lambda g, p, qi, kb: (g, 0, qi[p])
            k_map = lambda g, p, qi, kb: (g, 0, kb[p])
        else:
            tables = []
            inner = (self.n_q, self.n_kv)
            grid = (self.bh // hb, *(inner if q_axis == 1 else inner[::-1]))
            # q blocks are independent; what adds up over key blocks, or in
            # the backward's resident dq over both, is not
            semantics = ("parallel", "parallel" if q_axis == 1
                         else "arbitrary", "arbitrary")
            q_map = lambda *ids: (ids[0], 0, ids[q_axis])
            k_map = lambda *ids: (ids[0], 0, ids[3 - q_axis])
        in_specs, out_specs = specs(q_map, k_map)
        return pl.pallas_call(
            functools.partial(kernel, n_q=self.n_q, n_kv=self.n_kv,
                              t_kv=self.tk, live=self.live),
            out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(tables), grid=grid,
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            interpret=interpret_mode(),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem,
                dimension_semantics=semantics),
            name=name,
        )(*tables, *operands)


def _attn_spec(hb, d, t_block, index):
    return pl.BlockSpec((hb, d, t_block), index, memory_space=pltpu.VMEM)


_plans = {}
_plans_lock = named_lock("ops.attention_plans")


def attention_plans(reset=False):
    """``{signature: plan}`` of every attention call the kernel pair was
    traced for so far: ``heads_fwd`` / ``heads_bwd`` a grid step,
    ``grid_steps_fwd`` / ``grid_steps_bwd`` of a whole call, and of one
    head group's ``pairs`` of (q block, key block) the ``live_pairs`` that
    get a step and the ``diagonal_pairs`` that pay for the causal mask.
    Written when the forward kernel's call is traced, so it counts
    signatures and not calls (a jitted op is traced once for equal
    shapes).  The ``attention_plans`` provider of ``profiler.dumps()``."""
    with _plans_lock:
        out = {sig: dict(plan) for sig, plan in sorted(_plans.items())}
        if reset:
            _plans.clear()
    return out


_profiler.register_stats_provider("attention_plans", attention_plans)


def _fit_t(xt, t):
    """The last (sequence) axis cut or zero-padded to ``t`` positions; no
    copy at a length that is ``t`` already."""
    have = xt.shape[2]
    if have < t:
        return jnp.pad(xt, ((0, 0), (0, 0), (0, t - have)))
    return xt if have == t else xt[:, :, :t]


def _flash_fwd(qt, kt, vt, sm_scale, causal):
    """(B·H, D, T) operands → the output (B·H, D, Tq) and the rows'
    log-sum-exp (B·H, 1, padded Tq) float32."""
    pn = _AttnPlan(qt, kt, vt, causal)
    hb, bq, bk = pn.hb_fwd, pn.bq, pn.bk
    with _plans_lock:
        _plans[f"bh{pn.bh} d{pn.d}/{pn.dv} t{pn.tq}x{kt.shape[2]} "
               f"{'causal' if causal else 'dense'} {qt.dtype}"] = pn.stats()

    def specs(q_map, k_map):
        return ([_attn_spec(hb, pn.d, bq, q_map),
                 _attn_spec(hb, pn.d, bk, k_map),
                 _attn_spec(hb, pn.dv, bk, k_map)],
                (_attn_spec(hb, pn.dv, bq, q_map),
                 _attn_spec(hb, 1, bq, q_map)))

    scratch = [] if pn.n_kv == 1 else [
        pltpu.VMEM((hb, 1, bq), jnp.float32),
        pltpu.VMEM((hb, 1, bq), jnp.float32),
        pltpu.VMEM((hb, pn.dv, bq), jnp.float32)]
    ot, lse = pn.call(
        functools.partial(_flash_fwd_kernel, scale=sm_scale, causal=causal),
        "flash_attention_fwd", hb, pn.vmem_fwd, 1, specs,
        (jax.ShapeDtypeStruct((pn.bh, pn.dv, pn.tqp), qt.dtype),
         jax.ShapeDtypeStruct((pn.bh, 1, pn.tqp), jnp.float32)),
        scratch, _fit_t(qt, pn.tqp), pn.keys(kt), pn.keys(vt))
    return _fit_t(ot, pn.tq), lse


def _flash_bwd(qt, kt, vt, ot, lse, do_t, sm_scale, causal):
    pn = _AttnPlan(qt, kt, vt, causal)
    hb, bq, bk = pn.hb_bwd, pn.bq, pn.bk

    def specs(q_map, k_map):
        q_spec, o_spec = (_attn_spec(hb, d, bq, q_map)
                          for d in (pn.d, pn.dv))
        k_spec, v_spec = (_attn_spec(hb, d, bk, k_map)
                          for d in (pn.d, pn.dv))
        return ([q_spec, k_spec, v_spec, o_spec,
                 _attn_spec(hb, 1, bq, q_map), o_spec],
                (_attn_spec(hb, pn.d, pn.tqp, lambda g, *_: (g, 0, 0)),
                 k_spec, v_spec))

    scratch = []
    if pn.n_kv > 1:
        scratch.append(pltpu.VMEM((hb, pn.d, pn.tqp), jnp.float32))
    if pn.n_q > 1:
        scratch += [pltpu.VMEM((hb, pn.d, bk), jnp.float32),
                    pltpu.VMEM((hb, pn.dv, bk), jnp.float32)]
    dq_t, dk_t, dv_t = pn.call(
        functools.partial(_flash_bwd_kernel, scale=sm_scale, causal=causal),
        "flash_attention_bwd", hb, pn.vmem_bwd, 2, specs,
        (jax.ShapeDtypeStruct((pn.bh, pn.d, pn.tqp), qt.dtype),
         jax.ShapeDtypeStruct((pn.bh, pn.d, pn.tkp), kt.dtype),
         jax.ShapeDtypeStruct((pn.bh, pn.dv, pn.tkp), vt.dtype)),
        scratch, _fit_t(qt, pn.tqp), pn.keys(kt), pn.keys(vt),
        _fit_t(ot, pn.tqp), lse, _fit_t(do_t, pn.tqp))
    # the keys no query sees get zeros
    return (_fit_t(dq_t, pn.tq),
            *(_fit_t(_fit_t(x, pn.tk), kt.shape[2]) for x in (dk_t, dv_t)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, sm_scale, causal):
    return _flash_vjp_fwd(q, k, v, sm_scale, causal)[0]


def _flash_vjp_fwd(q, k, v, sm_scale, causal):
    qt, kt, vt = _heads_t(q), _heads_t(k), _heads_t(v)
    ot, lse = _flash_fwd(qt, kt, vt, sm_scale, causal)
    return _heads_back(ot, q.shape[:2]), (qt, kt, vt, ot, lse)


def _flash_vjp_bwd(sm_scale, causal, res, g):
    qt = res[0]
    grads = _flash_bwd(*res, _heads_t(g.astype(qt.dtype)), sm_scale, causal)
    return tuple(_heads_back(x, g.shape[:2]) for x in grads)


_flash_core.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def repeat_kv_heads(q, k, v):
    """Grouped-query attention: ``k`` and ``v`` with fewer heads than ``q``
    (``Hq % Hkv == 0``), each repeated so that query head ``i`` reads key
    head ``i // (Hq / Hkv)``.  Exact, and its transpose sums ``dk`` and
    ``dv`` over a group's query heads.  Equal head counts come back as they
    are: no operation is traced."""
    hq, hkv = q.shape[1], k.shape[1]
    if hq == hkv:
        return k, v
    if hq % hkv or v.shape[1] != hkv:
        raise ValueError(f"attention: {hq} query heads over {hkv} key and "
                         f"{v.shape[1]} value heads")
    return tuple(jnp.repeat(x, hq // hkv, axis=1) for x in (k, v))


def flash_attention(q, k, v, sm_scale=None, causal=False):
    """softmax(QKᵀ·scale)·V over (B, H, T, D) operands as a pair of
    Pallas kernels, forward and backward: scores, probabilities and
    their gradients live in VMEM one block at a time, and what the
    backward pass keeps is q, k, v, the output and one float32 a row.
    ``v`` (and with it the output) may have a width of its own, as
    latent attention's has (192 in q and k, 128 in v): no operand is
    padded to another's width.  ``k`` and ``v`` may have fewer heads than
    ``q`` (:func:`repeat_kv_heads`: repeated here, under this kernel's
    scope, so what the repeat costs is counted as attention's).
    Inside, heads are (D, T): ``q``, ``k``, ``v`` are transposed on the
    way in and the results on the way out, which XLA folds into the
    transposes that make (B, H, T, D) out of a packed projection.
    New capability relative to the reference (which caps sequence length
    by device memory, SURVEY.md §5.7); pairs with
    parallel/ring_attention.py for the sequence-parallel path.

    Like the other wrappers here this always runs the kernel (interpreted
    off a TPU); the op ``dot_product_attention`` is the entry point that
    routes between it and the XLA composition.  ``causal`` masks key j
    for query i where j > i, as the composition's ``tril`` does.
    """
    scale = float(sm_scale) if sm_scale is not None else q.shape[-1] ** -0.5
    return _flash_core(q, *repeat_kv_heads(q, k, v), scale, bool(causal))


# ======================================================================
# fused softmax cross-entropy (big-vocab LM loss)
# ======================================================================

def _xent_fwd_kernel(x_ref, lbl_ref, loss_ref, *, n_cols):
    x = x_ref[...].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < n_cols
    x = jnp.where(valid, x, _NEG_INF)
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)) + m
    # clip-mode label semantics (generic path uses pick(mode="clip"))
    lbl = jnp.clip(lbl_ref[...].astype(jnp.int32), 0, n_cols - 1)
    picked = jnp.sum(jnp.where(col == lbl, x, 0.0), axis=-1, keepdims=True)
    loss_ref[...] = (lse - picked).astype(loss_ref.dtype)


def _xent_bwd_kernel(x_ref, lbl_ref, g_ref, dx_ref, *, n_cols):
    x = x_ref[...].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < n_cols
    x = jnp.where(valid, x, _NEG_INF)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    lbl = jnp.clip(lbl_ref[...].astype(jnp.int32), 0, n_cols - 1)
    onehot = (col == lbl).astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)  # (block_r, 1)
    dx = (p - onehot) * g
    dx_ref[...] = jnp.where(valid, dx, 0.0).astype(dx_ref.dtype)


def _xent_call(kernel, name, out_shape, x2d, lbl2d, *extra):
    rows_p, cols_p = x2d.shape
    block_r = _rowwise_block(rows_p, cols_p, 3)
    xspec = pl.BlockSpec((block_r, cols_p), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((block_r, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    out_spec = sspec if out_shape[1] == 1 else xspec
    in_specs = [xspec, sspec] + [sspec] * len(extra)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        grid=(pl.cdiv(rows_p, block_r),),
        in_specs=in_specs,
        out_specs=out_spec,
        interpret=interpret_mode(),
        compiler_params=_COMPILER_PARAMS,
        name=name,
    )(x2d, lbl2d, *extra)


@jax.custom_vjp
def fused_softmax_xent(logits, labels):
    """Per-row cross-entropy loss = logsumexp(logits) - logits[label],
    one Pallas pass — the softmax probabilities are never materialized
    in HBM, which is the memory bottleneck of big-vocab LM training
    (reference softmax_cross_entropy, src/operator/loss_binary_op.cc,
    recast blockwise).

    logits (N, C), labels int (N,) → loss (N,) float32.
    """
    loss, _ = _xent_fwd(logits, labels)
    return loss


def _xent_fwd(logits, labels):
    n, c = logits.shape
    if c > _MAX_COLS:
        lse = jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1)
        lbl = jnp.clip(labels.astype(jnp.int32), 0, c - 1)
        picked = jnp.take_along_axis(
            logits.astype(jnp.float32), lbl[:, None], axis=-1)[:, 0]
        return lse - picked, (logits, labels)
    x2d, rows, cols = _pad_rows_cols(logits, 8, 128)
    lbl2d, _, _ = _pad_rows_cols(labels.reshape(-1, 1).astype(jnp.int32),
                                 8, 1)
    loss = _xent_call(
        functools.partial(_xent_fwd_kernel, n_cols=cols),
        "softmax_xent_fwd", (x2d.shape[0], 1), x2d, lbl2d)
    return loss[:rows, 0], (logits, labels)


def _xent_vjp_fwd(logits, labels):
    return _xent_fwd(logits, labels)


def _xent_vjp_bwd(res, g):
    logits, labels = res
    n, c = logits.shape
    if c > _MAX_COLS:
        p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(
            jnp.clip(labels.astype(jnp.int32), 0, c - 1), c,
            dtype=jnp.float32)
        dx = (p - onehot) * g[:, None].astype(jnp.float32)
        return dx.astype(logits.dtype), None
    x2d, rows, cols = _pad_rows_cols(logits, 8, 128)
    lbl2d, _, _ = _pad_rows_cols(labels.reshape(-1, 1).astype(jnp.int32),
                                 8, 1)
    g2d, _, _ = _pad_rows_cols(
        g.reshape(-1, 1).astype(jnp.float32), 8, 1)
    dx = _xent_call(
        functools.partial(_xent_bwd_kernel, n_cols=cols),
        "softmax_xent_bwd", x2d.shape, x2d, lbl2d, g2d)
    return dx[:rows, :cols].astype(logits.dtype), None


fused_softmax_xent.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


# ======================================================================
# fused RMSNorm (transformer stack's norm; no reference counterpart —
# TPU-era addition like the RMSNorm op itself)
# ======================================================================

def _rms_fwd_kernel(x_ref, gamma_ref, o_ref, rrms_ref, *, n_cols, eps):
    x = x_ref[:].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < n_cols
    xv = jnp.where(valid, x, 0.0)
    ms = jnp.sum(xv * xv, axis=-1, keepdims=True) / n_cols
    rrms = jax.lax.rsqrt(ms + eps)
    g = gamma_ref[:].astype(jnp.float32)
    o_ref[:] = (xv * rrms * g).astype(o_ref.dtype)
    rrms_ref[:] = rrms.astype(jnp.float32)


def _rms_bwd_kernel(x_ref, g_ref, gamma_ref, rrms_ref, dx_ref, dgamma_ref,
                    *, n_rows, n_cols):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    gamma = gamma_ref[:].astype(jnp.float32)
    rrms = rrms_ref[:]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = (col < n_cols) & _row_valid(x.shape[0], n_rows)
    xv = jnp.where(valid, x, 0.0)
    gv = jnp.where(valid, g, 0.0)
    ggam = gv * gamma
    # dx = rrms*(gγ − x·(rrms²/n)·sum(gγ·x))
    s = jnp.sum(ggam * xv, axis=-1, keepdims=True)
    dx = rrms * (ggam - xv * (rrms * rrms) * s / n_cols)
    dx_ref[:] = jnp.where(valid, dx, 0.0).astype(dx_ref.dtype)
    # select, not multiply: rrms of a row past the array is garbage
    dgamma_ref[:] = _col_partial(jnp.where(valid, gv * xv * rrms, 0.0))


def fused_rms_norm(x, gamma, eps=1e-6):
    """RMSNorm over the trailing axis in one Pallas pass (fp32 stats,
    output in x.dtype) — the transformer stack's norm.  Rows wider than
    _MAX_COLS fall back to the XLA formulation like the sibling
    kernels (one row must fit VMEM)."""
    if x.shape[-1] > _MAX_COLS:
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                      keepdims=True)
        y = (x.astype(jnp.float32) * jax.lax.rsqrt(ms + eps))
        return (y * gamma.astype(jnp.float32)).astype(x.dtype)
    return _fused_rms_core(x, gamma, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fused_rms_core(x, gamma, eps):
    y, _ = _rms_fwd(x, gamma, eps)
    return y


def _rms_fwd(x, gamma, eps):
    lead = x.shape[:-1]
    cols = x.shape[-1]
    x2d = x.reshape(-1, cols)
    x2d_p, rows, _ = _pad_rows_cols(x2d, 8, 128)
    rows_p, cols_p = x2d_p.shape
    gamma_p = jnp.pad(gamma.astype(x.dtype), (0, cols_p - cols))
    block_r = _rowwise_block(rows_p, cols_p, 2)
    grid = (pl.cdiv(rows_p, block_r),)
    row_spec = pl.BlockSpec((block_r, cols_p), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, cols_p), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    y, rrms = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, n_cols=cols, eps=eps),
        out_shape=(jax.ShapeDtypeStruct((rows_p, cols_p), x.dtype),
                   jax.ShapeDtypeStruct((rows_p, 1), jnp.float32)),
        grid=grid,
        in_specs=[row_spec, vec_spec],
        out_specs=(row_spec, stat_spec),
        interpret=interpret_mode(),
        compiler_params=_COMPILER_PARAMS,
        name="rms_norm_fwd",
    )(x2d_p, gamma_p.reshape(1, -1))
    return y[:rows, :cols].reshape(*lead, cols), (x, gamma, rrms)


def _rms_vjp_fwd(x, gamma, eps):
    return _rms_fwd(x, gamma, eps)


def _rms_vjp_bwd(eps, res, g):
    x, gamma, rrms = res
    lead = x.shape[:-1]
    cols = x.shape[-1]
    x2d_p, rows, _ = _pad_rows_cols(x.reshape(-1, cols), 8, 128)
    g2d_p, _, _ = _pad_rows_cols(
        g.reshape(-1, cols).astype(x.dtype), 8, 128)
    rows_p, cols_p = x2d_p.shape
    gamma_p = jnp.pad(gamma.astype(x.dtype), (0, cols_p - cols))
    block_r = _rowwise_block(rows_p, cols_p, 3)
    n_blocks = pl.cdiv(rows_p, block_r)
    row_spec = pl.BlockSpec((block_r, cols_p), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    vec_spec = pl.BlockSpec((1, cols_p), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((block_r, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    part_spec = pl.BlockSpec((8, cols_p), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    dx, dgamma_parts = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, n_rows=rows, n_cols=cols),
        out_shape=(jax.ShapeDtypeStruct((rows_p, cols_p), x.dtype),
                   jax.ShapeDtypeStruct((8 * n_blocks, cols_p),
                                        jnp.float32)),
        grid=(n_blocks,),
        in_specs=[row_spec, row_spec, vec_spec, stat_spec],
        out_specs=(row_spec, part_spec),
        interpret=interpret_mode(),
        compiler_params=_COMPILER_PARAMS,
        name="rms_norm_bwd",
    )(x2d_p, g2d_p, gamma_p.reshape(1, -1), rrms)
    dgamma = dgamma_parts.sum(axis=0)[:cols].astype(gamma.dtype)
    return dx[:rows, :cols].reshape(*lead, cols), dgamma


_fused_rms_core.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)
