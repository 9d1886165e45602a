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
* ``flash_attention`` — blockwise online-softmax attention, O(T) memory,
  q-block grid with an inner lax.fori_loop over KV blocks; backward is a
  memory-efficient KV-block scan (recompute, no T×T materialization).

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

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_softmax", "fused_layer_norm", "flash_attention",
           "dispatch", "kernel_name", "interpret_mode", "gspmd_trace",
           "fused_softmax_xent", "fused_rms_norm"]

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


def dispatch(kernel, xla, *args):
    """Route one op call to ``kernel(*args)`` (a Pallas wrapper) or to
    ``xla(*args)`` (its XLA composition, same contract), from what can
    be observed:

    * ``MXNET_USE_PALLAS`` '0' forces the composition, '1' the kernel
      (interpreted off-TPU — how the CPU tests reach the kernels);
    * 'auto' (default): a process without a TPU backend, and a program
      GSPMD partitions over a mesh (:func:`gspmd_trace`), get the
      composition; a process with one gets BOTH, under
      ``lax.platform_dependent``, and the lowering picks — the kernel
      where the computation is placed on a TPU, the composition where
      it is placed on the host's CPU (``mx.cpu()`` arrays on a TPU
      machine: Mosaic cannot lower there).
    """
    flag = os.environ.get("MXNET_USE_PALLAS", "auto").lower()
    with jax.named_scope(kernel_name(kernel)):
        if flag in ("0", "false", "off"):
            return xla(*args)
        if flag in ("1", "true", "on"):
            return kernel(*args)
        if jax.default_backend() != "tpu" or getattr(_trace, "gspmd", False):
            return xla(*args)
        return jax.lax.platform_dependent(*args, tpu=kernel, default=xla)


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
# flash attention (blockwise online softmax)
# ======================================================================

_BQ = 128
_BK = 128


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale, causal,
                      t_kv, block_k):
    """One q block vs the whole (padded) KV sequence, online softmax."""
    q = q_ref[0].astype(jnp.float32) * sm_scale  # (BQ, D)
    bq, d = q.shape
    n_kv = k_ref.shape[1] // block_k
    qi = pl.program_id(1)

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = col < t_kv
        if causal:
            row = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            mask = jnp.logical_and(mask, col <= row)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    init = (jnp.zeros((bq, d), jnp.float32),
            jnp.full((bq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32))
    if causal:
        # only blocks up to (and including) the diagonal contribute
        n_live = jnp.minimum(((qi + 1) * bq + block_k - 1) // block_k, n_kv)
    else:
        n_live = n_kv
    acc, _, l = jax.lax.fori_loop(0, n_live, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _flash_fwd_impl(q, k, v, sm_scale, causal):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    dp = _round_up(d, 128)
    tqp = _round_up(tq, _BQ)
    tkp = _round_up(tk, _BK)
    pad4 = lambda x, tp: jnp.pad(
        x, ((0, 0), (0, 0), (0, tp - x.shape[2]), (0, dp - d)))
    qp = pad4(q, tqp).reshape(b * h, tqp, dp)
    kp = pad4(k, tkp).reshape(b * h, tkp, dp)
    vp = pad4(v, tkp).reshape(b * h, tkp, dp)
    grid = (b * h, tqp // _BQ)
    q_spec = pl.BlockSpec((1, _BQ, dp), lambda bh, i: (bh, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, tkp, dp), lambda bh, i: (bh, 0, 0),
                           memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                          causal=causal, t_kv=tk, block_k=_BK),
        out_shape=jax.ShapeDtypeStruct((b * h, tqp, dp), q.dtype),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        interpret=interpret_mode(),
        name="flash_attention_fwd",
    )(qp, kp, vp)
    return out.reshape(b, h, tqp, dp)[:, :, :tq, :d]


def _attn_bwd_reference(q, k, v, sm_scale, causal, g):
    """Memory-efficient backward: scan over KV blocks, recomputing
    attention weights blockwise (never materializes the T×T matrix)."""
    fp32 = jnp.float32
    qf, kf, vf, gf = (t.astype(fp32) for t in (q, k, v, g))
    tq, tk = q.shape[2], k.shape[2]
    row = jnp.arange(tq)[:, None]

    # pass 1: softmax stats per q row, blockwise
    def stat_step(carry, kb):
        m_prev, l_prev = carry
        ks = jax.lax.dynamic_slice_in_dim(kf, kb * _BK, _BK, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, ks) * sm_scale
        col = kb * _BK + jnp.arange(_BK)[None, :]
        mask = col < tk
        if causal:
            mask = jnp.logical_and(mask, col <= row)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(-1))
        l_new = l_prev * jnp.exp(m_prev - m_new) + \
            jnp.exp(s - m_new[..., None]).sum(-1)
        return (m_new, l_new), None

    tkp = _round_up(tk, _BK)
    kf = jnp.pad(kf, ((0, 0), (0, 0), (0, tkp - tk), (0, 0)))
    vf = jnp.pad(vf, ((0, 0), (0, 0), (0, tkp - tk), (0, 0)))
    n_kv = tkp // _BK
    b, h = q.shape[:2]
    m0 = jnp.full((b, h, tq), _NEG_INF, fp32)
    l0 = jnp.zeros((b, h, tq), fp32)
    (m, l), _ = jax.lax.scan(stat_step, (m0, l0), jnp.arange(n_kv))
    l = jnp.maximum(l, 1e-30)

    # delta = rowsum(dO * O) computed blockwise from recomputed O
    def out_step(carry, kb):
        acc = carry
        ks = jax.lax.dynamic_slice_in_dim(kf, kb * _BK, _BK, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(vf, kb * _BK, _BK, axis=2)
        p = _block_probs(qf, ks, kb, m, l, sm_scale, causal, tk, row)
        return acc + jnp.einsum("bhqk,bhkd->bhqd", p, vs), None

    o, _ = jax.lax.scan(out_step, jnp.zeros_like(qf), jnp.arange(n_kv))
    delta = (gf * o).sum(-1)

    def grad_step(carry, kb):
        dq = carry
        ks = jax.lax.dynamic_slice_in_dim(kf, kb * _BK, _BK, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(vf, kb * _BK, _BK, axis=2)
        p = _block_probs(qf, ks, kb, m, l, sm_scale, causal, tk, row)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vs)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, ks)
        dk_b = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        dv_b = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        return dq, (dk_b, dv_b)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        grad_step, jnp.zeros_like(qf), jnp.arange(n_kv))
    # (n_kv, b, h, BK, d) → (b, h, n_kv·BK, d), trimmed to tk
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, tkp, -1)[:, :, :tk]
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, tkp, -1)[:, :, :tk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _block_probs(qf, ks, kb, m, l, sm_scale, causal, tk, row):
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, ks) * sm_scale
    col = kb * _BK + jnp.arange(_BK)[None, :]
    mask = col < tk
    if causal:
        mask = jnp.logical_and(mask, col <= row)
    s = jnp.where(mask, s, _NEG_INF)
    return jnp.exp(s - m[..., None]) / l[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, sm_scale, causal):
    return _flash_fwd_impl(q, k, v, sm_scale, causal)


def _flash_vjp_fwd(q, k, v, sm_scale, causal):
    return _flash_fwd_impl(q, k, v, sm_scale, causal), (q, k, v)


def _flash_vjp_bwd(sm_scale, causal, res, g):
    q, k, v = res
    return _attn_bwd_reference(q, k, v, sm_scale, causal, g)


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, sm_scale=None, causal=False):
    """Blockwise attention, O(T) memory: softmax(QKᵀ·scale)·V.

    Shapes (B, H, T, D). New capability relative to the reference (which
    caps sequence length by device memory, SURVEY.md §5.7); pairs with
    parallel/ring_attention.py for the sequence-parallel path.

    In a process without a TPU the kernel always runs (interpret mode),
    so CPU tests cover it; with one, :func:`dispatch` decides between
    the kernel and the O(T²) XLA formulation.
    """
    scale = float(sm_scale) if sm_scale is not None else q.shape[-1] ** -0.5
    causal = bool(causal)
    kernel = functools.partial(_flash_attention, sm_scale=scale,
                               causal=causal)
    if interpret_mode():
        with jax.named_scope(kernel_name(kernel)):
            return kernel(q, k, v)
    return dispatch(kernel,
                    functools.partial(_xla_attention, scale=scale,
                                      causal=causal), q, k, v)


def _xla_attention(q, k, v, scale, causal):
    logits = jnp.einsum("bhtd,bhsd->bhts", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        T, S = logits.shape[-2:]
        mask = jnp.tril(jnp.ones((T, S), bool))
        logits = jnp.where(mask, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p.astype(q.dtype), v)


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
