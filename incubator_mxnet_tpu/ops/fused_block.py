"""Fused 1x1-conv (matmul) + BatchNorm Pallas kernels for bottleneck nets.

Built on the premise that the bf16 ResNet-50 train step is bound by
HBM traffic around BatchNorm: where XLA does not fuse the batch-stat
reductions *into* the producing conv, every BatchNorm costs an extra
activation-sized read (stats) plus a materialized normalized copy
feeding the next conv.  On today's chip and compiler XLA does fuse the
statistics into the convolution (PERF.md section 5); whether these
kernels win anything there is ROADMAP S4's A/B, which has not been run.

These kernels remove that traffic for the 1x1 convolutions (2/3 of the
convs in a bottleneck ResNet), which are plain matmuls over the
flattened spatial grid:

  ``fused_matmul_bn(x, w)``               -> y = x @ w, plus per-column
      sum(y) and sum(y^2) accumulated in the matmul epilogue — the BN
      batch stats of y cost ZERO extra HBM reads.
  ``fused_matmul_bn(x, w, scale, bias)``  -> y = relu(x*scale+bias) @ w:
      the previous BatchNorm's normalize+ReLU is applied in-register as
      the matmul prologue, so the normalized activation is NEVER
      materialized in HBM.

The custom VJP keeps the same property on the backward pass: the two
matmuls (dx, dw) recompute the prologue in-register and carry the
BN/ReLU backward reductions (dscale, dbias) as epilogues of the dx
matmul, instead of XLA's separate reduction passes.

Reference analog: the CUDNN/NNVM fused conv+BN+ReLU segments the
reference builds via its pointwise-fusion pass (src/operator/fusion/
fused_op.cu, src/executor/pointwise_fusion_pass.cc) — re-designed here
as TPU Pallas kernels with stats epilogues instead of NVRTC codegen.

Numerics: matmuls run on the MXU in the input dtype (bf16 for the
benchmark path) with fp32 accumulation; the prologue normalize runs in
fp32; stats accumulate in fp32 from the *rounded* output y (matching
ops.nn_ops.batch_norm's one-pass E[x^2]-mu^2 convention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _round_up, dispatch, interpret_mode

__all__ = ["fused_matmul_bn", "bn_consts", "xla_matmul_bn"]


def _pick_bm(np_cols: int) -> int:
    # small-N matmuls (e.g. 256->64 c1 convs) amortize better with
    # taller M tiles; wide outputs keep VMEM in budget with BM=256
    return 512 if np_cols <= 256 else 256


def _div_block(dim: int, cap: int) -> int:
    """Largest 128-multiple block <= cap that divides dim (dim is a
    128-multiple): a non-divisor block with grid = dim // block would
    silently drop the tail columns."""
    b = min(dim, cap)
    while dim % b:
        b -= 128
    return b


def _pick_bn(kp: int, np_: int, bm: int) -> int:
    """Widest output block within a ~8 MB VMEM budget for the residents
    that scale with bn — the weight tile (kp*bn*2B) AND the output/
    accumulator tiles (bm*bn*(4+2)B): every N-block sweep re-reads the
    x tile, so a wider bn directly cuts activation re-reads.  Floor 512
    (= the previous fixed default) even when the budget is tighter."""
    per_col = kp * 2 + bm * 6
    cap = max(512, (8 * 2 ** 20 // per_col) // 128 * 128)
    return _div_block(np_, cap)


# ---------------------------------------------------------------------------
# forward: y = [relu(x*scale+bias)] @ w, s1 = sum(y), s2 = sum(y^2)
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, sc_ref, bi_ref, y_ref, s1_ref, s2_ref, *,
                m_real, bm, prologue):
    i = pl.program_id(1)
    xf = x_ref[...].astype(jnp.float32)
    if prologue:
        xf = jnp.maximum(xf * sc_ref[...] + bi_ref[...], 0.0)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, xf.shape, 0)
    xf = jnp.where(rows < m_real, xf, 0.0)  # padded rows contribute zero
    y = jax.lax.dot_general(xf.astype(x_ref.dtype), w_ref[...],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    yb = y.astype(y_ref.dtype)
    y_ref[...] = yb

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    yf = yb.astype(jnp.float32)
    s1_ref[...] += jnp.sum(yf, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(jnp.square(yf), axis=0, keepdims=True)


def _fwd_impl(x, w, scale, bias, prologue, bm=None, bn=None):
    m, k = x.shape
    n = w.shape[1]
    kp, np_ = _round_up(k, 128), _round_up(n, 128)
    bm = bm or _pick_bm(np_)
    bn = bn or _pick_bn(kp, np_, bm)
    if np_ % bn:  # grid = np_ // bn would silently drop output columns
        raise ValueError(f"bn={bn} must divide the padded width {np_}")
    mp = _round_up(m, bm)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    scp = jnp.pad(scale.astype(jnp.float32), (0, kp - k)).reshape(1, kp)
    bip = jnp.pad(bias.astype(jnp.float32), (0, kp - k)).reshape(1, kp)
    grid = (np_ // bn, mp // bm)
    y, s1, s2 = pl.pallas_call(
        functools.partial(_fwd_kernel, m_real=m, bm=bm, prologue=prologue),
        out_shape=[jax.ShapeDtypeStruct((mp, np_), x.dtype),
                   jax.ShapeDtypeStruct((1, np_), jnp.float32),
                   jax.ShapeDtypeStruct((1, np_), jnp.float32)],
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kp, bn), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kp), lambda j, i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kp), lambda j, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        interpret=interpret_mode(),
        name="matmul_bn_fwd",
    )(xp, wp, scp, bip)
    return y[:m, :n], s1[0, :n], s2[0, :n]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dx_kernel(dy_ref, y_ref, ds1_ref, ds2_ref, w_ref, x_ref, sc_ref,
                   bi_ref, dx_ref, dsc_ref, dbi_ref, *, m_real, bm, prologue):
    i = pl.program_id(1)
    dyt = (dy_ref[...].astype(jnp.float32) + ds1_ref[...]
           + 2.0 * y_ref[...].astype(jnp.float32) * ds2_ref[...])
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, dyt.shape, 0)
    dyt = jnp.where(rows < m_real, dyt, 0.0)  # ds1 broadcast hits pad rows
    dxn = jax.lax.dot_general(dyt.astype(dy_ref.dtype), w_ref[...],
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _init():
        dsc_ref[...] = jnp.zeros_like(dsc_ref)
        dbi_ref[...] = jnp.zeros_like(dbi_ref)

    if prologue:
        xf = x_ref[...].astype(jnp.float32)
        z = xf * sc_ref[...] + bi_ref[...]
        dz = jnp.where(z > 0.0, dxn, 0.0)
        dx_ref[...] = (dz * sc_ref[...]).astype(dx_ref.dtype)
        dsc_ref[...] += jnp.sum(dz * xf, axis=0, keepdims=True)
        dbi_ref[...] += jnp.sum(dz, axis=0, keepdims=True)
    else:
        dx_ref[...] = dxn.astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, dy_ref, y_ref, ds1_ref, ds2_ref, sc_ref, bi_ref,
                   dw_ref, *, m_real, bm, prologue):
    i = pl.program_id(2)
    xf = x_ref[...].astype(jnp.float32)
    if prologue:
        xf = jnp.maximum(xf * sc_ref[...] + bi_ref[...], 0.0)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, xf.shape, 0)
    xf = jnp.where(rows < m_real, xf, 0.0)
    dyt = (dy_ref[...].astype(jnp.float32) + ds1_ref[...]
           + 2.0 * y_ref[...].astype(jnp.float32) * ds2_ref[...])

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[...] += jax.lax.dot_general(
        xf.astype(x_ref.dtype), dyt.astype(dy_ref.dtype),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _bwd_impl(x, w, scale, bias, y, dy, ds1, ds2, prologue):
    m, k = x.shape
    n = w.shape[1]
    kp, np_ = _round_up(k, 128), _round_up(n, 128)
    scp = jnp.pad(scale.astype(jnp.float32), (0, kp - k)).reshape(1, kp)
    bip = jnp.pad(bias.astype(jnp.float32), (0, kp - k)).reshape(1, kp)
    ds1p = jnp.pad(ds1.astype(jnp.float32), (0, np_ - n)).reshape(1, np_)
    ds2p = jnp.pad(ds2.astype(jnp.float32), (0, np_ - n)).reshape(1, np_)

    # --- dx (+ dscale, dbias epilogue) ---
    bm = 256
    bk = _div_block(kp, 512)
    mp = _round_up(m, bm)
    pad_mn = lambda a: jnp.pad(a, ((0, mp - m), (0, np_ - n)))
    dyp, yp = pad_mn(dy), pad_mn(y)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    dx, dsc, dbi = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, m_real=m, bm=bm,
                          prologue=prologue),
        out_shape=[jax.ShapeDtypeStruct((mp, kp), x.dtype),
                   jax.ShapeDtypeStruct((1, kp), jnp.float32),
                   jax.ShapeDtypeStruct((1, kp), jnp.float32)],
        grid=(kp // bk, mp // bm),
        in_specs=[
            pl.BlockSpec((bm, np_), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, np_), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, np_), lambda j, i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, np_), lambda j, i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, np_), lambda j, i: (j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, bk), lambda j, i: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda j, i: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        interpret=interpret_mode(),
        name="matmul_bn_bwd_dx",
    )(dyp, yp, ds1p, ds2p, wp, xp, scp, bip)

    # --- dw --- (same M tiling as dx: the padded dy/y/x are reused)
    bk2 = _div_block(kp, 512)
    bn2 = _div_block(np_, 512)
    # dw accumulates across M blocks in fp32 (a bf16 running sum loses
    # mantissa every iteration); cast to the weight dtype at the end
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, m_real=m, bm=bm,
                          prologue=prologue),
        out_shape=jax.ShapeDtypeStruct((kp, np_), jnp.float32),
        grid=(kp // bk2, np_ // bn2, mp // bm),
        in_specs=[
            pl.BlockSpec((bm, bk2), lambda kj, nj, i: (i, kj),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, bn2), lambda kj, nj, i: (i, nj),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, bn2), lambda kj, nj, i: (i, nj),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn2), lambda kj, nj, i: (0, nj),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn2), lambda kj, nj, i: (0, nj),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk2), lambda kj, nj, i: (0, kj),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk2), lambda kj, nj, i: (0, kj),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bk2, bn2), lambda kj, nj, i: (kj, nj),
                               memory_space=pltpu.VMEM),
        interpret=interpret_mode(),
        name="matmul_bn_bwd_dw",
    )(xp, dyp, yp, ds1p, ds2p, scp, bip)

    dx = dx[:m, :k]
    dw = dw[:k, :n].astype(w.dtype)
    if prologue:
        return dx, dw, dsc[0, :k], dbi[0, :k]
    return dx, dw, jnp.zeros_like(scale), jnp.zeros_like(bias)


# ---------------------------------------------------------------------------
# custom_vjp plumbing + XLA reference/fallback
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fmm(x, w, scale, bias, prologue):
    y, s1, s2 = _fwd_impl(x, w, scale, bias, prologue)
    return y, s1, s2


def _fmm_fwd(x, w, scale, bias, prologue):
    y, s1, s2 = _fwd_impl(x, w, scale, bias, prologue)
    return (y, s1, s2), (x, w, scale, bias, y)


def _fmm_bwd(prologue, res, cts):
    x, w, scale, bias, y = res
    dy, ds1, ds2 = cts
    dx, dw, dsc, dbi = _bwd_impl(x, w, scale, bias, y, dy, ds1, ds2,
                                 prologue)
    return dx, dw, dsc, dbi


_fmm.defvjp(_fmm_fwd, _fmm_bwd)


def xla_matmul_bn(x, w, scale=None, bias=None):
    """Pure-XLA composition with the same contract (fallback + oracle)."""
    if scale is not None:
        xn = jnp.maximum(x.astype(jnp.float32) * scale.astype(jnp.float32)
                         + bias.astype(jnp.float32), 0.0).astype(x.dtype)
    else:
        xn = x
    y = jax.lax.dot_general(xn, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y.astype(x.dtype)
    yf = y.astype(jnp.float32)
    return (y, jnp.sum(yf, axis=0), jnp.sum(jnp.square(yf), axis=0))


def fused_matmul_bn(x, w, scale=None, bias=None):
    """y = [relu(x*scale + bias)] @ w with BN batch stats in the epilogue.

    Args:
      x: (M, K) activations (bf16 or f32); rows = flattened N*H*W.
      w: (K, N) weights — a 1x1 conv kernel reshaped.
      scale, bias: optional per-K fp32 normalize constants; when given,
        relu(x*scale+bias) is applied in-register (never materialized).

    Returns ``(y, s1, s2)`` with ``s1 = sum_M(y)``, ``s2 = sum_M(y^2)``
    in fp32: ``mean = s1/M``, ``var = s2/M - mean^2`` (one-pass BN).
    """
    # same contract as every other kernel gate (e.g. layer_norm): see
    # pallas_kernels.dispatch, which also names the trace scope after
    # this wrapper; tests that want interpret-mode Pallas off-TPU force
    # MXNET_USE_PALLAS=1
    def matmul_bn(x, w, scale=None, bias=None):
        if scale is None:
            ones = jnp.ones((x.shape[1],), jnp.float32)
            return _fmm(x, w, ones, jnp.zeros_like(ones), False)
        return _fmm(x, w, scale, bias, True)

    args = (x, w) if scale is None else (x, w, scale, bias)
    return dispatch(matmul_bn, xla_matmul_bn, *args)


def _bottleneck_core(x, w1, g1, b1, w2, g2, b2, w3, g3, b3,
                     wsc, gsc, bsc, stride, eps):
    """Bottleneck-V1 body with fused 1x1 matmul+BN kernels (NHWC).

    Weights are zoo NHWC kernels (O, kh, kw, I); the 1x1 convs become
    fused_matmul_bn calls (stats in the epilogue; bn2's normalize+relu
    in c3's prologue), the 3x3 stays an XLA conv.  Returns the block
    output plus every BN's batch mean/var so the gluon layer can update
    moving stats (reference BatchNork aux-state mutation contract).
    """
    n, h, w_, _ = x.shape
    s = int(stride)
    xs = x[:, ::s, ::s, :] if s > 1 else x
    flat = lambda t: t.reshape(-1, t.shape[-1])
    mm = lambda w4: w4.reshape(w4.shape[0], -1).T  # (O,1,1,I) -> (I,O)

    hs, ws = xs.shape[1], xs.shape[2]  # ::s slice is ceil(h/s), not h//s
    y1, a1, c1 = fused_matmul_bn(flat(xs), mm(w1))
    m1 = y1.shape[0]
    sc1, of1, mean1, var1 = bn_consts(a1, c1, m1, g1, b1, eps)
    cm = y1.shape[-1]

    # 3x3 stage conv: bn1's normalize+ReLU runs in the conv prologue
    # (the normalized y1 copy never exists in HBM) and bn2's batch
    # stats come from the conv epilogue — the round-5 extension of the
    # 1x1 pattern to the remaining stage-conv traffic.  Geometry the
    # blocking plan cannot fit in VMEM rides the XLA composition
    # (normalize+conv+stats, identical contract), with a warning.
    from .fused_conv import fused_conv3_bn
    y2, a2, c2 = fused_conv3_bn(y1.reshape(n, hs, ws, cm),
                                jnp.transpose(w2, (1, 2, 3, 0)), sc1, of1)
    sc2, of2, mean2, var2 = bn_consts(a2, c2, m1, g2, b2, eps)

    y3, a3, c3 = fused_matmul_bn(flat(y2), mm(w3), sc2, of2)
    sc3, of3, mean3, var3 = bn_consts(a3, c3, y3.shape[0], g3, b3, eps)

    if wsc is not None:
        ysc, asc, csc = fused_matmul_bn(flat(xs), mm(wsc))
        sccs, ofcs, meansc, varsc = bn_consts(asc, csc, ysc.shape[0],
                                              gsc, bsc, eps)
        short = ysc * sccs.astype(x.dtype) + ofcs.astype(x.dtype)
    else:
        short = flat(xs)
    out = jnp.maximum(
        y3 * sc3.astype(x.dtype) + of3.astype(x.dtype) + short, 0)
    out = out.reshape(n, hs, ws, y3.shape[-1])
    stats = (mean1, var1, mean2, var2, mean3, var3)
    if wsc is not None:
        stats = stats + (meansc, varsc)
    return (out,) + stats


def _blend(momentum, old, new):
    return momentum * old + (1.0 - momentum) * new.astype(old.dtype)


def fused_bottleneck_v1(x, w1, g1, b1, rm1, rv1, w2, g2, b2, rm2, rv2,
                        w3, g3, b3, rm3, rv3, stride=1, eps=1e-5,
                        momentum=0.9):
    """Identity-shortcut fused bottleneck (see _bottleneck_core).

    Follows the BatchNorm op contract (ops/nn_ops.py batch_norm): batch
    stats are folded into updated moving mean/var returned alongside the
    output; the gluon layer routes them through register_state_update.
    """
    out, m1, v1, m2, v2, m3, v3 = _bottleneck_core(
        x, w1, g1, b1, w2, g2, b2, w3, g3, b3, None, None, None,
        stride, eps)
    b = functools.partial(_blend, momentum)
    return (out, b(rm1, m1), b(rv1, v1), b(rm2, m2), b(rv2, v2),
            b(rm3, m3), b(rv3, v3))


def fused_bottleneck_v1_proj(x, w1, g1, b1, rm1, rv1, w2, g2, b2, rm2, rv2,
                             w3, g3, b3, rm3, rv3, wsc, gsc, bsc, rmsc, rvsc,
                             stride=1, eps=1e-5, momentum=0.9):
    """Projection-shortcut fused bottleneck (see _bottleneck_core)."""
    out, m1, v1, m2, v2, m3, v3, msc, vsc = _bottleneck_core(
        x, w1, g1, b1, w2, g2, b2, w3, g3, b3, wsc, gsc, bsc, stride, eps)
    b = functools.partial(_blend, momentum)
    return (out, b(rm1, m1), b(rv1, v1), b(rm2, m2), b(rv2, v2),
            b(rm3, m3), b(rv3, v3), b(rmsc, msc), b(rvsc, vsc))


def _bn_fold(x2, gamma, beta, eps):
    """One-pass batch stats of a flat activation + folded normalize
    constants (for BN inputs no kernel epilogue produced — e.g. the
    pre-activation bn1 over a block's raw input; XLA fuses the reduce
    with the producing elementwise add, one read).  Delegates the fold
    itself to bn_consts so the numerics cannot drift from the
    epilogue-fed BNs."""
    s1 = jnp.sum(x2, 0, dtype=jnp.float32)
    s2 = jnp.sum(jnp.square(x2.astype(jnp.float32)), 0)
    return bn_consts(s1, s2, x2.shape[0], gamma, beta, eps)


def _bottleneck_v2_core(x, w1, g1, b1, w2, g2, b2, w3, g3, b3, wsc,
                        stride, eps):
    """Pre-activation BottleneckV2 body with fused kernels (NHWC).

    The v2 ordering (bn->relu->conv, reference resnet.py BottleneckV2)
    maps directly onto the prologue pattern: every conv consumes its
    preceding BN's normalize+ReLU in-register, and the two inner BNs
    read their batch stats from the producing kernel's epilogue.  Only
    bn1 (over the block's raw input) needs an explicit stats pass.
    Stride sits on the 3x3 in v2: stride-2 blocks keep an XLA conv for
    it (the conv kernel is s1-only); everything else stays fused.
    """
    n, h, w_, _ = x.shape
    s = int(stride)
    flat = lambda t: t.reshape(-1, t.shape[-1])
    mm = lambda w4: w4.reshape(w4.shape[0], -1).T  # (O,1,1,I) -> (I,O)
    xf = flat(x)
    sc1, of1, mean1, var1 = _bn_fold(xf, g1, b1, eps)

    y1, a2, c2 = fused_matmul_bn(xf, mm(w1), sc1, of1)
    sc2, of2, mean2, var2 = bn_consts(a2, c2, y1.shape[0], g2, b2, eps)
    cm = y1.shape[-1]

    if s == 1:
        from .fused_conv import fused_conv3_bn
        y2, a3, c3 = fused_conv3_bn(y1.reshape(n, h, w_, cm),
                                    jnp.transpose(w2, (1, 2, 3, 0)),
                                    sc2, of2)
        hs, ws = h, w_
        y2f = flat(y2)
        sc3, of3, mean3, var3 = bn_consts(a3, c3, y2f.shape[0], g3, b3,
                                          eps)
    else:
        y1n = jnp.maximum(y1 * sc2.astype(x.dtype) + of2.astype(x.dtype),
                          0)
        y1n = y1n.reshape(n, h, w_, cm)
        dn = jax.lax.conv_dimension_numbers(y1n.shape, w2.shape,
                                            ("NHWC", "OHWI", "NHWC"))
        y2 = jax.lax.conv_general_dilated(
            y1n, w2, (s, s), [(1, 1), (1, 1)],
            dimension_numbers=dn).astype(x.dtype)
        hs, ws = y2.shape[1], y2.shape[2]
        y2f = flat(y2)
        sc3, of3, mean3, var3 = _bn_fold(y2f, g3, b3, eps)

    # conv3 has no BN after it in v2 — its stats epilogue is unused
    y3, _, _ = fused_matmul_bn(y2f, mm(w3), sc3, of3)

    if wsc is not None:
        # v2 downsample consumes relu(bn1(x)) — same prologue, never a
        # materialized normalized copy; stride rides the 1x1 as a slice
        xs = x[:, ::s, ::s, :] if s > 1 else x
        rsd, _, _ = fused_matmul_bn(flat(xs), mm(wsc), sc1, of1)
    else:
        rsd = xf
    out = (y3 + rsd).reshape(n, hs, ws, y3.shape[-1])
    return out, mean1, var1, mean2, var2, mean3, var3


def fused_bottleneck_v2(x, w1, g1, b1, rm1, rv1, w2, g2, b2, rm2, rv2,
                        w3, g3, b3, rm3, rv3, stride=1, eps=1e-5,
                        momentum=0.9):
    """Identity-shortcut fused pre-activation bottleneck (see
    _bottleneck_v2_core); moving stats follow the BatchNorm contract."""
    out, m1, v1, m2, v2, m3, v3 = _bottleneck_v2_core(
        x, w1, g1, b1, w2, g2, b2, w3, g3, b3, None, stride, eps)
    b = functools.partial(_blend, momentum)
    return (out, b(rm1, m1), b(rv1, v1), b(rm2, m2), b(rv2, v2),
            b(rm3, m3), b(rv3, v3))


def fused_bottleneck_v2_proj(x, w1, g1, b1, rm1, rv1, w2, g2, b2, rm2, rv2,
                             w3, g3, b3, rm3, rv3, wsc, stride=1, eps=1e-5,
                             momentum=0.9):
    """Projection-shortcut fused pre-activation bottleneck (v2's
    downsample is a bare conv — no shortcut BN)."""
    out, m1, v1, m2, v2, m3, v3 = _bottleneck_v2_core(
        x, w1, g1, b1, w2, g2, b2, w3, g3, b3, wsc, stride, eps)
    b = functools.partial(_blend, momentum)
    return (out, b(rm1, m1), b(rv1, v1), b(rm2, m2), b(rv2, v2),
            b(rm3, m3), b(rv3, v3))


def _register_ops():
    from .registry import register
    register("_fused_bottleneck_v1")(fused_bottleneck_v1)
    register("_fused_bottleneck_v1_proj")(fused_bottleneck_v1_proj)
    register("_fused_bottleneck_v2")(fused_bottleneck_v2)
    register("_fused_bottleneck_v2_proj")(fused_bottleneck_v2_proj)


_register_ops()


def bn_consts(s1, s2, m, gamma, beta, eps=1e-5):
    """Fold kernel stats into per-channel normalize constants.

    Returns ``(scale, bias, mean, var)`` with scale/bias in fp32 (fed to
    the next fused kernel's prologue) — y_norm = y*scale + bias.
    Differentiable: gradients flow back into s1/s2 cotangents, which the
    kernel VJP folds into its matmul prologues.
    """
    mf = jnp.float32(m)
    mean = s1 / mf
    var = jnp.maximum(s2 / mf - jnp.square(mean), 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    g32 = gamma.astype(jnp.float32)
    scale = g32 * rstd
    bias = beta.astype(jnp.float32) - mean * scale
    return scale, bias, mean, var
