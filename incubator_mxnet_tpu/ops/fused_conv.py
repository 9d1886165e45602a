"""Fused 3x3-conv + BatchNorm Pallas kernels (the stage convs).

`fused_block.py` removed the BN-structured HBM traffic around the 1x1
convolutions of a bottleneck ResNet; this module does the same for the
remaining 3x3 stage convs (stride 1, pad 1, NHWC), the last
BatchNorm-structured activation traffic of a bottleneck block (never
timed against the XLA composition on the chip: ROADMAP S4):

  * the previous BatchNorm's normalize+ReLU runs as the conv's PROLOGUE
    in-register — the normalized activation (`y1n` in the old
    `_bottleneck_core`) is never materialized in HBM;
  * the conv emits per-channel sum(y) and sum(y^2) from its EPILOGUE —
    the BN batch stats of the conv output cost zero extra HBM reads.

Kernel shape: a 3x3/s1/p1 conv over NHWC is nine shifted matmuls.  The
flattened (N*H*W, C) activation is blocked into groups of whole images
(block = b*H*W rows, so every spatial shift stays inside the block);
each tap (dh, dw) contributes dot(shift(x, dh*W+dw), W[dh,dw]) with an
iota-derived validity mask zeroing out-of-image neighbors.  No halo
exchange, no padded-copy of the input in HBM.  The custom VJP keeps the
property backward: dx is the nine-tap transposed conv of the
stats-adjusted cotangent (dy + ds1 + 2*y*ds2) with the ReLU/normalize
backward and dscale/dbias reductions fused as epilogues; dw accumulates
the nine (C, C_out) tap gradients across image blocks in fp32.

Reference analog: the conv+BN+ReLU segments the reference fuses via
cuDNN/NNVM (src/operator/fusion/fused_op.cu:24,
src/executor/pointwise_fusion_pass.cc) — re-designed as TPU Pallas
kernels with stats epilogues instead of NVRTC codegen.

Numerics match `fused_block.py`: MXU matmuls in the input dtype (bf16
on the bench path) with fp32 accumulation, prologue normalize in fp32,
stats accumulated in fp32 from the *rounded* output (the one-pass
E[x^2]-mu^2 convention of ops.nn_ops.batch_norm).

VMEM policy: channel width and block height anti-correlate in ResNet
(56px@64ch ... 7px@512ch), so whole-image blocks with a single output
block cover all four ResNet-50 stages inside the 64 MiB the kernels ask
Mosaic for; wider outputs split the output-channel dimension into N
blocks sized by a calibrated working-set bound (_Geom._bytes), with dx
accumulated in fp32 across N blocks and its ReLU/normalize backward
applied at the last one.  Geometry the plan cannot cover at any width
(a 112x112 image block, say) runs the XLA composition, and says so.
"""
from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _round_up, dispatch, interpret_mode

__all__ = ["fused_conv3_bn", "xla_conv3_bn"]

# Scoped-VMEM ceiling for the fused conv kernels (bytes): BOTH the
# limit handed to Mosaic (`vmem_limit_bytes` — its default, 16 MiB on
# v5e, refuses the 56x56 stage-1 block) and the ceiling the blocking
# plan holds its own estimate to (_Geom._bytes).  Half of a v5e core's
# 128 MiB of VMEM.
_VMEM_BUDGET = int(os.environ.get("MXNET_FUSED_CONV3_VMEM", 64 * 2 ** 20))
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BUDGET)

_TAPS = [(dh, dw) for dh in (-1, 0, 1) for dw in (-1, 0, 1)]


def _shift_rows(a, off):
    """Shift rows of a 2-D block by `off` (static) — the flattened-NHWC
    analog of a spatial (dh, dw) displacement.

    Contract: every caller masks all out-of-image positions (the
    `_shifted_taps` validity masks), which provably covers every
    wrapped/zero-filled row — so the zero-fill (concat) and wrap-around
    (roll) implementations are interchangeable.  `concat` is the
    default; `MXNET_FUSED_CONV3_SHIFT=roll` switches to pltpu.roll as
    the variant for the on-chip A/B (Mosaic compiles the concat form
    at all four ResNet-50 stage widths — tests/test_tpu_compile.py)."""
    if off == 0:
        return a
    if os.environ.get("MXNET_FUSED_CONV3_SHIFT", "concat") == "roll":
        if interpret_mode():
            return jnp.roll(a, -off, axis=0)
        return pltpu.roll(a, -off, 0)
    z = jnp.zeros((abs(off), a.shape[1]), a.dtype)
    if off > 0:
        return jnp.concatenate([a[off:], z], axis=0)
    return jnp.concatenate([z, a[:off]], axis=0)


def _local_hw(bm, w_img, h_img):
    """Per-row image-local (h, w) coordinates for a whole-image block."""
    r = jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    return (r // w_img) % h_img, r % w_img


def _shifted_taps(data, hl, wl, h_img, w_img, sgn):
    """The nine masked tap views of a block: tap t displaced by
    sgn*(dh, dw) with out-of-image neighbors zeroed.  sgn=+1 is the
    forward/weight-grad orientation; sgn=-1 the transposed (dx) one.
    Shared by every kernel so the shift/mask convention cannot drift."""
    for t, (dh, dw) in enumerate(_TAPS):
        shifted = _shift_rows(data, sgn * (dh * w_img + dw))
        valid = ((hl + sgn * dh >= 0) & (hl + sgn * dh < h_img)
                 & (wl + sgn * dw >= 0) & (wl + sgn * dw < w_img))
        yield t, jnp.where(valid, shifted, 0)


def _dx_partial(dc, w_ref, bm, kp, hl, wl, h_img, w_img):
    """Nine-tap transposed conv of a cotangent block: sum_t
    shifted(dc) @ W_t^T, fp32."""
    dxn = jnp.zeros((bm, kp), jnp.float32)
    for t, s in _shifted_taps(dc, hl, wl, h_img, w_img, -1):
        dxn += jax.lax.dot_general(
            s, w_ref[t * kp:(t + 1) * kp, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return dxn


def _prologue_bwd(dxn, x_ref, sc_ref, bi_ref):
    """ReLU/normalize backward: returns (dx block, dscale and dbias
    row contributions)."""
    xf = x_ref[...].astype(jnp.float32)
    z = xf * sc_ref[...] + bi_ref[...]
    dz = jnp.where(z > 0.0, dxn, 0.0)
    return (dz * sc_ref[...],
            jnp.sum(dz * xf, axis=0, keepdims=True),
            jnp.sum(dz, axis=0, keepdims=True))


# ---------------------------------------------------------------------------
# forward: y = conv3x3([relu(x*scale+bias)]), s1 = sum(y), s2 = sum(y^2)
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, sc_ref, bi_ref, y_ref, s1_ref, s2_ref, *,
                m_real, bm, kp, h_img, w_img, prologue):
    i = pl.program_id(1)  # M block (grid = (n_blocks, m_blocks))
    xf = x_ref[...].astype(jnp.float32)
    if prologue:
        xf = jnp.maximum(xf * sc_ref[...] + bi_ref[...], 0.0)
    xc = xf.astype(x_ref.dtype)  # MXU runs in the input dtype
    hl, wl = _local_hw(bm, w_img, h_img)
    acc = jnp.zeros((bm, y_ref.shape[1]), jnp.float32)
    for t, s in _shifted_taps(xc, hl, wl, h_img, w_img, 1):
        acc += jax.lax.dot_general(
            s, w_ref[t * kp:(t + 1) * kp, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    yb = acc.astype(y_ref.dtype)
    y_ref[...] = yb

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # pad rows produce values (their shifted taps read real rows) but
    # must not enter the batch stats
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    yf = jnp.where(rows < m_real, yb.astype(jnp.float32), 0.0)
    s1_ref[...] += jnp.sum(yf, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(jnp.square(yf), axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dyt(dy_ref, y_ref, ds1_ref, ds2_ref, rows, m_real):
    """Stats-adjusted cotangent dy + ds1 + 2*y*ds2, zeroed on pad rows
    (the ds1/ds2 broadcasts would otherwise hit them)."""
    d = (dy_ref[...].astype(jnp.float32) + ds1_ref[...]
         + 2.0 * y_ref[...].astype(jnp.float32) * ds2_ref[...])
    return jnp.where(rows < m_real, d, 0.0)


def _bwd_dx_kernel_nb(dy_ref, y_ref, ds1_ref, ds2_ref, w_ref, x_ref, sc_ref,
                      bi_ref, dx_ref, dsc_ref, dbi_ref, *,
                      m_real, bm, kp, h_img, w_img, prologue, n_last):
    """Multi-N-block dx: grid = (m_blocks, n_blocks), n inner.  The
    tap-transposed partial products accumulate into an fp32 dx block
    across N blocks; the ReLU/normalize backward (which needs the TOTAL
    dxn before masking) and the dscale/dbias reductions run once at the
    final N block."""
    i, j = pl.program_id(0), pl.program_id(1)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    dyt = _dyt(dy_ref, y_ref, ds1_ref, ds2_ref, rows, m_real)
    dc = dyt.astype(dy_ref.dtype)
    hl, wl = _local_hw(bm, w_img, h_img)
    partial = _dx_partial(dc, w_ref, bm, kp, hl, wl, h_img, w_img)
    partial = jnp.where(rows < m_real, partial, 0.0)

    @pl.when(j == 0)
    def _init_dx():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    dx_ref[...] += partial

    @pl.when((i == 0) & (j == 0))
    def _init_scale():
        dsc_ref[...] = jnp.zeros_like(dsc_ref)
        dbi_ref[...] = jnp.zeros_like(dbi_ref)

    if prologue:
        @pl.when(j == n_last)
        def _finish():
            dx, dsc, dbi = _prologue_bwd(dx_ref[...], x_ref, sc_ref,
                                         bi_ref)
            dx_ref[...] = dx
            dsc_ref[...] += dsc
            dbi_ref[...] += dbi


def _bwd_dx_kernel(dy_ref, y_ref, ds1_ref, ds2_ref, w_ref, x_ref, sc_ref,
                   bi_ref, dx_ref, dsc_ref, dbi_ref, *,
                   m_real, bm, kp, h_img, w_img, prologue):
    i = pl.program_id(0)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    dyt = _dyt(dy_ref, y_ref, ds1_ref, ds2_ref, rows, m_real)
    dc = dyt.astype(dy_ref.dtype)
    hl, wl = _local_hw(bm, w_img, h_img)
    # x-position r received tap (dh,dw) from output position r-off;
    # validity is the forward condition evaluated at that output
    dxn = _dx_partial(dc, w_ref, bm, kp, hl, wl, h_img, w_img)
    dxn = jnp.where(rows < m_real, dxn, 0.0)

    @pl.when(i == 0)
    def _init():
        dsc_ref[...] = jnp.zeros_like(dsc_ref)
        dbi_ref[...] = jnp.zeros_like(dbi_ref)

    if prologue:
        dx, dsc, dbi = _prologue_bwd(dxn, x_ref, sc_ref, bi_ref)
        dx_ref[...] = dx.astype(dx_ref.dtype)
        dsc_ref[...] += dsc
        dbi_ref[...] += dbi
    else:
        dx_ref[...] = dxn.astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, dy_ref, y_ref, ds1_ref, ds2_ref, sc_ref, bi_ref,
                   dw_ref, *, m_real, bm, kp, h_img, w_img, prologue):
    i = pl.program_id(1)  # M block (grid = (n_blocks, m_blocks))
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    dyt = _dyt(dy_ref, y_ref, ds1_ref, ds2_ref, rows, m_real)
    dc = dyt.astype(dy_ref.dtype)
    xf = x_ref[...].astype(jnp.float32)
    if prologue:
        xf = jnp.maximum(xf * sc_ref[...] + bi_ref[...], 0.0)
    xc = xf.astype(x_ref.dtype)
    hl, wl = _local_hw(bm, w_img, h_img)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    # one full-ref accumulate instead of nine slice-stores: stacked
    # in-register tap gradients use only store patterns the round-4
    # kernels already proved under Mosaic
    taps = [jax.lax.dot_general(s, dc, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for _t, s in _shifted_taps(xc, hl, wl, h_img, w_img, 1)]
    dw_ref[...] += jnp.concatenate(taps, axis=0)


# ---------------------------------------------------------------------------
# geometry / wrappers
# ---------------------------------------------------------------------------

class _Geom:
    """Blocking plan for a (N, H, W, C)->C_out fused conv; `fits()` is
    false when the kernel cannot cover the configuration.

    The M dimension is blocked into whole images (bm = b*H*W rows).
    The output-channel dimension is blocked too (bn), chosen as the
    widest divisor of the padded width whose worst-case kernel working
    set fits the VMEM budget — outputs too wide for one block run with
    several N blocks instead of leaving the kernel."""

    def __init__(self, x4, cout):
        n, h, w, c = x4.shape
        self.n, self.h, self.w, self.c, self.cout = n, h, w, c, cout
        self.hw = h * w
        self.m = n * self.hw
        self.kp = _round_up(c, 128)
        self.np = _round_up(cout, 128)
        row_mult = 16 if x4.dtype == jnp.bfloat16 else 8
        b = 1
        while (b * self.hw) % row_mult and b <= row_mult:
            b += 1
        # small images: grow blocks toward a decent MXU M tile
        while b * self.hw < 256 and b * 2 * self.hw <= 4096:
            b *= 2
        self.bm = b * self.hw
        self.mp = _round_up(self.m, self.bm)
        self.grid = self.mp // self.bm
        self.bn = self._pick_bn()

    def _bytes(self, bn):
        """Upper bound on the scoped VMEM Mosaic wants for the worst of
        the three kernels at output-block width bn.  Calibrated, not
        derived: the least `vmem_limit_bytes` each kernel compiles
        under was bisected for a described v5e (libtpu 0.0.34) at the
        four ResNet-50 stage shapes in bf16 and f32, stage 4 at bn
        128/256/512, and a 112x112x64 image.  The nine unrolled taps
        keep ~50-60 B live per element of a (bm, 128) activation tile
        (23.8 MiB at 56x56x64, where this bound says 26.7); the weight
        and dw tiles cost at most 16 B per element, double buffered."""
        return (32 * self.bm * (self.kp + bn)
                + 16 * 9 * self.kp * bn)

    def _pick_bn(self):
        bn = self.np
        while bn >= 128:
            if self.np % bn == 0 and self._bytes(bn) <= _VMEM_BUDGET:
                return bn
            bn -= 128
        return None

    @property
    def n_blocks(self):
        return self.np // self.bn

    def fits(self):
        return self.m > 0 and self.bm % 8 == 0 and self.bn is not None

    def pad_x(self, x4):
        x2 = x4.reshape(self.m, self.c)
        return jnp.pad(x2, ((0, self.mp - self.m), (0, self.kp - self.c)))

    def pad_w(self, w):  # (3, 3, C, C_out) HWIO -> (9*kp, np)
        wt = w.reshape(9, self.c, self.cout)
        wt = jnp.pad(wt, ((0, 0), (0, self.kp - self.c),
                          (0, self.np - self.cout)))
        return wt.reshape(9 * self.kp, self.np)

    def pad_vec(self, v, width):
        return jnp.pad(v.astype(jnp.float32),
                       (0, width - v.shape[0])).reshape(1, width)


def _fwd_impl(x4, w, scale, bias, prologue):
    g = _Geom(x4, w.shape[-1])
    kern = functools.partial(_fwd_kernel, m_real=g.m, bm=g.bm, kp=g.kp,
                             h_img=g.h, w_img=g.w, prologue=prologue)
    y, s1, s2 = pl.pallas_call(
        kern,
        out_shape=[jax.ShapeDtypeStruct((g.mp, g.np), x4.dtype),
                   jax.ShapeDtypeStruct((1, g.np), jnp.float32),
                   jax.ShapeDtypeStruct((1, g.np), jnp.float32)],
        grid=(g.n_blocks, g.grid),
        in_specs=[
            pl.BlockSpec((g.bm, g.kp), lambda j, i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((9 * g.kp, g.bn), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, g.kp), lambda j, i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, g.kp), lambda j, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((g.bm, g.bn), lambda j, i: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, g.bn), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, g.bn), lambda j, i: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        interpret=interpret_mode(),
        compiler_params=_COMPILER_PARAMS,
        name="conv3_bn_fwd",
    )(g.pad_x(x4), g.pad_w(w), g.pad_vec(scale, g.kp),
      g.pad_vec(bias, g.kp))
    y = y[:g.m, :g.cout].reshape(g.n, g.h, g.w, g.cout)
    return y, s1[0, :g.cout], s2[0, :g.cout]


def _bwd_impl(x4, w, scale, bias, y4, dy4, ds1, ds2, prologue):
    g = _Geom(x4, w.shape[-1])
    xp = g.pad_x(x4)
    wp = g.pad_w(w)
    scp = g.pad_vec(scale, g.kp)
    bip = g.pad_vec(bias, g.kp)
    pad_y = lambda t: jnp.pad(t.reshape(g.m, g.cout),
                              ((0, g.mp - g.m), (0, g.np - g.cout)))
    dyp, yp = pad_y(dy4), pad_y(y4)
    ds1p = g.pad_vec(ds1, g.np)
    ds2p = g.pad_vec(ds2, g.np)
    if g.n_blocks == 1:
        # single N block: the proven one-pass dx kernel (dx written in
        # the input dtype, prologue applied inline)
        row_spec = lambda cols: pl.BlockSpec(
            (g.bm, cols), lambda i: (i, 0), memory_space=pltpu.VMEM)
        vec_spec = lambda cols: pl.BlockSpec(
            (1, cols), lambda i: (0, 0), memory_space=pltpu.VMEM)
        dx, dsc, dbi = pl.pallas_call(
            functools.partial(_bwd_dx_kernel, m_real=g.m, bm=g.bm,
                              kp=g.kp, h_img=g.h, w_img=g.w,
                              prologue=prologue),
            out_shape=[jax.ShapeDtypeStruct((g.mp, g.kp), x4.dtype),
                       jax.ShapeDtypeStruct((1, g.kp), jnp.float32),
                       jax.ShapeDtypeStruct((1, g.kp), jnp.float32)],
            grid=(g.grid,),
            in_specs=[row_spec(g.np), row_spec(g.np), vec_spec(g.np),
                      vec_spec(g.np),
                      pl.BlockSpec((9 * g.kp, g.np), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
                      row_spec(g.kp), vec_spec(g.kp), vec_spec(g.kp)],
            out_specs=[row_spec(g.kp), vec_spec(g.kp), vec_spec(g.kp)],
            interpret=interpret_mode(),
            compiler_params=_COMPILER_PARAMS,
            name="conv3_bn_bwd_dx",
        )(dyp, yp, ds1p, ds2p, wp, xp, scp, bip)
    else:
        # wide outputs: accumulate fp32 dx partials across N blocks,
        # prologue backward at the last block (grid n inner)
        mrow = lambda cols: pl.BlockSpec(
            (g.bm, cols), lambda i, j: (i, 0), memory_space=pltpu.VMEM)
        nrow = lambda cols: pl.BlockSpec(
            (g.bm, cols), lambda i, j: (i, j), memory_space=pltpu.VMEM)
        nvec = lambda cols: pl.BlockSpec(
            (1, cols), lambda i, j: (0, j), memory_space=pltpu.VMEM)
        cvec = lambda cols: pl.BlockSpec(
            (1, cols), lambda i, j: (0, 0), memory_space=pltpu.VMEM)
        dx, dsc, dbi = pl.pallas_call(
            functools.partial(_bwd_dx_kernel_nb, m_real=g.m, bm=g.bm,
                              kp=g.kp, h_img=g.h, w_img=g.w,
                              prologue=prologue,
                              n_last=g.n_blocks - 1),
            out_shape=[jax.ShapeDtypeStruct((g.mp, g.kp), jnp.float32),
                       jax.ShapeDtypeStruct((1, g.kp), jnp.float32),
                       jax.ShapeDtypeStruct((1, g.kp), jnp.float32)],
            grid=(g.grid, g.n_blocks),
            in_specs=[nrow(g.bn), nrow(g.bn), nvec(g.bn), nvec(g.bn),
                      pl.BlockSpec((9 * g.kp, g.bn), lambda i, j: (0, j),
                                   memory_space=pltpu.VMEM),
                      mrow(g.kp), cvec(g.kp), cvec(g.kp)],
            out_specs=[mrow(g.kp), cvec(g.kp), cvec(g.kp)],
            interpret=interpret_mode(),
            compiler_params=_COMPILER_PARAMS,
            name="conv3_bn_bwd_dx_blocked",
        )(dyp, yp, ds1p, ds2p, wp, xp, scp, bip)

    dw_spec = lambda cols, im: pl.BlockSpec(  # noqa: E731
        (g.bm, cols), im, memory_space=pltpu.VMEM)
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, m_real=g.m, bm=g.bm, kp=g.kp,
                          h_img=g.h, w_img=g.w, prologue=prologue),
        out_shape=jax.ShapeDtypeStruct((9 * g.kp, g.np), jnp.float32),
        grid=(g.n_blocks, g.grid),
        in_specs=[dw_spec(g.kp, lambda j, i: (i, 0)),
                  dw_spec(g.bn, lambda j, i: (i, j)),
                  dw_spec(g.bn, lambda j, i: (i, j)),
                  pl.BlockSpec((1, g.bn), lambda j, i: (0, j),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, g.bn), lambda j, i: (0, j),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, g.kp), lambda j, i: (0, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, g.kp), lambda j, i: (0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((9 * g.kp, g.bn), lambda j, i: (0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret_mode(),
        compiler_params=_COMPILER_PARAMS,
        name="conv3_bn_bwd_dw",
    )(xp, dyp, yp, ds1p, ds2p, scp, bip)

    dx = dx[:g.m, :g.c].astype(x4.dtype).reshape(x4.shape)
    dw = dw.reshape(9, g.kp, g.np)[:, :g.c, :g.cout].reshape(
        3, 3, g.c, g.cout).astype(w.dtype)
    if prologue:
        return dx, dw, dsc[0, :g.c], dbi[0, :g.c]
    return dx, dw, jnp.zeros_like(scale), jnp.zeros_like(bias)


# ---------------------------------------------------------------------------
# custom_vjp plumbing + XLA reference/fallback
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fc3(x, w, scale, bias, prologue):
    y, s1, s2 = _fwd_impl(x, w, scale, bias, prologue)
    return y, s1, s2


def _fc3_fwd(x, w, scale, bias, prologue):
    y, s1, s2 = _fwd_impl(x, w, scale, bias, prologue)
    return (y, s1, s2), (x, w, scale, bias, y)


def _fc3_bwd(prologue, res, cts):
    x, w, scale, bias, y = res
    dy, ds1, ds2 = cts
    return _bwd_impl(x, w, scale, bias, y, dy, ds1, ds2, prologue)


_fc3.defvjp(_fc3_fwd, _fc3_bwd)


def xla_conv3_bn(x, w, scale=None, bias=None):
    """Pure-XLA composition with the same contract (fallback + oracle).

    x: (N, H, W, C) NHWC; w: (3, 3, C, C_out) HWIO.
    """
    if scale is not None:
        xn = jnp.maximum(x.astype(jnp.float32) * scale.astype(jnp.float32)
                         + bias.astype(jnp.float32), 0.0).astype(x.dtype)
    else:
        xn = x
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    y = jax.lax.conv_general_dilated(
        xn, w, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=dn).astype(x.dtype)
    yf = y.astype(jnp.float32)
    return (y, jnp.sum(yf, axis=(0, 1, 2)),
            jnp.sum(jnp.square(yf), axis=(0, 1, 2)))


def fused_conv3_bn(x, w, scale=None, bias=None):
    """3x3/s1/p1 NHWC conv with BN stats epilogue and optional
    normalize+ReLU prologue.

    Args:
      x: (N, H, W, C) activations (bf16 or f32).
      w: (3, 3, C, C_out) HWIO conv kernel.
      scale, bias: optional per-C fp32 normalize constants; when given,
        relu(x*scale+bias) is applied in-register (never materialized).

    Returns ``(y, s1, s2)``: y (N, H, W, C_out) plus fp32 per-channel
    ``s1 = sum(y)``, ``s2 = sum(y^2)`` over N*H*W (one-pass BN stats:
    mean = s1/M, var = s2/M - mean^2).
    """
    if w.ndim != 4 or w.shape[0] != 3 or w.shape[1] != 3:
        raise ValueError(f"fused_conv3_bn needs a 3x3 HWIO kernel, "
                         f"got {w.shape}")
    args = (x, w) if scale is None else (x, w, scale, bias)
    # per-width tuning knob: once an on-chip A/B of the kernel against
    # the XLA composition exists (none has been run), restrict the
    # kernel to the input widths where it wins, e.g.
    # MXNET_FUSED_CONV3_WIDTHS=64,128 — the rest ride the composition
    widths = os.environ.get("MXNET_FUSED_CONV3_WIDTHS")
    if widths is not None and x.shape[-1] not in {
            int(v) for v in widths.split(",") if v}:
        return xla_conv3_bn(*args)

    def conv3_bn(x, w, scale=None, bias=None):
        if not _Geom(x, w.shape[-1]).fits():
            warnings.warn(
                f"fused_conv3_bn: no whole-image blocking of "
                f"x{tuple(x.shape)} -> {w.shape[-1]} channels fits the "
                f"{_VMEM_BUDGET >> 20} MiB VMEM budget; this conv runs "
                "the XLA composition")
            return xla_conv3_bn(x, w, scale, bias)
        if scale is None:
            ones = jnp.ones((x.shape[-1],), jnp.float32)
            return _fc3(x, w, ones, jnp.zeros_like(ones), False)
        return _fc3(x, w, scale, bias, True)

    return dispatch(conv3_bn, xla_conv3_bn, *args)
