"""Fused matmul+BN Pallas kernel parity (ops/fused_block.py).

Oracle: the pure-XLA composition ``xla_matmul_bn`` (identical contract),
checked through fwd outputs, stats, and full VJP — including the
stats-cotangent path (ds1/ds2 feed the producing matmul via the BN
constants of the *next* layer, exactly how the bottleneck chain uses
it).  Kernels run in interpret mode on CPU (same numerics as Mosaic up
to dot rounding); the on-chip proof is chip_smoke.py's kernels phase.
"""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops import fused_block as fb


@pytest.fixture(autouse=True)
def _force_pallas(monkeypatch):
    """Interpret-mode kernels need the explicit override — scoped per
    test so the flag cannot leak into other files' manifest-gating
    tests (a module-level setenv broke
    test_flash_attention_falls_back_when_marked_bad in the full suite)."""
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")


def _mk(m, k, n, dtype, seed=0):
    rng = onp.random.RandomState(seed)
    x = jnp.asarray(rng.randn(m, k), dtype) * 0.5
    w = jnp.asarray(rng.randn(k, n), dtype) * (k ** -0.5)
    scale = jnp.asarray(rng.rand(k) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(k) * 0.2, jnp.float32)
    return x, w, scale, bias


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(256, 128, 128),   # exact tiles
                                   (200, 96, 72),     # all dims padded
                                   (1024, 256, 64),   # tall-skinny c1 shape
                                   (512, 64, 256)])   # c3 shape
@pytest.mark.parametrize("prologue", [False, True])
def test_fwd_parity(dtype, m, k, n, prologue):
    x, w, scale, bias = _mk(m, k, n, dtype)
    args = (scale, bias) if prologue else (None, None)
    y, s1, s2 = fb._fmm(x, w, scale if prologue else jnp.ones((k,), jnp.float32),
                        bias if prologue else jnp.zeros((k,), jnp.float32),
                        prologue)
    yr, s1r, s2r = fb.xla_matmul_bn(x, w, *args)
    tol = _tol(dtype)
    onp.testing.assert_allclose(onp.asarray(y, onp.float32),
                                onp.asarray(yr, onp.float32),
                                rtol=tol, atol=tol)
    # stats are sums over M: scale tolerance by M
    onp.testing.assert_allclose(onp.asarray(s1), onp.asarray(s1r),
                                rtol=tol, atol=tol * m)
    onp.testing.assert_allclose(onp.asarray(s2), onp.asarray(s2r),
                                rtol=tol, atol=tol * m)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(256, 128, 128), (200, 96, 72)])
@pytest.mark.parametrize("prologue", [False, True])
def test_vjp_parity(dtype, m, k, n, prologue):
    x, w, scale, bias = _mk(m, k, n, dtype, seed=1)
    rng = onp.random.RandomState(2)
    dy = jnp.asarray(rng.randn(m, n), dtype) * 0.1
    ds1 = jnp.asarray(rng.randn(n), jnp.float32) * 0.01
    ds2 = jnp.asarray(rng.randn(n), jnp.float32) * 0.001

    def run(fused):
        def f(x, w, scale, bias):
            if fused:
                return fb._fmm(x, w, scale, bias, prologue)
            return fb.xla_matmul_bn(x, w, scale if prologue else None,
                                    bias if prologue else None)
        out, vjp = jax.vjp(f, x, w, scale, bias)
        return out, vjp((dy, ds1, ds2))

    (y, s1, s2), (dx, dw, dsc, dbi) = run(True)
    (yr, _, _), (dxr, dwr, dscr, dbir) = run(False)
    tol = _tol(dtype)
    onp.testing.assert_allclose(onp.asarray(dx, onp.float32),
                                onp.asarray(dxr, onp.float32),
                                rtol=5 * tol, atol=5 * tol)
    # dw accumulates over M rows: absolute tolerance scales with M
    onp.testing.assert_allclose(onp.asarray(dw, onp.float32),
                                onp.asarray(dwr, onp.float32),
                                rtol=5 * tol, atol=tol * m ** 0.5)
    if prologue:
        onp.testing.assert_allclose(onp.asarray(dsc), onp.asarray(dscr),
                                    rtol=5 * tol, atol=tol * m ** 0.5)
        onp.testing.assert_allclose(onp.asarray(dbi), onp.asarray(dbir),
                                    rtol=5 * tol, atol=tol * m ** 0.5)


def test_bn_consts_chain_grad():
    """End-to-end mini-chain: fmm -> bn_consts -> prologue fmm -> loss.

    Verifies the ds1/ds2 cotangent path through bn_consts matches the
    XLA composition — the exact dataflow of a fused bottleneck block.
    """
    m, k, n1, n2 = 128, 64, 96, 80
    x, w1, _, _ = _mk(m, k, n1, jnp.float32, seed=3)
    _, w2, _, _ = _mk(m, n1, n2, jnp.float32, seed=4)
    gamma = jnp.asarray(onp.random.RandomState(5).rand(n1) + 0.5, jnp.float32)
    beta = jnp.asarray(onp.random.RandomState(6).randn(n1), jnp.float32)

    def chain(fused):
        fn = fb._fmm if fused else (
            lambda x, w, s, b, p: fb.xla_matmul_bn(
                x, w, s if p else None, b if p else None))

        def f(x, w1, w2, gamma, beta):
            y1, s1, s2 = fn(x, w1, jnp.ones((k,), jnp.float32),
                            jnp.zeros((k,), jnp.float32), False)
            sc, bi, _, _ = fb.bn_consts(s1, s2, m, gamma, beta)
            y2, t1, t2 = fn(y1, w2, sc, bi, True)
            return jnp.sum(jnp.square(y2)) + jnp.sum(t1) + jnp.sum(t2)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))(
            x, w1, w2, gamma, beta)

    v, g = chain(True)
    vr, gr = chain(False)
    onp.testing.assert_allclose(float(v), float(vr), rtol=1e-4)
    for a, b in zip(g, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# gluon zoo integration (layout="NHWC", fused=True)
# ---------------------------------------------------------------------------




@pytest.mark.parametrize("thumbnail", [False, True])
def test_zoo_nhwc_layout_matches_nchw(thumbnail):
    """thumbnail=True covers the (O,3,3,3) stem kernel whose OIHW and
    OHWI shapes coincide — a shape heuristic would copy it untransposed
    (review finding); the converter must use layer metadata."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, autograd
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    a = vision.resnet18_v1(classes=10, thumbnail=thumbnail)
    b = vision.resnet18_v1(classes=10, layout="NHWC", thumbnail=thumbnail)
    x = nd.random.uniform(shape=(2, 3, 32, 32))
    a.initialize(ctx=mx.cpu())
    b.initialize(ctx=mx.cpu())
    a(x)
    b(nd.transpose(x, (0, 2, 3, 1)))  # resolve deferred shapes
    from incubator_mxnet_tpu.gluon.utils import convert_conv_params_layout
    convert_conv_params_layout(a, b)
    ya = a(x).asnumpy()
    yb = b(nd.transpose(x, (0, 2, 3, 1))).asnumpy()
    onp.testing.assert_allclose(ya, yb, rtol=1e-4, atol=1e-4)


def test_zoo_fused_bottleneck_matches_unfused():
    """fused=True BottleneckV1 training forward/backward == the layer
    composition, and moving stats update identically.

    Block-level parity is the right oracle: FULL-model grad equality is
    not testable at f32 — the 50-layer tiny-batch-BN gradient is
    chaotic at rounding scale (measured on the CPU at bs=4: a 1e-6
    relative input perturbation moves the PLAIN path's own worst grad
    by ~0.37 relative, as much as fused-vs-plain differ; several BNs
    have var/meansq ~ 2e-2 and rstd amplifies ~7x a layer), so
    fused-vs-plain full-model diffs just re-measure that chaos."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, autograd
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import \
        BottleneckV1
    for stride, down in ((1, False), (2, True)):
        blk_f = BottleneckV1(32, stride, down, in_channels=32 if down else 32,
                             layout="NHWC", fused=True)
        blk_u = BottleneckV1(32, stride, down, in_channels=32 if down else 32,
                             layout="NHWC", fused=False)
        x = nd.random.uniform(shape=(2, 8, 8, 32))
        blk_f.initialize(ctx=mx.cpu())
        blk_u.initialize(ctx=mx.cpu())
        blk_f(x)  # resolve shapes via the (eval-mode) layer path
        blk_u(x)
        for name, p in blk_u.collect_params().items():
            blk_f.collect_params()[name].set_data(p.data())

        def run(blk):
            with autograd.record():
                y = blk(x)
                loss = (y * y).mean()
            loss.backward()
            g = blk.body[0].weight.grad().asnumpy()
            return (y.asnumpy(), g,
                    blk.body[1].running_mean.data().asnumpy(),
                    blk.body[1].running_var.data().asnumpy())

        yf, gf, rmf, rvf = run(blk_f)
        yu, gu, rmu, rvu = run(blk_u)
        onp.testing.assert_allclose(yf, yu, rtol=2e-3, atol=2e-3)
        onp.testing.assert_allclose(gf, gu, rtol=2e-2, atol=2e-3)
        # the fused path must update moving stats like the BN layers do
        onp.testing.assert_allclose(rmf, rmu, rtol=1e-3, atol=1e-4)
        onp.testing.assert_allclose(rvf, rvu, rtol=1e-3, atol=1e-4)


def test_zoo_fused_bottleneck_v2_matches_unfused():
    """fused=True BottleneckV2 (pre-activation) training fwd/bwd == the
    layer composition, incl. moving-stat updates — both the stride-1
    fully-fused path (conv kernel) and the stride-2 branch (XLA 3x3).
    Same block-level oracle rationale as the V1 test above."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, autograd
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import \
        BottleneckV2
    for stride, down in ((1, False), (2, True)):
        blk_f = BottleneckV2(32, stride, down, in_channels=32,
                             layout="NHWC", fused=True)
        blk_u = BottleneckV2(32, stride, down, in_channels=32,
                             layout="NHWC", fused=False)
        x = nd.random.uniform(shape=(2, 8, 8, 32))
        blk_f.initialize(ctx=mx.cpu())
        blk_u.initialize(ctx=mx.cpu())
        blk_f(x)  # resolve shapes via the (eval-mode) layer path
        blk_u(x)
        for name, p in blk_u.collect_params().items():
            blk_f.collect_params()[name].set_data(p.data())

        def run(blk):
            with autograd.record():
                y = blk(x)
                loss = (y * y).mean()
            loss.backward()
            g = blk.conv1.weight.grad().asnumpy()
            return (y.asnumpy(), g,
                    blk.bn2.running_mean.data().asnumpy(),
                    blk.bn2.running_var.data().asnumpy())

        yf, gf, rmf, rvf = run(blk_f)
        yu, gu, rmu, rvu = run(blk_u)
        onp.testing.assert_allclose(yf, yu, rtol=2e-3, atol=2e-3)
        onp.testing.assert_allclose(gf, gu, rtol=2e-2, atol=2e-3)
        onp.testing.assert_allclose(rmf, rmu, rtol=1e-3, atol=1e-4)
        onp.testing.assert_allclose(rvf, rvu, rtol=1e-3, atol=1e-4)


def test_fused_model_under_dp_mesh():
    """The fused-bottleneck model must compile and run under a GSPMD
    data-parallel mesh (FusedTrainStep mesh=...): pallas_call has no
    partitioning rule, so GSPMD replicates around it — correct, and the
    single-chip bench path is unaffected; this guards the combination
    from regressing into a compile error."""
    import numpy as onp_
    import jax
    from jax.sharding import Mesh
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.gluon.model_zoo.vision.resnet import (
        BottleneckV1, ResNetV1)

    net = ResNetV1(BottleneckV1, [1], [16, 64], classes=4, thumbnail=True,
                   layout="NHWC", fused=True)
    net.initialize(ctx=mx.cpu())
    net(nd.random.uniform(shape=(1, 8, 8, 3)))
    mesh = Mesh(onp_.array(jax.devices()).reshape(8,), ("dp",))
    step = make_fused_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "sgd", {"learning_rate": 0.1}, mesh=mesh)
    x = jnp.ones((16, 8, 8, 3), jnp.float32)
    y = jnp.zeros((16,), jnp.int32)
    loss1 = float(step(x, y))
    loss2 = float(step(x, y))
    assert onp.isfinite(loss1) and onp.isfinite(loss2)
    assert loss2 < loss1 + 1e-3  # training on a constant batch descends


def test_fuse_conv_bn_inference_parity():
    """gluon.contrib.fuse_conv_bn folds every Conv->BN pair (incl. the
    pre-activation V2 ordering and biasless convs) with exact eval
    parity, and leaves BatchNormReLU (has a relu inside) alone."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon.contrib import fuse_conv_bn
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    # v2 folds fewer by design: pre-activation bn1 consumes the block
    # INPUT (no producing conv); only conv_i -> bn_{i+1} pairs fold
    for factory, min_pairs in ((vision.resnet18_v1, 20),
                               (vision.resnet18_v2, 9)):
        net = factory(classes=10)
        net.initialize(ctx=mx.cpu())
        x = nd.random.uniform(shape=(2, 3, 32, 32))
        y0 = net(x).asnumpy()
        n = fuse_conv_bn(net)
        y1 = net(x).asnumpy()
        assert n >= min_pairs, n
        onp.testing.assert_allclose(y0, y1, rtol=1e-4, atol=1e-5)
        # folded net still hybridizes and runs
        net.hybridize()
        y2 = net(x).asnumpy()
        onp.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)

    # exclusions: BatchNormReLU (relu inside) and conv with built-in
    # activation (activation runs after the conv) must NOT fold
    from incubator_mxnet_tpu.gluon import nn
    seq = nn.HybridSequential()
    seq.add(nn.Conv2D(4, 3, padding=1, in_channels=3),
            nn.BatchNormReLU(),
            nn.Conv2D(4, 3, padding=1, activation="relu", in_channels=4),
            nn.BatchNorm())
    seq.initialize(ctx=mx.cpu())
    x = nd.random.uniform(shape=(2, 3, 8, 8))
    y0 = seq(x).asnumpy()
    assert fuse_conv_bn(seq) == 0
    onp.testing.assert_allclose(y0, seq(x).asnumpy())


@pytest.mark.parametrize("prologue", [False, True])
def test_nonmultiple_width_fwd_bwd(prologue):
    """n=600 (padded 640) exercises block sizes that do not divide the
    padded width: _div_block must shrink the bwd tiles instead of
    silently dropping columns past 512 (review finding)."""
    m, k, n = 192, 200, 600
    x, w, scale, bias = _mk(m, k, n, jnp.float32, seed=9)
    dy = jnp.asarray(onp.random.RandomState(10).randn(m, n), jnp.float32)
    ds1 = jnp.zeros((n,), jnp.float32)
    ds2 = jnp.zeros((n,), jnp.float32)

    def run(fused):
        f = (lambda *a: fb._fmm(*a, prologue)) if fused else (
            lambda *a: fb.xla_matmul_bn(
                a[0], a[1], a[2] if prologue else None,
                a[3] if prologue else None))
        out, vjp = jax.vjp(f, x, w, scale, bias)
        return out, vjp((dy, ds1, ds2))

    (y, s1, s2), (dx, dw, dsc, dbi) = run(True)
    (yr, s1r, s2r), (dxr, dwr, dscr, dbir) = run(False)
    onp.testing.assert_allclose(onp.asarray(y), onp.asarray(yr),
                                rtol=1e-4, atol=1e-4)
    # the columns past 512 are the regression: they must carry real
    # gradients, not uninitialized pallas output
    onp.testing.assert_allclose(onp.asarray(dw), onp.asarray(dwr),
                                rtol=1e-3, atol=1e-3)
    onp.testing.assert_allclose(onp.asarray(dx), onp.asarray(dxr),
                                rtol=1e-3, atol=1e-3)
