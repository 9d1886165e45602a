"""Fused 3x3-conv+BN Pallas kernel parity (ops/fused_conv.py).

Oracle: the pure-XLA composition ``xla_conv3_bn`` (identical contract),
checked through fwd outputs, stats, and full VJP — including the
stats-cotangent path (ds1/ds2 feed the producing conv via the BN
constants of the *next* layer, the bottleneck-chain dataflow).  Kernels
run in interpret mode on CPU; that they compile for the chip is
tests/test_tpu_compile.py, the on-chip proof chip_smoke.py's kernels phase.
"""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest

from incubator_mxnet_tpu.ops import fused_conv as fc


@pytest.fixture(autouse=True)
def _force_pallas(monkeypatch):
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")


def _mk(n, h, w, c, cout, dtype, seed=0):
    rng = onp.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, h, w, c), dtype) * 0.5
    k = jnp.asarray(rng.randn(3, 3, c, cout), dtype) * ((9 * c) ** -0.5)
    scale = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(c) * 0.2, jnp.float32)
    return x, k, scale, bias


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-4


# geometry sweep: whole-image blocks (8x8 divides the f32 sublane), a
# multi-image block with batch padding (hw=36, bf16 -> b=4 > n), the
# resnet 14px shape (hw=196 needs b=4 for bf16), and a non-square image
SHAPES = [(2, 8, 8, 16, 24),
          (3, 6, 6, 16, 16),
          (2, 14, 14, 32, 16),
          (2, 5, 9, 16, 8)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,c,cout", SHAPES)
@pytest.mark.parametrize("prologue", [False, True])
def test_fwd_parity(dtype, n, h, w, c, cout, prologue):
    x, k, scale, bias = _mk(n, h, w, c, cout, dtype)
    y, s1, s2 = fc._fc3(x, k, scale, bias, prologue)
    yr, s1r, s2r = fc.xla_conv3_bn(x, k, scale if prologue else None,
                                   bias if prologue else None)
    tol = _tol(dtype)
    m = n * h * w
    onp.testing.assert_allclose(onp.asarray(y, onp.float32),
                                onp.asarray(yr, onp.float32),
                                rtol=tol, atol=tol)
    onp.testing.assert_allclose(onp.asarray(s1), onp.asarray(s1r),
                                rtol=tol, atol=tol * m)
    onp.testing.assert_allclose(onp.asarray(s2), onp.asarray(s2r),
                                rtol=tol, atol=tol * m)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,c,cout", [SHAPES[0], SHAPES[1], SHAPES[3]])
@pytest.mark.parametrize("prologue", [False, True])
def test_vjp_parity(dtype, n, h, w, c, cout, prologue):
    x, k, scale, bias = _mk(n, h, w, c, cout, dtype, seed=1)
    rng = onp.random.RandomState(2)
    dy = jnp.asarray(rng.randn(n, h, w, cout), dtype) * 0.1
    ds1 = jnp.asarray(rng.randn(cout), jnp.float32) * 0.01
    ds2 = jnp.asarray(rng.randn(cout), jnp.float32) * 0.001

    def run(fused):
        def f(x, k, scale, bias):
            if fused:
                return fc._fc3(x, k, scale, bias, prologue)
            return fc.xla_conv3_bn(x, k, scale if prologue else None,
                                   bias if prologue else None)
        out, vjp = jax.vjp(f, x, k, scale, bias)
        return out, vjp((dy, ds1, ds2))

    (y, s1, s2), (dx, dk, dsc, dbi) = run(True)
    (yr, _, _), (dxr, dkr, dscr, dbir) = run(False)
    tol = _tol(dtype)
    m = n * h * w
    onp.testing.assert_allclose(onp.asarray(dx, onp.float32),
                                onp.asarray(dxr, onp.float32),
                                rtol=5 * tol, atol=5 * tol)
    onp.testing.assert_allclose(onp.asarray(dk, onp.float32),
                                onp.asarray(dkr, onp.float32),
                                rtol=5 * tol, atol=tol * m ** 0.5)
    if prologue:
        onp.testing.assert_allclose(onp.asarray(dsc), onp.asarray(dscr),
                                    rtol=5 * tol, atol=tol * m ** 0.5)
        onp.testing.assert_allclose(onp.asarray(dbi), onp.asarray(dbir),
                                    rtol=5 * tol, atol=tol * m ** 0.5)


def test_chain_grad_through_bn_consts():
    """fmm -> bn_consts -> prologue conv3 -> bn_consts -> loss: the
    full fused-bottleneck dataflow with the conv in the middle."""
    from incubator_mxnet_tpu.ops import fused_block as fb
    n, h, w, c, cout = 2, 8, 8, 16, 24
    x, k, _, _ = _mk(n, h, w, c, cout, jnp.float32, seed=3)
    rng = onp.random.RandomState(4)
    gamma = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(c), jnp.float32)
    m = n * h * w

    def chain(fused):
        conv = fc._fc3 if fused else (
            lambda x, k, s, b, p: fc.xla_conv3_bn(
                x, k, s if p else None, b if p else None))

        def f(x, k, gamma, beta):
            s1 = jnp.sum(x.reshape(-1, c), axis=0)
            s2 = jnp.sum(jnp.square(x.reshape(-1, c)), axis=0)
            sc, bi, _, _ = fb.bn_consts(s1, s2, m, gamma, beta)
            y, t1, t2 = conv(x, k, sc, bi, True)
            return jnp.sum(jnp.square(y)) + jnp.sum(t1) + jnp.sum(t2)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3))(
            x, k, gamma, beta)

    v, g = chain(True)
    vr, gr = chain(False)
    onp.testing.assert_allclose(float(v), float(vr), rtol=1e-4)
    for a, b in zip(g, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("prologue", [False, True])
def test_multi_nblock_parity(dtype, prologue, monkeypatch):
    """Wide outputs split into several N blocks (the 512-channel
    stage-4 path): constrain the VMEM budget so cout=260 (padded 384)
    runs with bn=128 (3 N blocks) and check full fwd+VJP parity,
    including the fp32 dx accumulation and the last-block prologue
    backward.  n=8 makes the M grid multi-block too (review finding:
    every cross-i interaction — per-i dx re-init, dsc/dbi and stats
    accumulation across M under the 2-D grids — must actually
    execute)."""
    import incubator_mxnet_tpu.ops.fused_conv as fcm
    n, h, w, c, cout = 16, 6, 6, 16, 260
    x, k, scale, bias = _mk(n, h, w, c, cout, dtype, seed=5)
    g_full = fcm._Geom(x, cout)
    assert g_full.bn == g_full.np  # sanity: unconstrained = one block
    monkeypatch.setattr(fcm, "_VMEM_BUDGET",
                        g_full._bytes(128) + 1)
    g = fcm._Geom(x, cout)
    assert g.bn == 128 and g.n_blocks == 3 and g.fits()
    assert g.grid >= 2  # multi M block as well

    rng = onp.random.RandomState(6)
    dy = jnp.asarray(rng.randn(n, h, w, cout), dtype) * 0.1
    ds1 = jnp.asarray(rng.randn(cout), jnp.float32) * 0.01
    ds2 = jnp.asarray(rng.randn(cout), jnp.float32) * 0.001

    def run(fused):
        def f(x, k, scale, bias):
            if fused:
                return fc._fc3(x, k, scale, bias, prologue)
            return fc.xla_conv3_bn(x, k, scale if prologue else None,
                                   bias if prologue else None)
        out, vjp = jax.vjp(f, x, k, scale, bias)
        return out, vjp((dy, ds1, ds2))

    (y, s1, s2), (dx, dk, dsc, dbi) = run(True)
    (yr, s1r, s2r), (dxr, dkr, dscr, dbir) = run(False)
    tol = _tol(dtype)
    m = n * h * w
    onp.testing.assert_allclose(onp.asarray(y, onp.float32),
                                onp.asarray(yr, onp.float32),
                                rtol=tol, atol=tol)
    onp.testing.assert_allclose(onp.asarray(s1), onp.asarray(s1r),
                                rtol=tol, atol=tol * m)
    onp.testing.assert_allclose(onp.asarray(dx, onp.float32),
                                onp.asarray(dxr, onp.float32),
                                rtol=5 * tol, atol=5 * tol)
    onp.testing.assert_allclose(onp.asarray(dk, onp.float32),
                                onp.asarray(dkr, onp.float32),
                                rtol=5 * tol, atol=tol * m ** 0.5)
    if prologue:
        onp.testing.assert_allclose(onp.asarray(dsc), onp.asarray(dscr),
                                    rtol=5 * tol, atol=tol * m ** 0.5)
        onp.testing.assert_allclose(onp.asarray(dbi), onp.asarray(dbir),
                                    rtol=5 * tol, atol=tol * m ** 0.5)


@pytest.mark.parametrize("prologue", [False, True])
def test_roll_shift_impl_parity(prologue, monkeypatch):
    """The wrap-around (roll) shift implementation must be numerically
    identical to the zero-fill default — the masks cover every wrapped
    row (the _shift_rows contract the on-chip escape hatch relies on)."""
    n, h, w, c, cout = 3, 6, 6, 16, 24
    x, k, scale, bias = _mk(n, h, w, c, cout, jnp.float32, seed=7)
    rng = onp.random.RandomState(8)
    dy = jnp.asarray(rng.randn(n, h, w, cout), jnp.float32) * 0.1
    ds1 = jnp.asarray(rng.randn(cout), jnp.float32) * 0.01
    ds2 = jnp.asarray(rng.randn(cout), jnp.float32) * 0.001

    def run():
        out, vjp = jax.vjp(
            lambda *a: fc._fc3(*a, prologue), x, k, scale, bias)
        return out, vjp((dy, ds1, ds2))

    monkeypatch.setenv("MXNET_FUSED_CONV3_SHIFT", "concat")
    (y1, s11, s21), g1 = run()
    monkeypatch.setenv("MXNET_FUSED_CONV3_SHIFT", "roll")
    (y2, s12, s22), g2 = run()
    for a, b in [(y1, y2), (s11, s12), (s21, s22)] + list(zip(g1, g2)):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=1e-6, atol=1e-6)


def test_dispatch_falls_back_on_unsupported():
    """Non-3x3 kernels raise; over-budget geometry silently uses the
    XLA composition (identical results either way)."""
    x, k, scale, bias = _mk(2, 8, 8, 16, 8, jnp.float32)
    with pytest.raises(ValueError):
        fc.fused_conv3_bn(x, jnp.zeros((1, 1, 16, 8), jnp.float32))
    # the dispatcher output must equal the oracle regardless of path
    y, s1, s2 = fc.fused_conv3_bn(x, k, scale, bias)
    yr, s1r, s2r = fc.xla_conv3_bn(x, k, scale, bias)
    onp.testing.assert_allclose(onp.asarray(y), onp.asarray(yr),
                                rtol=1e-4, atol=1e-4)
    # a tiny VMEM budget must force the fallback, not an error
    import incubator_mxnet_tpu.ops.fused_conv as fcm
    old = fcm._VMEM_BUDGET
    try:
        fcm._VMEM_BUDGET = 1
        assert not fcm._Geom(x, 8).fits()
        y2, _, _ = fc.fused_conv3_bn(x, k, scale, bias)
        onp.testing.assert_allclose(onp.asarray(y2), onp.asarray(yr),
                                    rtol=1e-4, atol=1e-4)
    finally:
        fcm._VMEM_BUDGET = old
