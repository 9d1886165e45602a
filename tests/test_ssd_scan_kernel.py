"""The Pallas pair of ``ssd_scan`` (``ops/ssm_ops.py``) in interpret mode on
the CPU, against the XLA composition it stands beside and against the
recurrence a position at a time, at the smallest case of each published head
geometry that is whole lane tiles: heads of 64 that share a tile over a
128-wide state in several groups (Nemotron-H), heads of 128 over a 256-wide
state in two groups (Falcon-H1), chunks of 128.  What only Mosaic refuses
shows in ``tests/test_tpu_compile.py``; how fast the pair is, on the chip."""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu.ops import pallas_kernels as pk
from incubator_mxnet_tpu.ops import ssm_ops

F32, BF16 = jnp.float32, jnp.bfloat16
NAMES = "x dt A B C D".split()

# (batch, T, heads, P, groups, N): two chunks; a length that pads; a batch
GEOMETRIES = {
    "h64_n128_g4": (1, 256, 8, 64, 4, 128),
    "h128_n256_g2": (1, 256, 2, 128, 2, 256),
    "h64_n128_padded_batch2": (2, 200, 4, 64, 2, 128),
    # a group's state over 1 MiB: the forward kernel, the composition's
    # backward from the kernel's residuals
    "h128_n256_state_over_1mib": (1, 256, 9, 128, 1, 256),
}


def _rel(a, b):
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / (jnp.linalg.norm(b.ravel()) + 1e-30))


def _inputs(batch, t, heads, p, groups, n, seed=0):
    rng = onp.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    return (draw(batch, t, heads, p),
            jax.nn.softplus(draw(batch, t, heads)) * 0.1,
            -jnp.asarray(rng.uniform(1, 16, heads), F32),
            draw(batch, t, groups, n), draw(batch, t, groups, n),
            draw(heads)), draw(batch, t, heads, p)


def _recurrence(x, dt, a, b, c, d):
    rep = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(v, rep, axis=2) for v in (b, c))

    def step(state, now):
        x_t, dt_t, b_t, c_t = now
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision="highest") + d[:, None] * x_t

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], F32)
    _, ys = jax.lax.scan(step, start, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1)


def _value_and_grads(fn, args, weigh):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(F32) * weigh),
        argnums=range(6)))(*args)


def _side(monkeypatch, flag):
    """``ssd_scan`` with the dispatch forced: '1' the kernel pair
    (interpreted here), '0' the composition."""
    monkeypatch.setenv("MXNET_USE_PALLAS", flag)
    return functools.partial(ssm_ops.ssd_scan.fn, chunk=128)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_pair_in_float32_is_the_composition_and_the_recurrence(
        monkeypatch, geometry):
    """``y`` and all six gradients, float32: the pair computes what the
    composition computes, product for product."""
    args, weigh = _inputs(*GEOMETRIES[geometry])
    ssm_ops.ssm_plans(reset=True)
    y = _side(monkeypatch, "1")(*args)
    (plan,) = ssm_ops.ssm_plans().values()
    assert plan["route"] == "kernel"
    assert (plan["grid_steps_bwd"] == 0) == ("over_1mib" in geometry)
    value, grads = _value_and_grads(_side(monkeypatch, "1"), args, weigh)
    for other in (_side(monkeypatch, "0"), _recurrence):
        assert _rel(y, other(*args)) < 2e-5
        other_value, other_grads = _value_and_grads(other, args, weigh)
        assert abs(value - other_value) <= 2e-5 * abs(other_value) + 1e-3
        for name, mine, theirs in zip(NAMES, grads, other_grads):
            assert _rel(mine, theirs) < 1e-4, (name, geometry)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_pair_in_bfloat16_is_as_close_to_float32_as_the_composition(
        monkeypatch, geometry):
    """bfloat16 ``x``, ``B`` and ``C`` with float32 Δ, ``A`` and ``D``: both
    sides round their products where the composition casts, so each is held
    to the float32 result on the same (rounded) inputs — ``A``'s gradient
    too, a sum in which the decays' row and column shares nearly cancel."""
    (x, dt, a, b, c, d), weigh = _inputs(*GEOMETRIES[geometry])
    low = (x.astype(BF16), dt, a, b.astype(BF16), c.astype(BF16), d)
    exact = tuple(v.astype(F32) for v in low)
    want_y = _side(monkeypatch, "0")(*exact)
    _, want = _value_and_grads(_side(monkeypatch, "0"), exact, weigh)
    for flag in "10":
        fn = _side(monkeypatch, flag)
        y = fn(*low)
        assert y.dtype == BF16 and _rel(y, want_y) < 6e-3
        _, grads = _value_and_grads(fn, low, weigh)
        for name, mine, theirs in zip(NAMES, grads, want):
            assert mine.dtype == (F32 if name in ("dt", "A", "D") else BF16)
            # a handful of numbers each left over from sums of thousands
            assert _rel(mine, theirs) < (2e-2 if name == "A" else 8e-3), (
                name, geometry, flag)


def test_the_pair_in_bfloat16_keeps_its_decays_in_float32(monkeypatch):
    """The property of ``test_ssd_scan_in_bfloat16_keeps_its_decays_in_
    float32`` on the kernel side: steps so small that a bfloat16 running sum
    would lose them (Δ A of 1e-3 beside a sum of several units) still decay
    the state as the float32 recurrence does, and the entry states the
    forward keeps are float32."""
    (x, dt, a, b, c, d), _ = _inputs(1, 256, 4, 64, 2, 128)
    dt = dt * 0.05
    low = (x.astype(BF16), dt, a, b.astype(BF16), c.astype(BF16), d)
    y = _side(monkeypatch, "1")(*low)
    assert _rel(y, _recurrence(*(v.astype(F32) for v in low))) < 6e-3
    text = str(jax.make_jaxpr(_side(monkeypatch, "1"))(*low))
    assert "ssd_scan_fwd" in text
    assert "f32[1,2,256,128]" in text          # (b, chunks, heads·P, N)
    assert "bf16[1,2,256,128]" not in text


def test_the_route_goes_by_the_shape(monkeypatch):
    """Forced to the kernel side, a toy shape still takes the composition
    (``xla:shape`` in ``kernel_routes`` and in its plan), a float16 one too
    (``xla:dtype``), and a shape of whole lane tiles takes the pair, a
    group's heads a step."""
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")
    pk.kernel_routes(reset=True)
    ssm_ops.ssm_plans(reset=True)
    (toy, _), (real, _) = _inputs(2, 24, 4, 8, 2, 16), _inputs(
        1, 256, 4, 64, 2, 128)
    ssm_ops.ssd_scan.fn(*toy, chunk=8)
    ssm_ops.ssd_scan.fn(*real, chunk=128)
    ssm_ops.ssd_scan.fn(*(v.astype(jnp.float16) for v in real), chunk=128)
    assert pk.kernel_routes()["ssd_scan"] == {
        "xla:shape": 1, "kernel": 1, "xla:dtype": 1}
    plans = ssm_ops.ssm_plans()
    assert plans["b2 t24 h4x8 g2 n16 float32"]["route"] == "xla:shape"
    assert plans["b1 t256 h4x64 g2 n128 float16"]["route"] == "xla:dtype"
    assert plans["b1 t256 h4x64 g2 n128 float32"] == {
        "chunk": 128, "chunks": 2, "heads_a_step": 2,
        "state_bytes_saved": 4 * 2 * 4 * 64 * 128, "padded_rows": 0,
        "route": "kernel", "grid_steps_fwd": 4, "grid_steps_bwd": 4,
        "vmem_bytes": plans["b1 t256 h4x64 g2 n128 float32"]["vmem_bytes"]}
    assert 6 * 2 ** 20 < plans["b1 t256 h4x64 g2 n128 float32"][
        "vmem_bytes"] < 64 * 2 ** 20


def test_under_a_mesh_the_composition_runs(monkeypatch):
    """A program GSPMD partitions gets the composition, as every kernel's
    op does: on a TPU backend ``dispatch`` counts ``xla:gspmd``."""
    monkeypatch.delenv("MXNET_USE_PALLAS", raising=False)
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    (real, _) = _inputs(1, 256, 4, 64, 2, 128)
    pk.kernel_routes(reset=True)
    ssm_ops.ssm_plans(reset=True)
    with pk.gspmd_trace():
        text = str(jax.make_jaxpr(functools.partial(
            ssm_ops.ssd_scan.fn, chunk=128))(*real))
    assert "pallas_call" not in text
    assert pk.kernel_routes()["ssd_scan"] == {"xla:gspmd": 1}
    (plan,) = ssm_ops.ssm_plans().values()
    assert plan["route"] == "xla:gspmd" and plan["heads_a_step"] == 4
