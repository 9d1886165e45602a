"""The Pallas pair of ``causal_conv1d`` (``ops/ssm_ops.py``) in interpret mode
on the CPU, against the XLA composition it stands beside and against the
convolution a position at a time: channels that are one lane tile and
several, lengths that are whole turns of a step's walk and that are not,
four taps and two, a batch.  What only Mosaic refuses shows in
``tests/test_tpu_compile.py``; how fast the pair is, on the chip."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from incubator_mxnet_tpu.ops import pallas_kernels as pk
from incubator_mxnet_tpu.ops import ssm_ops

F32, BF16 = jnp.float32, jnp.bfloat16
NAMES = "y dx dw db".split()
TURN = 16       # positions a turn of a step's walk here; 128 on the chip

# (batch, T, channels, taps): one turn of one lane tile; several turns with
# a last one that is padded, several lane tiles, a batch; two taps
GEOMETRIES = {
    "one_turn_one_tile": (1, TURN, 128, 4),
    "padded_turns_tiles_batch2": (2, 2 * TURN + 11, 384, 4),
    "two_taps": (1, 2 * TURN, 256, 2),
}


@pytest.fixture
def short_turns(monkeypatch):
    """The same kernel bodies walking 16 positions a turn (bfloat16's
    smallest tile), so that a sequence of several turns is short enough for
    the interpreter and for the loop it is held to."""
    monkeypatch.setattr(ssm_ops, "_CONV_ROWS", TURN)


def _rel(a, b):
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / (jnp.linalg.norm(b.ravel()) + 1e-30))


def _inputs(batch, t, channels, taps, dtype=F32, seed=0):
    rng = onp.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    return ((draw(batch, t, channels).astype(dtype),
             (draw(channels, taps) * 0.5).astype(dtype),
             (draw(channels) * 0.5).astype(dtype)), draw(batch, t, channels))


def _loop(x, weight, bias):
    """The convolution a position and a tap at a time, float32, as
    ``tests/test_falcon_h1.py::test_causal_conv1d_against_a_loop`` writes
    it (in jax.numpy, so that it has gradients)."""
    x, weight, bias = (v.astype(F32) for v in (x, weight, bias))
    taps, rows = weight.shape[1], []
    for t in range(x.shape[1]):
        acc = bias
        for k in range(taps):
            if t - (taps - 1) + k >= 0:
                acc = acc + weight[:, k] * x[:, t - (taps - 1) + k]
        rows.append(acc / (1 + jnp.exp(-acc)))
    return jnp.stack(rows, axis=1)


def _value_and_grads(fn, args, weigh):
    return (fn(*args),) + jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(F32) * weigh),
        argnums=(0, 1, 2)))(*args)


def _side(monkeypatch, flag):
    """``causal_conv1d`` with the dispatch forced: '1' the kernel pair
    (interpreted here), '0' the composition."""
    monkeypatch.setenv("MXNET_USE_PALLAS", flag)
    return ssm_ops.causal_conv1d.fn


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_pair_in_float32_is_the_composition_and_the_loop(
        monkeypatch, short_turns, geometry):
    """``y``, ``dx``, ``dw`` and ``db``, float32: the pair computes what
    the composition computes, tap for tap."""
    args, weigh = _inputs(*GEOMETRIES[geometry])
    ssm_ops.ssm_plans(reset=True)
    mine = _value_and_grads(_side(monkeypatch, "1"), args, weigh)
    (plan,) = ssm_ops.ssm_plans().values()
    assert plan["route"] == "kernel"
    assert plan["padded_rows"] == -GEOMETRIES[geometry][1] % TURN
    for other in (_side(monkeypatch, "0"), _loop):
        for name, got, want in zip(NAMES, mine,
                                   _value_and_grads(other, args, weigh)):
            assert got.shape == want.shape and got.dtype == F32
            assert _rel(got, want) < 2e-6, (name, geometry)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_pair_in_bfloat16_is_as_close_to_float32_as_the_composition(
        monkeypatch, short_turns, geometry):
    """bfloat16 ``x``, taps and bias: both sides sum in float32 and round
    where the composition casts, so each is held to the float32 result on
    the same (rounded) inputs."""
    low, weigh = _inputs(*GEOMETRIES[geometry], dtype=BF16)
    want = _value_and_grads(_loop, low, weigh)
    for flag in "10":
        got = _value_and_grads(_side(monkeypatch, flag), low, weigh)
        for name, mine, theirs in zip(NAMES, got, want):
            assert mine.dtype == BF16 and mine.shape == theirs.shape
            assert _rel(mine, theirs) < 4e-3, (name, geometry, flag)


def test_the_pair_at_the_chips_turn_is_the_composition(monkeypatch):
    """The walk as the chip makes it, 128 positions a turn: two turns, the
    second padded, bfloat16, against the composition."""
    assert ssm_ops._CONV_ROWS == 128
    low, weigh = _inputs(1, 128 + 37, 128, 4, dtype=BF16)
    got = _value_and_grads(_side(monkeypatch, "1"), low, weigh)
    want = _value_and_grads(_side(monkeypatch, "0"), low, weigh)
    for name, mine, theirs in zip(NAMES, got, want):
        # a last bit of a few sums taken in another order
        assert mine.dtype == BF16 and _rel(mine, theirs) < 1e-4, name


def test_the_first_rows_see_zeros_and_the_later_rows_their_own_past(
        monkeypatch, short_turns):
    """One impulse at position 0 and one at the first position of the second
    turn: ``y`` after each is the taps in reverse order and nothing before
    it moves — a halo read from the wrong side, or from the block's end,
    would show in the first three rows of a turn.  Backward, a cotangent at one
    position reaches the three positions before it and none after, across
    the turn's edge too."""
    taps = jnp.asarray(onp.tile([1.0, 2.0, 3.0, 4.0], (128, 1)), F32)
    bias = jnp.zeros((128,), F32)
    conv = _side(monkeypatch, "1")
    silu = lambda v: v / (1 + onp.exp(-v))
    for at in (0, TURN):
        x = jnp.zeros((1, 2 * TURN, 128), F32).at[0, at].set(1.0)
        y = onp.asarray(conv(x, taps, bias))[0, :, 0]
        assert onp.allclose(y[at:at + 4], silu(onp.array([4., 3., 2., 1.])),
                            atol=1e-6)
        assert not y[:at].any() and not y[at + 4:].any()
    for at in (2, TURN + 1, 2 * TURN - 1):
        weigh = jnp.zeros((1, 2 * TURN, 128), F32).at[0, at].set(1.0)
        dx = jax.grad(lambda v: jnp.sum(conv(v, taps, bias) * weigh))(
            jnp.zeros((1, 2 * TURN, 128), F32))
        dx = onp.asarray(dx)[0, :, 0]
        first = max(at - 3, 0)
        # silu'(0) = 1/2, times the tap that read the position
        assert onp.allclose(dx[first:at + 1],
                            0.5 * onp.array([1., 2., 3., 4.])[first - at - 1:])
        assert not dx[:first].any() and not dx[at + 1:].any()


def test_the_route_goes_by_the_shape(monkeypatch):
    """Forced to the kernel side, a toy width still takes the composition
    (``xla:shape`` in ``kernel_routes`` and in its plan), as do more taps
    than a block's rows hold and a sequence whose blocks do not fit; a
    float16 input too (``xla:dtype``); channels of whole lane tiles take
    the pair, a lane tile a step."""
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")
    pk.kernel_routes(reset=True)
    ssm_ops.ssm_plans(reset=True)
    (toy, _), (real, _) = _inputs(2, 11, 6, 4), _inputs(2, 75, 384, 4)
    assert ssm_ops._CONV_ROWS == 128
    ssm_ops.causal_conv1d.fn(*toy)
    ssm_ops.causal_conv1d.fn(*real)
    ssm_ops.causal_conv1d.fn(*(v.astype(jnp.float16) for v in real))
    ssm_ops.causal_conv1d.fn(*_inputs(1, 16, 128, 8)[0])
    assert pk.kernel_routes()["causal_conv1d"] == {
        "xla:shape": 2, "kernel": 1, "xla:dtype": 1}
    plans = ssm_ops.ssm_plans()
    assert plans["conv b2 t11 c6 k4 float32"] == {
        "route": "xla:shape", "taps": 4, "channels_a_step": 6,
        "padded_rows": 0, "grid_steps_fwd": 0, "grid_steps_bwd": 0,
        "vmem_bytes": 0}
    assert plans["conv b2 t75 c384 k4 float16"]["route"] == "xla:dtype"
    assert plans["conv b1 t16 c128 k8 float32"]["route"] == "xla:shape"
    taken = plans["conv b2 t75 c384 k4 float32"]
    assert taken == {
        "route": "kernel", "taps": 4, "channels_a_step": 128,
        "padded_rows": 53, "grid_steps_fwd": 6, "grid_steps_bwd": 6,
        "vmem_bytes": taken["vmem_bytes"]}
    assert 2 * 2 ** 20 < taken["vmem_bytes"] < 16 * 2 ** 20
    assert ssm_ops._ConvPlan(1, 4096, 10240, 4, BF16).stats("kernel") == {
        "route": "kernel", "taps": 4, "channels_a_step": 128,
        "padded_rows": 0, "grid_steps_fwd": 80, "grid_steps_bwd": 80,
        "vmem_bytes": 12_591_104}
    long = ssm_ops._ConvPlan(1, 2 ** 17, 128, 4, BF16)
    assert long.why_not == "shape" and long.vmem_bwd > 48 * 2 ** 20
    assert ssm_ops._ConvPlan(1, 4096, 10240, 4, BF16).why_not is None
    assert ssm_ops._ConvPlan(1, 4096, 5120, 4, BF16).why_not is None


def test_under_a_mesh_the_composition_runs(monkeypatch):
    """A program GSPMD partitions gets the composition, as every kernel's
    op does: on a TPU backend ``dispatch`` counts ``xla:gspmd``."""
    monkeypatch.delenv("MXNET_USE_PALLAS", raising=False)
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    (real, _) = _inputs(1, 64, 128, 4)
    pk.kernel_routes(reset=True)
    ssm_ops.ssm_plans(reset=True)
    with pk.gspmd_trace():
        text = str(jax.make_jaxpr(ssm_ops.causal_conv1d.fn)(*real))
    assert "pallas_call" not in text
    assert pk.kernel_routes()["causal_conv1d"] == {"xla:gspmd": 1}
    (plan,) = ssm_ops.ssm_plans().values()
    assert plan["route"] == "xla:gspmd" and plan["grid_steps_bwd"] == 0


def test_the_pair_keeps_its_inputs_and_nothing_else(monkeypatch):
    """The residuals are ``x``, the taps and the bias — no float32 sum before
    SiLU, no padded input — and the program of forward and backward is the
    two calls."""
    (x, w, b), weigh = _inputs(1, 64, 256, 4, dtype=BF16)
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")
    plan = ssm_ops._ConvPlan(*x.shape, 4, x.dtype)
    _, kept = ssm_ops._conv_kernels_fwd(plan, x, w, b)
    assert [(v.shape, v.dtype) for v in kept] == [
        (v.shape, v.dtype) for v in (x, w, b)]
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssm_ops.causal_conv1d.fn(*a).astype(F32) * weigh),
        argnums=(0, 1, 2)))(x, w, b))
    assert "causal_conv1d_fwd" in text and "causal_conv1d_bwd" in text
    assert "f32[1,67,256]" not in text          # the composition's padding
