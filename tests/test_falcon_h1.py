"""The state-space / attention hybrid decoder against its plain reference at
a small size on the CPU (hidden 64, two blocks of a 4-head Mamba-2 mixer
beside 4-over-2 grouped-query attention and a gated MLP, 96 ids, the
family's multipliers), and what it brought to the ops: the chunked scan
against the step-by-step recurrence and the quadratic form, the causal
convolution against a loop, fewer key heads than query heads, the rotary
embedding over two halves, and blocks that are run again in the backward
pass.  The reference is the benchmark's copy
(``chipbench/configs/falcon_h1_34b_ref.py``), which shares no code with the
package."""
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import incubator_mxnet_tpu as mx  # noqa: E402
from incubator_mxnet_tpu import amp, profiler  # noqa: E402
from incubator_mxnet_tpu.fuse import make_fused_train_step  # noqa: E402
from incubator_mxnet_tpu.ndarray import NDArray  # noqa: E402
from incubator_mxnet_tpu.ops import moe_ops, nn_ops, ssm_ops  # noqa: E402
from incubator_mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402
from chipbench.configs import falcon_h1_34b as model  # noqa: E402
from chipbench.configs import falcon_h1_34b_ref as ref  # noqa: E402

TOY = os.path.join(REPO, "tests", "chipbench", "toy_falcon_h1", "cells",
                   "configs", "toy_falcon_h1.json")
REAL = os.path.join(REPO, "chipbench", "configs", "falcon_h1_34b.json")
TRAFFIC = {"batch": 2, "seq_len": 21, "successors": 4}
F32 = jnp.float32


def _rel(a, b):
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / (jnp.linalg.norm(b.ravel()) + 1e-30))


@pytest.fixture(scope="module")
def config():
    with open(TOY) as f:
        return json.load(f)


# ------------------------------------------------------------ the scan

def _recurrence(x, dt, a, b, c, d):
    """``S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t B_tᵀ``, ``y_t = S_t C_t + D
    x_t``, a position at a time."""
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


def _quadratic(x, dt, a, b, c, d):
    """The reference's closed form over the whole sequence, a sequence at a
    time."""
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda x, dt, b, c: ref.scan_quadratic(
            x, dt, a, b, c, d[:, None], jnp.matmul))(x, dt, b, c)


def _scan_inputs(t, step_size, seed=0):
    rng = onp.random.default_rng(seed)
    batch, heads, p, groups, n = 2, 4, 8, 2, 16
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    return (draw(batch, t, heads, p),
            jax.nn.softplus(draw(batch, t, heads)) * step_size,
            -jnp.asarray(rng.uniform(1, 16, heads), F32),
            draw(batch, t, groups, n), draw(batch, t, groups, n),
            draw(heads))


# one chunk, several chunks, a length that is no multiple of the chunk;
# steps so small that the state barely decays, and so large that hardly
# anything of it outlives a position
@pytest.mark.parametrize("step_size", [0.002, 1.0],
                         ids=["decay_near_1", "decay_near_0"])
@pytest.mark.parametrize("t", [8, 24, 21])
def test_ssd_scan_agrees_with_the_recurrence_and_the_quadratic_form(
        t, step_size):
    args = _scan_inputs(t, step_size)
    weigh = jnp.asarray(onp.random.default_rng(1).standard_normal(
        args[0].shape), F32)
    chunked = functools.partial(ssm_ops.ssd_scan.fn, chunk=8)
    results = [jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * weigh), argnums=range(6))(*args)
        for fn in (chunked, _recurrence, _quadratic)]
    assert _rel(chunked(*args), _recurrence(*args)) < 2e-6
    assert _rel(chunked(*args), _quadratic(*args)) < 2e-6
    (value, grads), *others = results
    for other_value, other_grads in others:
        assert abs(value - other_value) <= 2e-5 * abs(other_value) + 1e-4
        for name, mine, theirs in zip("x dt A B C D".split(), grads,
                                      other_grads):
            assert _rel(mine, theirs) < 1e-4, (name, t, step_size)


def test_ssd_scan_pads_with_rows_that_leave_the_state_alone():
    """21 positions in chunks of 8 are 3 chunks with 3 padded rows; the
    plan says so, and the 21 outputs are those of the first 21 of 24."""
    x, dt, a, b, c, d = _scan_inputs(24, 0.1)
    ssm_ops.ssm_plans(reset=True)
    short = ssm_ops.ssd_scan.fn(x[:, :21], dt[:, :21], a, b[:, :21],
                                c[:, :21], d, chunk=8)
    (plan,) = ssm_ops.ssm_plans().values()
    # a chunk of 8 is no lane tile: the composition, all heads a step
    assert plan == {"chunk": 8, "chunks": 3, "heads_a_step": 4,
                    "state_bytes_saved": 4 * 2 * 3 * 4 * 8 * 16,
                    "padded_rows": 3, "route": "xla:shape",
                    "grid_steps_fwd": 0, "grid_steps_bwd": 0,
                    "vmem_bytes": 0}
    whole = ssm_ops.ssd_scan.fn(x, dt, a, b, c, d, chunk=8)
    assert _rel(short, whole[:, :21]) < 1e-6
    assert "ssm_plans" in profiler.provider_stats()


def test_ssd_scan_in_bfloat16_keeps_its_decays_in_float32():
    x, dt, a, b, c, d = _scan_inputs(24, 0.1)
    low = ssm_ops.ssd_scan.fn(x.astype(jnp.bfloat16), dt, a,
                              b.astype(jnp.bfloat16), c.astype(jnp.bfloat16),
                              d, chunk=8)
    assert low.dtype == jnp.bfloat16
    assert _rel(low, _recurrence(x, dt, a, b, c, d)) < 1.5e-2
    text = str(jax.make_jaxpr(functools.partial(
        ssm_ops.ssd_scan.fn, chunk=8))(x.astype(jnp.bfloat16), dt, a, b, c,
                                       d))
    assert "exp" in text and "bf16[2,3,4,2,2,8,16]" not in text  # states f32


def test_causal_conv1d_against_a_loop():
    rng = onp.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 11, 6)), F32)
    w = jnp.asarray(rng.standard_normal((6, 4)), F32)
    bias = jnp.asarray(rng.standard_normal(6), F32)
    want = onp.zeros(x.shape, onp.float32)
    for t in range(11):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += onp.asarray(w[:, k]) * onp.asarray(
                    x[:, t - 3 + k])
    want = want + onp.asarray(bias)
    want = want / (1 + onp.exp(-want))
    got, pull = jax.vjp(ssm_ops.causal_conv1d.fn, x, w, bias)
    assert _rel(got, want) < 1e-6
    ref_got, ref_pull = jax.vjp(
        lambda x, w, b: jax.vmap(lambda s: ref.conv_silu(s, w, b))(x),
        x, w, bias)
    for mine, theirs in zip(pull(jnp.ones_like(got)),
                            ref_pull(jnp.ones_like(got))):
        assert _rel(mine, theirs) < 1e-6


# ------------------------------------------- attention and the rotary op

def _qkv(hq, hkv, t=24, d=16, dtype=F32):
    rng = onp.random.default_rng(5)
    draw = lambda h: jnp.asarray(rng.standard_normal((2, h, t, d)), dtype)
    return draw(hq), draw(hkv), draw(hkv)


@pytest.mark.parametrize("route", ["0", "1"], ids=["composition", "kernel"])
def test_fewer_key_heads_than_query_heads(route, monkeypatch):
    """Query head ``i`` reads key head ``i // 3``: the op over (6, 2) heads
    gives what it gives over keys and values repeated by hand, outputs and
    all three gradients (``dk`` and ``dv`` summed over a group)."""
    monkeypatch.setenv("MXNET_USE_PALLAS", route)
    q, k, v = _qkv(6, 2)
    attend = functools.partial(nn_ops.dot_product_attention.fn, causal=True)
    by_hand = lambda q, k, v: attend(q, jnp.repeat(k, 3, 1),
                                     jnp.repeat(v, 3, 1))
    pk.kernel_routes(reset=True)
    out, pull = jax.vjp(attend, q, k, v)
    want, want_pull = jax.vjp(by_hand, q, k, v)
    assert out.shape == q.shape and _rel(out, want) < 1e-6
    for mine, theirs in zip(pull(jnp.ones_like(out)),
                            want_pull(jnp.ones_like(out))):
        assert mine.shape == theirs.shape and _rel(mine, theirs) < 1e-6
    expected = "xla:flag" if route == "0" else "kernel"
    assert pk.kernel_routes()["flash_attention"][expected] >= 1
    with pytest.raises(ValueError, match="5 query heads over 2 key"):
        attend(q[:, :5], k, v)


def test_equal_head_counts_trace_to_the_parents_program(monkeypatch):
    """BERT's and JoyAI's attention calls read unchanged: with as many key
    heads as query heads both sides of the dispatch trace to the program
    they traced to before keys could be fewer (the composition copied here
    from the parent commit; the kernel's wrapper was one call of
    ``_flash_core``)."""
    q, k, v = _qkv(4, 4, dtype=jnp.bfloat16)

    def parents(q, k, v):
        logits = jnp.einsum("bhtd,bhsd->bhts", q, k,
                            preferred_element_type=jnp.float32) * 0.25
        t, s = logits.shape[-2:]
        logits = jnp.where(jnp.tril(jnp.ones((t, s), bool)), logits,
                           -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhts,bhsd->bhtd", probs, v)

    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    attend = functools.partial(nn_ops.dot_product_attention.fn, causal=True)
    assert str(jax.make_jaxpr(attend)(q, k, v)) == \
        str(jax.make_jaxpr(parents)(q, k, v))
    assert str(jax.make_jaxpr(functools.partial(
        pk.flash_attention, causal=True))(q, k, v)) == \
        str(jax.make_jaxpr(lambda q, k, v: pk._flash_core(
            q, k, v, 0.25, True))(q, k, v))
    assert pk.repeat_kv_heads(q, k, v) == (k, v)


def test_rope_in_two_halves_against_complex_multiplication():
    rng = onp.random.default_rng(7)
    x = rng.standard_normal((2, 9, 3, 8)).astype(onp.float32)
    theta = 1e11
    angle = onp.arange(9)[:, None] * theta ** (-onp.arange(0, 8, 2) / 8)
    turn = onp.exp(1j * angle)[None, :, None, :]
    as_complex = (x[..., :4] + 1j * x[..., 4:]) * turn
    want = onp.concatenate([as_complex.real, as_complex.imag], -1)
    got = moe_ops.rope.fn(jnp.asarray(x), theta=theta, interleaved=False)
    assert _rel(got, want) < 1e-6
    # the default is the interleaved form, as before
    as_complex = (x[..., 0::2] + 1j * x[..., 1::2]) * turn
    want = onp.stack([as_complex.real, as_complex.imag], -1).reshape(x.shape)
    assert _rel(moe_ops.rope.fn(jnp.asarray(x), theta=theta), want) < 1e-6
    assert str(jax.make_jaxpr(functools.partial(moe_ops.rope.fn, theta=1e4))(
        x)) == str(jax.make_jaxpr(functools.partial(
            moe_ops.rope.fn, theta=1e4, interleaved=True))(x))


def test_grouped_rms_norm_takes_each_groups_mean_square_apart():
    from incubator_mxnet_tpu.models.falcon_h1 import _GroupedRMSNorm
    norm = _GroupedRMSNorm(12, 1e-5, 3)
    norm.initialize()
    gain = onp.linspace(0.5, 2.0, 12).astype(onp.float32)
    norm.gamma.set_data(mx.nd.array(gain))
    x = onp.random.default_rng(8).standard_normal((2, 5, 12)).astype("f")
    parts = x.reshape(2, 5, 3, 4)
    want = (parts / onp.sqrt((parts ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(x.shape) * gain
    assert _rel(norm(mx.nd.array(x)).asnumpy(), want) < 1e-6


# ------------------------------------------------- the model, whole

def _net(config, seed, **changes):
    mx.random.seed(seed)
    return model.build(seed, dict(config, **changes))


def _loss_logits_grads(built, dtype, batch):
    if dtype != "float32":
        amp.convert_block(built["net"], dtype)
    params, apply = built["net"].functional()
    x, y = batch

    def loss_of(p):
        out = apply(p, x, training=True)
        return jnp.mean(built["loss"](NDArray(out), NDArray(y)).data), out

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            loss_of, has_aux=True))(params)
    return params, loss, logits, grads


@pytest.fixture(scope="module")
def batch(config):
    return model.make_batch(3, 0, 2, config, TRAFFIC)


@pytest.fixture(scope="module")
def in_float32(config, batch):
    """The system without recomputation, in float32, and the reference on
    its weights."""
    params, loss, logits, grads = _loss_logits_grads(
        _net(config, 3, recompute="none"), "float32", batch)
    return (params, loss, logits, grads,
            ref.loss_and_grads(params, *batch, config))


def test_the_model_agrees_with_the_reference_in_float32(in_float32):
    params, loss, logits, grads, ((ref_loss, ref_logits), ref_grads) = \
        in_float32
    assert set(grads) == set(ref_grads) == set(params) and len(grads) == 35
    assert abs(float(loss) - float(ref_loss)) < 1e-6 * float(ref_loss)
    assert _rel(logits, ref_logits) < 1e-6
    worst = max((_rel(grads[n], ref_grads[n]), n) for n in grads)
    assert worst[0] < 2e-5, worst
    assert all(float(jnp.linalg.norm(g)) > 0 for g in grads.values())


def test_the_model_agrees_with_the_reference_under_amp_bfloat16(
        config, batch, in_float32):
    """bfloat16 weights and activations against the float32 reference on
    the same (rounded) weights: the limits are the toy cell's, a few times
    what this seed reads (logits 4.5e-3, all gradients 3.9e-3, the worst
    one 1.4e-2)."""
    params, loss, logits, grads = _loss_logits_grads(
        _net(config, 3), "bfloat16", batch)
    kept = {n for n, v in params.items() if v.dtype == jnp.float32}
    assert kept == {n for n in params if n.endswith(
        ("gamma", "a_log", "dt_bias", "d_skip"))}
    (ref_loss, ref_logits), ref_grads = ref.loss_and_grads(
        params, *batch, config)
    assert abs(float(loss) - float(ref_loss)) < 2e-3 * float(ref_loss)
    assert _rel(logits, ref_logits) < 0.03
    off = sum(float(jnp.sum((grads[n].astype(F32) - ref_grads[n]) ** 2))
              for n in grads)
    assert (off / sum(float(jnp.sum(g ** 2)) for g in ref_grads.values())
            ) ** 0.5 < 0.06
    assert max(_rel(grads[n], ref_grads[n]) for n in grads) < 0.12


def test_recomputation_changes_no_number_in_float32(config, batch,
                                                    in_float32):
    """Every block run again in the backward pass: the loss and the logits
    bit for bit; the gradients to the last bits (XLA fuses the region it
    runs again apart from the first run, so single roundings differ: 1e-6
    of a tensor's largest entry is float32's spacing there)."""
    _, loss, logits, grads, _ = in_float32
    _, again_loss, again_logits, again = _loss_logits_grads(
        _net(config, 3, recompute="blocks"), "float32", batch)
    assert float(loss) == float(again_loss)
    assert bool((logits == again_logits).all())
    for name in grads:
        assert float(jnp.max(jnp.abs(grads[name] - again[name]))) <= \
            1e-6 * float(jnp.max(jnp.abs(grads[name]))), name


def _step_jaxpr(config, seed):
    built = _net(config, seed)
    amp.convert_block(built["net"], "bfloat16")
    step = make_fused_train_step(built["net"], built["loss"],
                                 built["optimizer"],
                                 dict(built["optimizer_params"]))
    x, y = model.make_batch(seed, 0, 2, config, TRAFFIC)
    jaxpr = jax.make_jaxpr(step.step_fn)(step.params, step.aux,
                                         step.opt_state, x, y, step._key)
    shapes = sorted(str(v.aval) for eqn in jaxpr.jaxpr.eqns
                    for v in eqn.outvars)
    return jaxpr, shapes


def test_the_steps_program_is_the_same_for_two_seeds(config):
    """Every shape and trip count of the step is the configuration's and
    the traffic's: two seeds trace to the same program, equation for
    equation, with one checkpointed region a block."""
    (a, shapes_a), (b, shapes_b) = (_step_jaxpr(config, s) for s in (11, 12))
    assert shapes_a == shapes_b and str(a) == str(b)
    assert str(a).count("remat2[") == config["num_hidden_layers"]


def test_the_steps_names_carry_the_block_keys_and_the_kernel_scopes(config):
    """What the five readers look for is in the step's ``op_name``s: the
    block keys ``layers/N/mamba|attn|ffn``, the kernel scopes ``ssd_scan``,
    ``causal_conv1d`` and ``flash_attention``, and ``rematted_computation``
    on what a block runs again; ``scope_reduce.parse`` still finds the
    phase and the block of such an instruction."""
    from chipbench import scope_reduce
    built = _net(config, 14)
    step = make_fused_train_step(built["net"], built["loss"],
                                 built["optimizer"],
                                 dict(built["optimizer_params"]))
    x, y = model.make_batch(14, 0, 2, config, TRAFFIC)
    pk.kernel_routes(reset=True)
    text = jax.jit(step.step_fn).lower(
        step.params, step.aux, step.opt_state, x, y, step._key
    ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    segments = lambda name: set(name.split("/"))
    for wanted in ({"layers", "1", "mamba", "ssd_scan"},
                   {"layers", "0", "mamba", "causal_conv1d"},
                   {"layers", "1", "attn", "flash_attention"},
                   {"layers", "0", "ffn"},
                   {"rematted_computation", "mamba", "ssd_scan"},
                   {"rematted_computation", "ffn"}):
        assert any(wanted <= segments(n) for n in names), wanted
    again = next(n for n in names if "rematted_computation" in n
                 and "ssd_scan" in n)
    parsed = scope_reduce.parse(again)
    assert parsed.phase == "backward" and parsed.op == "ssd_scan"
    assert parsed.blocks[0] == "layers" and parsed.blocks[1] in "01"
    routes = pk.kernel_routes()     # an op is traced once a signature
    # the toy heads (P 8, state 16, chunks of 8) are no lane tiles: the
    # kernel pair leaves them to the composition
    assert set(routes["ssd_scan"]) == {"xla:shape"}
    # the toy's convolution is one lane tile wide (4 x 16 + 2 x 2 x 16
    # channels): the pair's, where there is a chip
    assert set(routes["causal_conv1d"]) == {"xla:no_tpu"}


def test_one_layer_has_the_published_parameter_count():
    """430,120,032 parameters a layer and 2,054,718,848 in the cut, from
    the built net's own shapes, nothing allocated."""
    with open(REAL) as f:
        real = json.load(f)

    def shapes(layers):
        def make():
            net = model.build(0, dict(real, num_hidden_layers=layers))["net"]
            return net.functional()[0]
        return jax.eval_shape(make)

    count = lambda tree: sum(v.size for v in tree.values())
    one, two = count(shapes(1)), count(shapes(2))
    assert two - one == 430_120_032 == model.layer_params(real)
    assert one + 3 * (two - one) == 2_054_718_848 == model.total_params(real)
