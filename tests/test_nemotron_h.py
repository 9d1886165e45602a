"""The decoder whose layers differ in kind (Mamba-2 mixer, latent mixture of
experts, attention without a position embedding) and its MTP module against
their plain reference at a small size on the CPU, and what the architecture
forced on the shared code: ungated ``relu²`` experts in ``moe_ffn``, a latent
around the experts in ``RoutedFFN``, a share of the experts that adds up to
the uncut layer through the latent's up-projection, the scan at another
shape, attention without rotary at 16 query heads a key head, recomputed
layers that carry a router's state out, and the configuration's own counts.
The reference is the benchmark's copy
(``chipbench/configs/nemotron3_super_120b_ref.py``), which shares no code
with the package."""
import functools
import json
import os
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
from incubator_mxnet_tpu.gluon import nn  # noqa: E402
from incubator_mxnet_tpu.gluon.nn import transformer_layers as tl  # noqa: E402
from incubator_mxnet_tpu.models import nemotron_h  # noqa: E402
from incubator_mxnet_tpu.ndarray import NDArray  # noqa: E402
from incubator_mxnet_tpu.ops import moe_ops, ssm_ops  # noqa: E402
from chipbench.configs import nemotron3_super_120b as model  # noqa: E402
from chipbench.configs import nemotron3_super_120b_ref as ref  # noqa: E402

TOY = os.path.join(REPO, "tests", "chipbench", "toy_nemotron_h", "cells",
                   "configs", "toy_nemotron_h.json")
REAL = os.path.join(REPO, "chipbench", "configs",
                    "nemotron3_super_120b.json")
CELL = os.path.join(REPO, "chipbench", "workloads",
                    "nemotron3-super-train-ep32-b1-s4096.json")
TRAFFIC = {"batch": 2, "seq_len": 21, "successors": 4}
F32 = jnp.float32


def _rel(a, b):
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / (jnp.linalg.norm(b.ravel()) + 1e-30))


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load(TOY)


@pytest.fixture(scope="module")
def real():
    return _load(REAL)


# ------------------------------------------------------ the model, whole

def _net(config, seed, **changes):
    mx.random.seed(seed)
    return model.build(seed, dict(config, **changes))


def _loss_logits_grads(built, dtype, batch):
    if dtype != "float32":
        amp.convert_block(built["net"], dtype)
    params, apply = built["net"].functional()
    x, y = batch

    def loss_of(p):
        with tl.record_routing() as chosen:
            outs = apply(p, x, training=True)
        loss = built["loss"](*map(NDArray, outs), NDArray(y))
        return jnp.mean(loss.data), (outs, list(chosen))

    with jax.default_matmul_precision("highest"):
        (loss, (logits, chosen)), grads = jax.jit(jax.value_and_grad(
            loss_of, has_aux=True))(params)
    return params, loss, logits, grads, chosen


@pytest.fixture(scope="module")
def batch(config):
    return tuple(jnp.asarray(a) for a in model.make_batch(3, 0, 2, config,
                                                          TRAFFIC))


@pytest.fixture(scope="module")
def in_float32(config, batch):
    """The system with every layer recomputed, in float32, and the
    reference on its weights."""
    params, loss, logits, grads, chosen = _loss_logits_grads(
        _net(config, 3), "float32", batch)
    return (params, loss, logits, grads, chosen,
            ref.loss_and_grads(params, *batch, config))


def test_the_model_agrees_with_the_reference_in_float32(in_float32):
    params, loss, (main, mtp), grads, chosen, (
        (ref_loss, (ref_main, ref_mtp, routing)), ref_grads) = in_float32
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    assert _rel(main, ref_main) < 1e-5 and _rel(mtp, ref_mtp) < 1e-5
    # every parameter but the routers' bias and counters has a gradient
    state = {n for n in params if n.endswith(("score_bias", "moe_stats"))}
    assert len(state) == 6 and set(ref_grads) == set(params) - state
    for name, want in ref_grads.items():
        assert _rel(grads[name], want) < 2e-4, name
    # the routers chose what the reference's chose, in the order they ran
    assert list(routing) == ["layers.1.moe", "layers.3.moe",
                             "mtp.block.1.moe"]
    for did, mine in zip(routing.values(), chosen):
        assert (jnp.sort(did["own_idx"], -1) == jnp.sort(mine, -1)).all()


def test_recomputed_layers_give_what_plain_layers_give(config, batch,
                                                       in_float32):
    _, loss, (main, mtp), grads, _, _ = in_float32
    _, plain_loss, (plain_main, plain_mtp), plain_grads, _ = \
        _loss_logits_grads(_net(config, 3, recompute="none"), "float32",
                           batch)
    assert float(loss) == float(plain_loss)
    assert (main == plain_main).all() and (mtp == plain_mtp).all()
    for name, want in plain_grads.items():
        assert _rel(grads[name], want) < 1e-5, name


def test_in_bfloat16_the_model_stays_near_the_reference(config, batch):
    params, loss, (main, mtp), grads, chosen = _loss_logits_grads(
        _net(config, 3), "bfloat16", batch)
    forced = dict(zip(["layers.1.moe", "layers.3.moe", "mtp.block.1.moe"],
                      chosen))
    (ref_loss, (ref_main, ref_mtp, _)), ref_grads = ref.loss_and_grads(
        params, *batch, config, forced=forced, margin=0.004)
    assert abs(float(loss) - float(ref_loss)) < 2e-3 * float(ref_loss)
    assert max(_rel(main, ref_main), _rel(mtp, ref_mtp)) < 0.03
    assert all(_rel(grads[n], g) < 0.12 for n, g in ref_grads.items())


@pytest.mark.parametrize("kind", ["mamba", "attn", "moe"])
def test_each_kind_of_layer_against_the_reference(config, kind):
    """One ``HybridLayer`` of each kind, output and every gradient."""
    built = _net(dict(config, hybrid_override_pattern={
        "mamba": "M", "attn": "*", "moe": "E"}[kind]), 5, recompute="none")
    layer = built["net"].layers[0]
    params, apply = layer.functional()
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 21, 64), F32)
    weigh = jax.random.normal(jax.random.PRNGKey(7), h.shape, F32)
    trainable = {n: v for n, v in params.items()
                 if not n.endswith(("score_bias", "moe_stats"))}
    fixed = {n: v for n, v in params.items() if n not in trainable}

    def system(p, h):
        return jnp.sum(apply({**p, **fixed}, h, training=True) * weigh)

    def reference(p, h):
        return jnp.sum(ref.layer({**p, **fixed}, kind, h, config)[0] * weigh)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(system, (0, 1))(trainable, h)
        want = jax.value_and_grad(reference, (0, 1))(trainable, h)
    assert abs(got[0] - want[0]) < 1e-4 * abs(want[0]) + 1e-4
    assert _rel(got[1][1], want[1][1]) < 1e-4
    for name in trainable:
        assert _rel(got[1][0][name], want[1][0][name]) < 2e-4, name


def test_layer_kinds_from_the_pattern():
    assert nemotron_h.layer_kinds("*EMEM") == ["attn", "moe", "mamba", "moe",
                                               "mamba"]
    for bad in ("*EMX", "", "-"):
        with pytest.raises(ValueError, match="layer pattern"):
            nemotron_h.layer_kinds(bad)


def test_block_keys_and_parameter_names(config):
    net = _net(config, 3)["net"]
    names = set(net.collect_params())
    assert {"layers.0.norm.gamma", "layers.0.attn.q.weight",
            "layers.1.moe.router_weight", "layers.1.moe.latent_down.weight",
            "layers.1.moe.latent_up.weight", "layers.1.moe.shared.up.weight",
            "layers.1.moe.shared.down.weight", "layers.2.mamba.a_log",
            "layers.2.mamba.norm.gamma", "mtp.proj.weight",
            "mtp.block.0.attn.o.weight", "mtp.block.1.moe.experts_in",
            "mtp.block.2.gamma", "norm.gamma", "head.weight"} <= names
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert shapes["layers.1.moe.experts_in"] == (4, 32, 24)    # ungated
    assert shapes["layers.1.moe.experts_out"] == (4, 24, 32)
    assert shapes["layers.1.moe.router_weight"] == (16, 64)    # full width


# ------------------------------- a fused step: recomputation with state

def test_a_fused_step_recomputes_layers_that_hold_a_routers_state(config):
    """Every layer is under ``recompute()``, the routed ones too: their
    bias and counters leave the checkpointed region and move, the program
    holds the instructions run again, and ``moe_plans`` and ``ssm_plans``
    say what the step's three signatures were cut into."""
    built = _net(config, 9)
    amp.convert_block(built["net"], "bfloat16")
    step = make_fused_train_step(built["net"], built["loss"],
                                 built["optimizer"],
                                 dict(built["optimizer_params"]))
    x, y = model.make_batch(9, 0, 2, config, TRAFFIC)
    moe_ops.moe_plans(reset=True)
    ssm_ops.ssm_plans(reset=True)
    text = str(jax.make_jaxpr(step.step_fn)(
        step.params, step.aux, step.opt_state, x, y, step._key))
    assert text.count("remat2[") >= 7       # five layers and the MTP's two
    assert moe_ops.moe_plans() == {
        "t42 k4 e4/16 w32 i24 relu2 bfloat16": {
            "tokens": 42, "top_k": 4, "n_experts": 16, "held": 4,
            "assignments": 168, "buffer_rows": moe_ops.buffer_rows(
                42, 4, 16, 4, 1.75), "tile": 128, "width": 32, "hidden": 24,
            "activation": "relu2"}}
    assert list(ssm_ops.ssm_plans()) == ["b2 t21 h8x8 g2 n16 bfloat16",
                                         "conv b2 t21 c128 k4 bfloat16"]
    assert ssm_ops.ssm_plans()["b2 t21 h8x8 g2 n16 bfloat16"][
        "route"] == "xla:shape"       # toy heads: the composition
    # the toy's convolution is one lane tile wide, which the pair takes
    # where there is a chip
    assert ssm_ops.ssm_plans()["conv b2 t21 c128 k4 bfloat16"][
        "route"] == "xla:no_tpu"
    assert "moe_plans" in profiler.provider_stats()
    losses = [float(step(x, y)) for _ in range(3)]
    assert losses[-1] < losses[0]
    counters = tl.moe_stats(step.aux)
    assert len(counters) == 3
    for c in counters.values():
        assert c["passes"] == 1 and c["overflow_steps"] == 0
        assert 0 < c["rows_held"] <= 168
    bias = [v for n, v in step.aux.items() if n.endswith("score_bias")]
    assert all(float(jnp.abs(b).max()) > 0 for b in bias)


# --------------------------------------- moe_ffn and RoutedFFN: relu2, latent

def _loop_over_experts(x, idx, gates, w_in, w_out, first, activation):
    y = jnp.zeros((x.shape[0], w_out.shape[-1]), F32)
    for e in range(w_in.shape[0]):
        weight = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
        h = x @ w_in[e]
        if activation == "relu2":
            act = jnp.square(jax.nn.relu(h))
        else:
            gate, up = jnp.split(h, 2, -1)
            act = jax.nn.silu(gate) * up
        y = y + weight[:, None] * (act @ w_out[e])
    return y


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["composition", "kernel"])
@pytest.mark.parametrize("activation", ["relu2", "swiglu"])
def test_moe_ffn_against_a_loop_over_experts(monkeypatch, pallas,
                                             activation):
    """300 tokens, top-3 of 8 experts of which 3 (ids 2-4) are held, width
    16, hidden 24: output and the gradients by the rows, the gates and both
    weight stacks, on both sides of the dispatch."""
    monkeypatch.setenv("MXNET_USE_PALLAS", pallas)
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    wide = 24 * (2 if activation == "swiglu" else 1)
    x = jax.random.normal(keys[0], (300, 16))
    idx = jnp.argsort(jax.random.uniform(keys[1], (300, 8)), -1)[:, :3] \
        .astype(jnp.int32)
    gates = jax.random.uniform(keys[2], (300, 3)) + 0.1
    w_in = 0.3 * jax.random.normal(keys[3], (3, 16, wide))
    w_out = 0.3 * jax.random.normal(keys[4], (3, 24, 16))
    cot = jax.random.normal(keys[5], (300, 16))
    system = lambda *a: jnp.sum(moe_ops.moe_ffn.fn(
        a[0], idx, a[1], a[2], a[3], n_experts=8, first=2,
        activation=activation)[0] * cot)
    loop = lambda *a: jnp.sum(_loop_over_experts(
        a[0], idx, a[1], a[2], a[3], 2, activation) * cot)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(system, (0, 1, 2, 3))(x, gates, w_in, w_out)
        want = jax.value_and_grad(loop, (0, 1, 2, 3))(x, gates, w_in, w_out)
    assert abs(got[0] - want[0]) < 1e-4 * abs(want[0]) + 1e-4
    for name, mine, theirs in zip(("x", "gates", "w_in", "w_out"), got[1],
                                  want[1]):
        assert _rel(mine, theirs) < 1e-4, name


def test_moe_ffn_refuses_an_activation_it_does_not_know():
    with pytest.raises(ValueError, match="neither 'swiglu' nor 'relu2'"):
        moe_ops.moe_ffn.fn(jnp.zeros((4, 8)), jnp.zeros((4, 1), jnp.int32),
                           jnp.ones((4, 1)), jnp.zeros((1, 8, 4)),
                           jnp.zeros((1, 4, 8)), activation="gelu")


def test_routed_ffns_defaults_are_the_layer_it_was():
    """No latent, SwiGLU experts and shared expert: the parameters, their
    shapes and order, and for a seed their values."""
    def layer(**more):
        mx.random.seed(21)
        block = nn.RoutedFFN(16, 8, 8, (2, 2), 2, 2.5, shared_hidden_size=8,
                             **more)
        block.initialize(mx.initializer.Normal(0.1))
        return block

    plain, spelled = layer(), layer(latent_size=0, activation="swiglu")
    params = plain.collect_params()
    assert list(params) == ["router_weight", "score_bias", "moe_stats",
                            "experts_in", "experts_out",
                            "shared.gate_up.weight", "shared.down.weight"]
    assert params["experts_in"].shape == (2, 16, 16)
    assert params["experts_out"].shape == (2, 8, 16)
    for name, param in spelled.collect_params().items():
        assert (param.data().asnumpy() == params[name].data().asnumpy()).all()
    latent = layer(activation="relu2", latent_size=4).collect_params()
    assert latent["experts_in"].shape == (2, 4, 8)
    assert "shared.up.weight" in latent and "latent_up.weight" in latent


def _latent_layer(tokens=200, hidden=16, latent=8, width=12, experts=16):
    keys = jax.random.split(jax.random.PRNGKey(1), 9)
    draw = lambda i, *shape: 0.3 * jax.random.normal(keys[i], shape)
    p = {"moe.router_weight": draw(0, experts, hidden),
         "moe.score_bias": 0.01 * jax.random.normal(keys[1], (experts,)),
         "moe.latent_down.weight": draw(2, latent, hidden),
         "moe.latent_up.weight": draw(3, hidden, latent),
         "moe.experts_in": draw(4, experts, latent, width),
         "moe.experts_out": draw(5, experts, width, latent),
         "moe.shared.up.weight": draw(6, 2 * width, hidden),
         "moe.shared.down.weight": draw(7, hidden, 2 * width)}
    cfg = {"num_experts_per_tok": 4, "routed_scaling_factor": 5.0,
           "router_outputs": experts, "held_experts": [0, experts]}
    return p, cfg, jax.random.normal(keys[8], (tokens, hidden))


def _routed_layer(p, first, count, shared=True):
    """``nn.RoutedFFN`` holding experts ``first`` … with ``p``'s weights."""
    layer = nn.RoutedFFN(16, 12, 16, (first, count), 4, 5.0,
                         capacity_factor=1.75, shared_hidden_size=24,
                         latent_size=8, activation="relu2")
    layer.initialize()
    for name, param in layer.collect_params().items():
        if name == "moe_stats":
            continue
        value = p["moe." + name]
        if name.startswith("experts_"):
            value = value[first:first + count]
        param.set_data(mx.nd.array(onp.asarray(value)))
    if not shared:
        layer.shared.down.weight.set_data(mx.nd.zeros((16, 24)))
    return layer


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["composition", "kernel"])
def test_the_shares_add_up_to_the_uncut_layer(monkeypatch, pallas):
    """Four chips with four experts each: their routed parts, each through
    ``W_up``, plus the shared expert counted once, are the uncut
    reference's layer output — the up-projection is linear, so it may be
    applied to a chip's partial sum."""
    monkeypatch.setenv("MXNET_USE_PALLAS", pallas)
    p, cfg, x = _latent_layer()
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.latent_moe(p, x, cfg, jnp.matmul)
        parts = [_routed_layer(p, first, 4, shared=False)(NDArray(x)).data
                 for first in (0, 4, 8, 12)]
        shared = ref.relu2_mlp(x, p["moe.shared.up.weight"].T,
                               p["moe.shared.down.weight"].T, jnp.matmul)
        onp.testing.assert_allclose(sum(parts) + shared, whole, atol=5e-4,
                                    rtol=1e-4)
        # and one share, shared expert and all, is the reference's share
        held = {**p, "moe.experts_in": p["moe.experts_in"][4:8],
                "moe.experts_out": p["moe.experts_out"][4:8]}
        mine, _ = ref.latent_moe(held, x, cfg, jnp.matmul, held=(4, 4))
        onp.testing.assert_allclose(
            _routed_layer(p, 4, 4)(NDArray(x)).data, mine, atol=5e-4,
            rtol=1e-4)


# ----------------------------------- the scan and attention at their shapes

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


def test_ssd_scan_at_eight_heads_of_64_in_four_groups():
    """The new shape's proportions (more, narrower heads; more groups; a
    state as wide as the chunk) against the recurrence a position at a
    time, forward and every gradient; 40 positions in chunks of 16."""
    rng = onp.random.default_rng(0)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), F32)
    args = (draw(2, 40, 8, 64), jax.nn.softplus(draw(2, 40, 8)) * 0.1,
            -jnp.asarray(rng.uniform(1, 16, 8), F32), draw(2, 40, 4, 16),
            draw(2, 40, 4, 16), draw(8))
    weigh = draw(2, 40, 8, 64)
    chunked = functools.partial(ssm_ops.ssd_scan.fn, chunk=16)
    ssm_ops.ssm_plans(reset=True)
    got, want = (jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * weigh), argnums=range(6))(*args)
        for fn in (chunked, _recurrence))
    assert list(ssm_ops.ssm_plans()) == ["b2 t40 h8x64 g4 n16 float32"]
    # heads of 64 share a lane tile, but a state of 16 and chunks of 16 are
    # none: the composition, all 8 heads a step
    plan = ssm_ops.ssm_plans()["b2 t40 h8x64 g4 n16 float32"]
    assert (plan["route"], plan["heads_a_step"]) == ("xla:shape", 8)
    assert _rel(chunked(*args), _recurrence(*args)) < 2e-6
    assert abs(got[0] - want[0]) < 2e-5 * abs(want[0]) + 1e-4
    for name, mine, theirs in zip("x dt A B C D".split(), got[1], want[1]):
        assert _rel(mine, theirs) < 1e-4, name


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["composition", "kernel"])
def test_attention_without_rotary_at_16_query_heads_a_key_head(monkeypatch,
                                                               pallas):
    """``GroupedQueryAttention(rope_theta=None)`` over 32 query and 2 key
    heads is the reference's attention; with a theta it is not, and
    Falcon-H1's default still turns."""
    monkeypatch.setenv("MXNET_USE_PALLAS", pallas)
    cfg = {"num_attention_heads": 32, "num_key_value_heads": 2,
           "head_dim": 8}
    mx.random.seed(2)
    layer = nn.GroupedQueryAttention(64, 32, 2, 8, rope_theta=None)
    layer.initialize(mx.initializer.Normal(0.2))
    params, apply = layer.functional()
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), F32)
    weigh = jax.random.normal(jax.random.PRNGKey(4), u.shape, F32)
    named = lambda p: {"attn." + n: v for n, v in p.items()}
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(lambda p, u: jnp.sum(
            apply(p, u) * weigh), (0, 1))(params, u)
        want = jax.value_and_grad(lambda p, u: jnp.sum(jax.vmap(
            lambda s: ref.attention(named(p), s, cfg, jnp.matmul))(u)
            * weigh), (0, 1))(params, u)
        turned = nn.GroupedQueryAttention(64, 32, 2, 8, rope_theta=1e4)
        turned.initialize()
        out = turned.functional()[1](params, u)
    assert _rel(apply(params, u), jax.vmap(lambda s: ref.attention(
        named(params), s, cfg, jnp.matmul))(u)) < 1e-5
    assert _rel(got[1][1], want[1][1]) < 1e-4
    for name in params:
        assert _rel(got[1][0][name], want[1][0][name]) < 1e-4, name
    assert _rel(out, apply(params, u)) > 0.05


# ------------------------------------------------------ the configuration

def test_the_configuration_keeps_every_published_width(real):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [json.loads(line) for line in f
                  if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line]
    published = row["config"]
    differ = {k for k, v in published.items() if real.get(k) != v}
    assert differ == {"num_hidden_layers", "hybrid_override_pattern",
                      "n_routed_experts", "vocab_size"}
    assert sorted(real["reduced"]) == sorted(differ)
    assert real["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072,
        "hybrid_override_pattern": published["hybrid_override_pattern"]}
    # one whole period: layers 25-35 of the published pattern
    assert real["hybrid_override_pattern"] == \
        published["hybrid_override_pattern"][25:36] == "*EMEMEMEMEM"
    assert (real["num_hidden_layers"], real["n_routed_experts"],
            real["vocab_size"]) == (11, 16, 131072 // 8)
    assert (real["router_outputs"], real["held_experts"]) == (512, [0, 16])
    assert real["recompute"] == "layers"
    assert {"rotary", "latent_moe", "mtp_loss_weight", "bias_update_gamma",
            "initialisation", "level_routers", "optimizer_params", "dtype",
            "recompute"} <= set(real["assumed"])
    assert set(real) >= {"published", "deployment", "cut", "assumed",
                         "departures"}
    manifest = _load(os.path.join(REPO, "BENCHMARK.json"))
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == "nemotron3_super_120b"]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == real["reduced"]
    assert len(real["source"]) <= 200


def test_the_cells_traffic_is_the_issues_to_the_number():
    cell = _load(CELL)
    assert cell["traffic"] == {"batch": 1, "seq_len": 4096, "successors": 4,
                               "pool": 4, "queue_depth": 8,
                               "warmup_steps": 5, "trace_steps": 10}
    assert cell["runner"] == "train_vs_blockwise_reference_moe"
    assert cell["chips"] == 1
    limits = cell["reference"]
    assert limits["lower_precision_probe"] == "float8_e4m3fn"
    assert set(limits["reasons"]) == set(limits) - {"reasons",
                                                    "lower_precision_probe"}
    assert 0.0 < limits["update"] <= 0.6    # a state left unchanged reads 1
    manifest = _load(os.path.join(REPO, "BENCHMARK.json"))
    (entry,) = [w for w in manifest["workloads"]
                if w["name"] == cell["name"]]
    assert (entry["config"], entry["chips"]) == ("nemotron3_super_120b", 1)


def test_the_built_net_counts_the_formulas_parameters(real):
    """``jax.eval_shape`` over ``build``: nothing is allocated."""
    per = model.layer_params(real)
    assert per == {"M": 109_640_064, "*": 35_655_680,
                   "E": (54_530_560, 5_505_024)}
    assert model.total_params(real) == 1_642_965_888
    whole = dict(real, hybrid_override_pattern=real["published"][
        "hybrid_override_pattern"], held_experts=[0, 512],
        vocab_size=131072, num_nextn_predict_layers=0)
    assert round(model.total_params(whole) / 1e9, 2) == 120.67

    def shapes():
        net = model.build(0, real)["net"]
        return {n: p.data().data for n, p in net.collect_params().items()}

    built = jax.eval_shape(shapes)
    counted = sum(v.size for n, v in built.items()
                  if not n.endswith("moe_stats"))
    assert counted == model.total_params(real)
    assert model.kinds(real) == {"M": 5, "E": 6, "*": 2}


def test_flops_and_the_three_kernels_work_on_hand_computed_values(real):
    traffic = _load(CELL)["traffic"]
    assert model.held_share(real) == 22 * 16 / 512
    # a token: attention's four projections, the mixer's two, a routed
    # layer's router, latent pair, shared expert and 0.6875 experts
    attention = 2 * 4096 * 4096 + 2 * 4096 * 256
    mixer = 4096 * 18560 + 8192 * 4096
    routed = (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
              + 0.6875 * 2 * 1024 * 2688)
    assert model.matmul_params(real) == (
        2 * attention + 5 * mixer + 6 * routed + 2 * 4096 * 4096
        + 2 * 4096 * 16384)
    per_step = model.flops_per_sample(real, traffic)
    assert per_step == pytest.approx(29.093e12, rel=1e-4)
    # the scan, forward a token a layer: C·Bᵀ 8 groups x 128 x 128 and
    # (L ⊙ CBᵀ)(Δx) 128 heads x 128 x 64 at half the chunk's square, own
    # state and carried output 128 heads x 2 x 2 x 64 x 128; three passes
    ops, moved = model.ssm_scan_work(real, traffic)
    assert ops == 5 * 4096 * 3 * (8 * 128 * 128 + 128 * 128 * 64
                                  + 128 * 4 * 64 * 128)
    # bfloat16 x, B, C (10,240 wide) three times, y twice; float32 Δ thrice
    assert moved == 5 * 4096 * (2 * (3 * 10240 + 2 * 8192) + 4 * 3 * 128)
    assert ops / 197e12 < moved / 819e9         # bound by bytes here
    # attention: 2 layers x 4096 tokens x 32 heads x 3 x (128 + 128) x 4096
    ops, moved = model.gqa_attention_work(real, traffic)
    assert ops == 2 * 4096 * 32 * 3 * 256 * 4096
    assert moved == 2 * 2 * 4096 * 128 * 6 * (32 + 2)
    # the experts: 2,816 rows a layer through two 1,024 x 2,688 products
    ops, moved = model.latent_moe_experts_work(real, traffic)
    assert ops == 6 * 6 * 5_505_024 * 2816
    assert moved == 6 * 2 * (3 * 16 * 5_505_024 + 4 * 2816 * 1024)
    assert ops / 197e12 < moved / 819e9         # bound by bytes


def test_the_first_loss_expected_of_gaussian_logits(real, config):
    import math
    assert model.uniform_loss(real) == pytest.approx(
        1.3 * (math.log(16384) + 0.02 ** 2 * 4096 / 2))
    assert model.uniform_loss(config) == pytest.approx(
        1.3 * (math.log(96) + 0.05 ** 2 * 64 / 2))
