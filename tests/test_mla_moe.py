"""The latent-attention / routed-experts decoder against its plain
reference at a small size on the CPU (hidden 64, 8 experts of which 2 are
held, top-2, 1 dense + 2 routed layers and the MTP module, 96 of 768 ids),
and the routed layer's own promises: dropless for any routing, a share of
the experts that adds up to the uncut layer, and a step whose shapes no seed
and no routing can change.  The reference is the benchmark's copy
(``chipbench/configs/joyai_llm_flash_ref.py``), which shares no code with
the package."""
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
from incubator_mxnet_tpu.ndarray import NDArray  # noqa: E402
from incubator_mxnet_tpu.ops import moe_ops, nn_ops  # noqa: E402
from incubator_mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402
from chipbench.configs import joyai_llm_flash as model  # noqa: E402
from chipbench.configs import joyai_llm_flash_ref as ref  # noqa: E402

TOY = os.path.join(REPO, "tests", "chipbench", "toy_joyai", "cells",
                   "configs", "toy_joyai.json")
TRAFFIC = {"batch": 2, "seq_len": 32, "successors": 4}


@pytest.fixture(scope="module")
def config():
    with open(TOY) as f:
        return json.load(f)


def _net(config, seed):
    mx.random.seed(seed)
    return model.build(seed, config)


@pytest.fixture(scope="module")
def both(config):
    """One float32 training step's worth of the system (loss, both heads'
    logits, every gradient, the aux updates) and the reference's."""
    built = _net(config, 3)
    params, apply = built["net"].functional()
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x, y = (jnp.asarray(a) for a in model.make_batch(3, 0, 2, config,
                                                     TRAFFIC))

    def loss_of(p):
        (main, mtp), updates = apply(p, x, training=True, with_updates=True)
        loss = built["loss"](NDArray(main), NDArray(mtp), NDArray(y))
        return jnp.mean(loss.data), (main, mtp, updates)

    with jax.default_matmul_precision("highest"):
        system = jax.value_and_grad(loss_of, has_aux=True)(params)
    return params, system, ref.loss_and_grads(params, x, y, config)


def test_loss_and_both_heads_logits_agree_with_the_reference(both):
    _, ((loss, (main, mtp, _)), _), ((ref_loss, (ref_main, ref_mtp, _)), _) \
        = both
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert main.shape == mtp.shape == (2, 32, 96)
    onp.testing.assert_allclose(main, ref_main, atol=2e-6)
    onp.testing.assert_allclose(mtp, ref_mtp, atol=2e-6)


def test_every_gradient_agrees_with_the_reference(both):
    params, (_, grads), (_, ref_grads) = both
    compared = 0
    for name in params:
        if name.endswith(("score_bias", "moe_stats")):
            assert not onp.any(onp.asarray(grads[name]))
            continue
        scale = float(jnp.max(jnp.abs(ref_grads[name])))
        assert scale > 0, name
        onp.testing.assert_allclose(grads[name], ref_grads[name],
                                    atol=2e-5 * scale, err_msg=name)
        compared += 1
    assert compared == 59


def test_bias_update_and_counters_after_one_step(both, config):
    params, ((_, (_, _, updates)), _), ((_, (_, _, routing)), _) = both
    assert sorted(routing) == ["layers.1.ffn", "layers.2.ffn",
                               "mtp.block.ffn"]
    for name, did in routing.items():
        after = ref.bias_after_step(params[name + ".score_bias"],
                                    did["load"], config)
        onp.testing.assert_allclose(updates[name + ".score_bias"], after,
                                    atol=1e-7)
        assert {round(float(v), 6) for v in onp.abs(after)} <= {0.0, 0.001}
        first, count = config["held_experts"]
        held = float(jnp.sum(did["load"][first:first + count]))
        stats = dict(zip(tl.STATS, onp.asarray(updates[name + ".moe_stats"])))
        assert stats["rows_held"] == held
        assert stats["buffer_rows"] == 384 and stats["passes"] == 1
        assert stats["overflow_steps"] == 0


def test_the_fused_step_trains_in_bfloat16_with_one_compile(config):
    built = _net(config, 5)
    amp.convert_block(built["net"], "bfloat16")
    dtypes = {n: str(p.dtype) for n, p in
              built["net"].collect_params().items()}
    assert dtypes["layers.1.ffn.experts_in"] == "bfloat16"
    assert dtypes["layers.1.ffn.score_bias"] == "float32"
    assert dtypes["layers.1.ffn.moe_stats"] == "float32"
    step = make_fused_train_step(built["net"], built["loss"],
                                 built["optimizer"],
                                 dict(built["optimizer_params"]))
    pool = [model.make_batch(5, i, 2, config, TRAFFIC) for i in range(2)]
    losses = [float(step(*pool[i % 2])) for i in range(30)]
    assert abs(losses[0] - model.uniform_loss(config)) < 0.5
    assert losses[-1] < 0.5 * losses[0]
    assert step._executor.compile_count == 1
    # the counters travel as aux leaves and reach the stats provider
    stats = tl.moe_stats(step.aux)
    assert sorted(stats) == ["layers.1.ffn.moe_stats",
                             "layers.2.ffn.moe_stats",
                             "mtp.block.ffn.moe_stats"]
    assert all(s["overflow_steps"] == 0 and s["rows_held"] > 0
               for s in stats.values())
    assert any(onp.any(onp.asarray(v)) for n, v in step.aux.items()
               if n.endswith("score_bias"))
    step.write_back()
    live = profiler.provider_stats()["moe"]
    assert {tuple(s.values()) for s in stats.values()} <= \
        {tuple(s.values()) for s in live.values()}


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
    return str(jaxpr), shapes


def test_the_steps_program_is_the_same_for_two_seeds_and_two_routings(
        config):
    """Every shape, grid and trip count of the step is the configuration's
    and the traffic's: two seeds (other weights, other tokens, so another
    routing) trace to the same program, equation for equation."""
    (text_a, shapes_a), (text_b, shapes_b) = (_step_jaxpr(config, s)
                                              for s in (11, 12))
    assert shapes_a == shapes_b
    assert text_a == text_b
    # the buffer is the configuration's: 384 rows in every routed layer
    assert "[384,64]" in text_a


def test_both_attention_kernels_of_the_step_sit_under_flash_attention(
        config, monkeypatch):
    """``mla_attention_roofline_pct`` sums the device time of the
    instructions whose ``op_name`` holds the segment ``flash_attention``:
    both ``pallas_call``s of every attention of the toy decoder's step
    (1 dense + 2 routed layers and the MTP block: 4 each way) carry it,
    under the names the trace's kernel table reads."""
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")     # interpreted kernels
    built = _net(config, 13)
    amp.convert_block(built["net"], "bfloat16")
    step = make_fused_train_step(built["net"], built["loss"],
                                 built["optimizer"],
                                 dict(built["optimizer_params"]))
    # shapes of its own: an op's jit keeps the route it was first traced with
    x, y = model.make_batch(13, 0, 3, config, TRAFFIC)
    pk.kernel_routes(reset=True)
    pk.attention_plans(reset=True)
    jaxpr = jax.make_jaxpr(step.step_fn)(step.params, step.aux,
                                         step.opt_state, x, y, step._key)

    def calls(jaxpr, prefix=""):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"], stack.split("/")
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub, stack)

    pair = [(name, stack) for name, stack in calls(jaxpr.jaxpr)
            if name.startswith("flash_attention")]
    assert sorted(name for name, _ in pair) == \
        ["flash_attention_bwd"] * 4 + ["flash_attention_fwd"] * 4
    assert all("flash_attention" in stack for _, stack in pair)
    assert pk.kernel_routes()["flash_attention"] == {"kernel": 1}
    # one signature, traced once: 32 positions are one block each way
    (plan,) = pk.attention_plans().values()
    assert (plan["pairs"], plan["live_pairs"]) == (1, 1)


# ---------------------------------------------------- the routed layer alone

def _layer_inputs(tokens=200, hidden=16, width=8, experts=8):
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    p = {"ffn.router_weight": 0.3 * jax.random.normal(keys[1],
                                                      (experts, hidden)),
         "ffn.score_bias": 0.01 * jax.random.normal(keys[2], (experts,)),
         "ffn.experts_in": 0.3 * jax.random.normal(
             keys[3], (experts, hidden, 2 * width)),
         "ffn.experts_out": 0.3 * jax.random.normal(
             keys[4], (experts, width, hidden)),
         "ffn.shared.gate_up.weight": 0.3 * jax.random.normal(
             keys[5], (2 * width, hidden)),
         "ffn.shared.down.weight": 0.3 * jax.random.normal(
             keys[0], (hidden, width))}
    cfg = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
           "router_outputs": experts, "held_experts": [0, experts]}
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, hidden))
    return p, cfg, x


def _share(p, x, first, count, idx=None, factor=1.5):
    """What the chip holding experts ``first`` … gives: the ops, as the
    Gluon layer calls them."""
    own_idx, gates, _, _ = moe_ops.moe_route.fn(
        x, p["ffn.router_weight"], p["ffn.score_bias"], top_k=2, scale=2.5)
    if idx is not None:
        s = jax.nn.sigmoid(x @ p["ffn.router_weight"].T)
        chosen = jnp.take_along_axis(s, idx, -1)
        gates = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    return moe_ops.moe_ffn.fn(
        x, own_idx if idx is None else idx, gates,
        p["ffn.experts_in"][first:first + count],
        p["ffn.experts_out"][first:first + count], n_experts=8, first=first,
        capacity_factor=factor)


@pytest.mark.parametrize("pallas", ["0", "1"])
def test_the_shares_add_up_to_the_uncut_layer(monkeypatch, pallas):
    """Four chips with two experts each: their routed parts, plus the
    shared expert once, are the uncut reference's layer output."""
    monkeypatch.setenv("MXNET_USE_PALLAS", pallas)
    p, cfg, x = _layer_inputs()
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.routed_ffn(p, "ffn", x, cfg, jnp.matmul, shared=True)
        parts = [_share(p, x, first, 2)[0] for first in (0, 2, 4, 6)]
        shared = ref.swiglu(x, p["ffn.shared.gate_up.weight"].T,
                            p["ffn.shared.down.weight"].T, jnp.matmul)
    onp.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    # and one share is the reference's share
    mine, _ = ref.routed_ffn(
        {**p, "ffn.experts_in": p["ffn.experts_in"][2:4],
         "ffn.experts_out": p["ffn.experts_out"][2:4]}, "ffn", x, cfg,
        jnp.matmul, held=(2, 2), shared=False)
    onp.testing.assert_allclose(parts[1], mine, atol=2e-5)


@pytest.mark.parametrize("pallas", ["0", "1"])
@pytest.mark.parametrize("factor,passes", [(1.5, 1), (0.01, 2)])
def test_every_token_to_one_held_expert_drops_nothing(monkeypatch, pallas,
                                                      factor, passes):
    """All 200 tokens choose experts 3 and 2, both held: 400 rows.  With a
    buffer that holds them one pass computes them; with one of 384 rows a
    second pass computes the rest, and the counters say so.  Output and
    every gradient are the dense reference's either way."""
    monkeypatch.setenv("MXNET_USE_PALLAS", pallas)
    p, cfg, x = _layer_inputs()
    idx = jnp.tile(jnp.array([[3, 2]], jnp.int32), (200, 1))
    held = {**p, "ffn.experts_in": p["ffn.experts_in"][2:4],
            "ffn.experts_out": p["ffn.experts_out"][2:4]}
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def system(x, w_in, w_out, router):
        q = {**p, "ffn.experts_in": w_in, "ffn.experts_out": w_out,
             "ffn.router_weight": router}
        return jnp.sum(_share(q, x, 2, 2, idx, factor)[0] * cot)

    def reference(x, w_in, w_out, router):
        q = {**held, "ffn.experts_in": w_in[2:4],
             "ffn.experts_out": w_out[2:4], "ffn.router_weight": router}
        y, _ = ref.routed_ffn(q, "ffn", x, cfg, jnp.matmul, held=(2, 2),
                              forced=idx, margin=1e9, shared=False)
        return jnp.sum(y * cot)

    args = (x, p["ffn.experts_in"], p["ffn.experts_out"],
            p["ffn.router_weight"])
    with jax.default_matmul_precision("highest"):
        y, stats = _share(p, x, 2, 2, idx, factor)
        want, _ = ref.routed_ffn(held, "ffn", x, cfg, jnp.matmul,
                                 held=(2, 2), forced=idx, margin=1e9,
                                 shared=False)
        got = jax.grad(system, (0, 1, 2, 3))(*args)
        exp = jax.grad(reference, (0, 1, 2, 3))(*args)
    onp.testing.assert_allclose(y, want, atol=2e-5)
    assert list(onp.asarray(stats)) == [
        400.0, moe_ops.buffer_rows(200, 2, 8, 2, factor), passes, 4.0]
    for g, e in zip(got, exp):
        onp.testing.assert_allclose(g, e, atol=2e-5 * float(jnp.max(
            jnp.abs(e))))


def test_buffer_rows_is_the_configurations():
    # the benchmark's cell: ceil(1.5 x 8192 x 8 x 16/256) + 16 x 128
    assert moe_ops.buffer_rows(8192, 8, 256, 16, 1.5) == 8192
    assert moe_ops.buffer_rows(64, 2, 8, 2, 1.5) == 384


def test_routed_layer_names_its_scopes_and_counts_its_routes():
    pk.kernel_routes(reset=True)
    p, _, x = _layer_inputs(tokens=64)
    text = str(jax.make_jaxpr(lambda x: _share(p, x, 2, 2)[0])(x))
    routes = pk.kernel_routes()
    assert routes["moe_route"] == {"xla:no_kernel": 1}
    # two grouped matmuls in the pass every call takes, two in the body of
    # the loop over further passes
    assert routes["moe_experts"] == {"xla:no_tpu": 4}
    lowered = jax.jit(lambda x: jax.grad(
        lambda x: jnp.sum(_share(p, x, 2, 2)[0]))(x)).lower(x)
    names = lowered.as_text(debug_info=True)
    for scope in ("moe_route", "moe_dispatch", "moe_experts"):
        assert scope in names, scope
    assert "while" in text                 # the loop that runs only then


# ------------------------------------------------ attention and the rotary

def test_flash_attention_with_a_narrower_v_against_the_composition(
        monkeypatch):
    """q and k 48 wide, v 32, causal, two key blocks (the online rescale):
    the interpreted kernel pair against the XLA composition, forward and
    backward, through the op that routes between them."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(keys[i], (1, 2, 1024, 48)) for i in (0, 1))
    v, cot = (jax.random.normal(keys[i], (1, 2, 1024, 32)) for i in (2, 3))

    def run(flag):
        monkeypatch.setenv("MXNET_USE_PALLAS", flag)
        pk.kernel_routes(reset=True)
        f = lambda q, k, v: jnp.sum(nn_ops.dot_product_attention.fn(
            q, k, v, causal=True) * cot)
        out = nn_ops.dot_product_attention.fn(q, k, v, causal=True)
        return out, jax.grad(f, (0, 1, 2))(q, k, v), pk.kernel_routes()

    with jax.default_matmul_precision("highest"):
        out, grads, routes = run("1")
        want, want_grads, _ = run("0")
    assert routes["flash_attention"] == {"kernel": 2}
    assert out.shape == (1, 2, 1024, 32)
    onp.testing.assert_allclose(out, want, atol=2e-5)
    for g, w in zip(grads, want_grads):
        assert g.shape == w.shape
        onp.testing.assert_allclose(g, w, atol=5e-5)


def test_rotary_against_complex_multiplication():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 3, 8))
    theta = 32e6
    pairs = onp.asarray(x, onp.float64).reshape(2, 9, 3, 4, 2)
    angle = onp.arange(9)[:, None] * theta ** (-onp.arange(0, 8, 2) / 8)
    turned = (pairs[..., 0] + 1j * pairs[..., 1]) \
        * onp.exp(1j * angle)[None, :, None, :]
    want = onp.stack([turned.real, turned.imag], -1).reshape(x.shape)
    onp.testing.assert_allclose(moe_ops.rope.fn(x, theta=theta), want,
                                atol=1e-5)
    # the reference's own rotary, one sequence at a time
    onp.testing.assert_allclose(ref.rotary(x[0], theta), want[0], atol=1e-5)
    # bfloat16 in, bfloat16 out, the angles still float32
    low = moe_ops.rope.fn(x.astype(jnp.bfloat16), theta=theta)
    assert low.dtype == jnp.bfloat16
    onp.testing.assert_allclose(low.astype(jnp.float32), want, atol=0.05)


def test_weighted_heads_loss_is_the_sum_of_the_heads(config):
    from incubator_mxnet_tpu import gluon
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    main, mtp = (jax.random.normal(keys[i], (2, 6, 11)) for i in (0, 1))
    labels = jax.random.randint(keys[2], (2, 2, 6), 0, 11)
    loss = gluon.loss.WeightedHeadsSoftmaxCELoss((1.0, 0.3))(
        NDArray(main), NDArray(mtp), NDArray(labels))
    want = ref.cross_entropy(main, labels[:, 0]) \
        + 0.3 * ref.cross_entropy(mtp, labels[:, 1])
    assert loss.shape == (2,)
    assert float(jnp.mean(loss.data)) == pytest.approx(float(want), rel=1e-6)


def test_a_one_output_net_and_a_tuple_output_keep_their_loss():
    """``fuse`` hands a loss every output only where the loss asks for
    them: BERT's tuple still means its first element."""
    from incubator_mxnet_tpu import gluon

    class Two(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.a, self.b = nn.Dense(3, in_units=4), nn.Dense(5, in_units=4)

        def forward(self, x):
            return self.a(x), self.b(x)

    mx.random.seed(1)
    net = Two()
    net.initialize()
    step = make_fused_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "sgd", {"learning_rate": 0.1})
    x = onp.random.default_rng(0).normal(size=(6, 4)).astype("float32")
    y = onp.arange(6) % 3
    first = float(step(x, y))
    assert first == pytest.approx(float(jnp.mean(
        gluon.loss.SoftmaxCrossEntropyLoss()(net.a(NDArray(x)),
                                             NDArray(y)).data)), rel=1e-5)
    assert float(step(x, y)) < first
