"""The names the fused train step gives its instructions (CPU, no compile).

A profile of the step attributes device time by each instruction's
``op_name``: ``jit(step)/<phase>/<block keys>/jit(<op>)/<kernel>/<primitive>``
(docs/observability.md).  Held to here, on the toy ResNet and toy BERT of
tests/chipbench/toy/, with and without a ``dp`` mesh: nearly every equation
carries a phase, every convolution and matmul a block path made of the keys
the blocks are registered under, two nets built in one process give the same
paths, the kernels' scopes and every ``pl.pallas_call`` are named.  The
grammar is parsed by the benchmark's own reader, ``chipbench/scope_reduce``.
"""
import ast
import collections
import functools
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
from chipbench import run as harness, scope_reduce as sr  # noqa: E402
from incubator_mxnet_tpu import amp  # noqa: E402
from incubator_mxnet_tpu.fuse import make_fused_train_step  # noqa: E402
from incubator_mxnet_tpu.ops import (  # noqa: E402
    fused_block, fused_conv, pallas_kernels as pk)

TOY = os.path.join(REPO, "tests", "chipbench", "toy", "cells", "configs")
CALLS = ("jaxpr", "call_jaxpr", "fun_jaxpr")    # an equation's inner program


def _leaves(jaxpr, prefix=()):
    """``(primitive, name-stack segments)`` of every equation that is no
    call, through the jitted ops and custom-vjp calls of the step; a call's
    equations stand below its own stack and its ``jit(<name>)``."""
    for eqn in jaxpr.eqns:
        stack = prefix + tuple(
            s for s in str(eqn.source_info.name_stack).split("/") if s)
        inner = next((eqn.params[k] for k in CALLS if k in eqn.params), None)
        if inner is None or eqn.primitive.name == "pallas_call":
            yield eqn.primitive.name, stack
            continue
        if eqn.primitive.name in ("jit", "pjit"):
            stack += (f"jit({eqn.params['name']})",)
        yield from _leaves(getattr(inner, "jaxpr", inner), stack)


def _step(name, mesh, batch=4, seq_len=16):
    config = harness.load_json(os.path.join(TOY, name + ".json"))
    model = harness.load_module(os.path.join(TOY, name + ".py"))
    mx.random.seed(7)
    built = model.build(7, config)
    amp.convert_block(built["net"], config["dtype"])
    kwargs = {}
    if mesh:
        from jax.sharding import Mesh
        kwargs["mesh"] = Mesh(onp.array(jax.devices()[:4]), ("dp",))
    step = make_fused_train_step(built["net"], built["loss"],
                                 built["optimizer"],
                                 dict(built["optimizer_params"]), **kwargs)
    x, y = model.make_batch(7, 0, batch, config, {"seq_len": seq_len})
    jaxpr = jax.make_jaxpr(step.step_fn)(
        step.params, step.aux, step.opt_state, jnp.asarray(x),
        jnp.asarray(y), step._key)
    return built["net"], [(prim, "/".join(("jit(step)",) + stack + (prim,)))
                          for prim, stack in _leaves(jaxpr.jaxpr)]


def _key_paths(block, prefix=()):
    """Every path of registration keys from the root block down."""
    yield prefix
    for key, child in block._children.items():
        yield from _key_paths(child, prefix + (key,))


@functools.lru_cache(maxsize=None)
def _traced(name, mesh):
    return _step(name, mesh)


CASES = [("toy_resnet", False), ("toy_resnet", True),
         ("toy_bert", False), ("toy_bert", True)]


@pytest.mark.parametrize("name,mesh", CASES)
def test_nearly_every_equation_carries_a_phase(name, mesh):
    _, eqns = _traced(name, mesh)
    phases = collections.Counter(sr.parse(op).phase for _, op in eqns)
    assert len(eqns) > 300
    assert phases[None] <= 0.01 * len(eqns), phases
    # the three phases are told apart with no scope for the backward pass
    assert min(phases["forward"], phases["backward"],
               phases["optimizer"]) > 20, phases


@pytest.mark.parametrize("name,mesh", CASES)
def test_every_conv_and_dot_carries_a_path_of_registration_keys(name, mesh):
    net, eqns = _traced(name, mesh)
    known = set(_key_paths(net))
    heavy = [op for prim, op in eqns
             if prim in ("conv_general_dilated", "dot_general")]
    assert len(heavy) >= 9
    for op in heavy:
        parsed = sr.parse(op)
        assert parsed.phase in ("forward", "backward"), op
        assert parsed.blocks and parsed.blocks in known, op
    # the root contributes no segment, and no path holds a counter-made name
    assert not [op for _, op in eqns if re.search(r"resnetv1|bertmodel", op)]


@pytest.mark.parametrize("name", ["toy_resnet", "toy_bert"])
def test_two_nets_built_in_one_process_give_identical_paths(name):
    first = _traced(name, False)
    second = _step(name, False)
    assert first[0].name != second[0].name or first[0].prefix == ""
    assert sorted(first[1]) == sorted(second[1])


def test_the_loss_has_one_kernel_name_on_both_sides_of_the_dispatch():
    """Under a mesh the XLA composition stands in for the kernel
    (``gspmd_trace``): it is found under the kernel's name all the same."""
    for mesh in (False, True):
        _, eqns = _traced("toy_resnet", mesh)
        loss = collections.Counter(
            (p.phase, p.kernel) for p in (sr.parse(op) for _, op in eqns)
            if p.blocks[:1] == ("loss",) and p.kernel)
        assert loss[("forward", "softmax_xent")] > 3
        assert loss[("backward", "softmax_xent")] > 3


def test_kernel_scopes_and_pallas_names_in_a_traced_step(monkeypatch):
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")     # interpreted kernels
    pk.kernel_routes(reset=True)
    # shapes of its own: an op's jit keeps the route it was first traced with
    _, eqns = _step("toy_bert", False, batch=3, seq_len=8)
    calls = collections.Counter()
    for prim, op in eqns:
        if prim == "pallas_call":
            parsed = sr.parse(op)
            calls[(parsed.phase, parsed.kernel)] += 1
            assert parsed.call == parsed.kernel + (
                "_fwd" if parsed.phase == "forward" else "_bwd"), op
            assert parsed.blocks[-1] in ("embed_ln", "ln1", "ln2",
                                         "attention"), op
    # the 5 LayerNorms and the 2 layers' attention, forward and backward;
    # the loss over (batch, tokens, vocabulary) logits is log_softmax + pick
    assert calls == {("forward", "layer_norm"): 5,
                     ("backward", "layer_norm"): 5,
                     ("forward", "flash_attention"): 2,
                     ("backward", "flash_attention"): 2}
    # each op's body was traced once for its one signature
    assert pk.kernel_routes()["flash_attention"] == {"kernel": 1}


def test_kernel_name_comes_from_the_wrapper():
    assert pk.kernel_name(pk.fused_layer_norm) == "layer_norm"
    assert pk.kernel_name(functools.partial(
        pk.fused_softmax, axis=-1)) == "softmax"
    assert pk.kernel_name(pk.fused_softmax_xent) == "softmax_xent"
    assert pk.kernel_name(lambda x: x) == "lambda"
    # the reader's vocabulary is the program's
    made = {pk.kernel_name(f) for f in (
        pk.fused_layer_norm, pk.fused_rms_norm, pk.fused_softmax,
        pk.fused_softmax_xent, pk.flash_attention)}
    assert made | {"matmul_bn", "conv3_bn"} == set(sr.KERNELS)


def test_every_pallas_call_site_has_a_name_of_its_own():
    sites, names = 0, []
    for module in (pk, fused_block, fused_conv):
        with open(module.__file__) as f:
            text = f.read()
        for node in ast.walk(ast.parse(text)):
            if (isinstance(node, ast.Call)
                    and ast.unparse(node.func) == "pl.pallas_call"):
                sites += 1
                assert "name" in {k.arg for k in node.keywords}, \
                    (module.__name__, node.lineno)
        names += re.findall(r'"((?:%s)_(?:fwd|bwd)\w*)"'
                            % "|".join(sr.KERNELS), text)
    # the attention pair's two calls are made at one site (_AttnPlan.call)
    assert sites == 14
    # forward and backward apart, also where two share one call site
    assert len(names) == len(set(names)) == 17, sorted(names)
    assert {"layer_norm_fwd", "layer_norm_bwd", "softmax_xent_fwd",
            "flash_attention_fwd", "flash_attention_bwd", "matmul_bn_bwd_dw",
            "conv3_bn_bwd_dx_blocked"} <= set(names)


def test_pallas_names_reach_the_traced_program():
    x = jnp.ones((16, 256), jnp.float32)
    g = jnp.ones((256,), jnp.float32)

    def loss(x, g, b):
        return pk.fused_layer_norm(x, g, b).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, g, g)
    found = [eqn.params["name"] for eqn in jaxpr.jaxpr.eqns
             if eqn.primitive.name == "pallas_call"]
    assert found == ["layer_norm_fwd", "layer_norm_bwd"]


# ---------------------------------------------------------------------------
# set-up on the process trace (trace.py): the spans a net's initialisation, a
# step's build and its first call leave, with sampling off and no profiler
# ---------------------------------------------------------------------------

@pytest.fixture
def process_trace():
    """An empty process trace before the test and after it."""
    from incubator_mxnet_tpu import trace
    trace.reset()
    yield trace
    trace.reset()


def _small_net():
    from incubator_mxnet_tpu.gluon import nn
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(4))       # 4 leaves, 1 deferred
    return net


def _named(trace, name):
    return [r for r in trace.process_spans() if r["name"] == name]


def _batch(rows):
    rng = onp.random.RandomState(rows)
    return (jnp.asarray(rng.rand(rows, 4).astype("f")),
            jnp.asarray(rng.rand(rows, 4).astype("f")))


def test_initialize_and_first_forward_leave_one_param_init_a_leaf(
        process_trace):
    net = _small_net()
    net.initialize()
    net(mx.nd.random.uniform(shape=(1, 4)))
    net(mx.nd.random.uniform(shape=(1, 4)))             # a later call: nothing
    (init,) = _named(process_trace, "gluon.initialize")
    (first,) = _named(process_trace, "gluon.first_forward")
    leaves = _named(process_trace, "gluon.param_init")
    # the second Dense's weight knows its shape only inside the eager
    # pass; a float32 leaf's bytes are 4 a value
    assert [(r["parent"], r["args"]["bytes"], r["args"]["deferred"])
            for r in leaves] == [
        ("gluon.initialize", 8 * 4 * 4, False),
        ("gluon.initialize", 8 * 4, False),
        ("gluon.initialize", 4 * 4, False),
        ("gluon.first_forward", 4 * 8 * 4, True)]
    assert all(r["args"]["initializer"] and r["args"]["name"] for r in leaves)
    assert init["args"]["leaves"] == 3 and init["args"]["bytes"] == 176
    assert first["args"] == {"block": "HybridSequential", "deferred": 1,
                             "outcome": "ok"}
    # the blocks inside it were not outermost: no span of their own, and
    # every latch below the root is closed
    assert not any(b._first_forward_pending
                   for b in [net] + list(net._children.values()))


def test_a_first_call_under_a_trace_is_no_first_forward(process_trace):
    net = _small_net()
    net[0].initialize()
    net[1].weight.shape = (4, 8)
    net.initialize()
    step = make_fused_train_step(net, mx.gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1})
    step(*_batch(2))            # the children's first call has tracers
    assert _named(process_trace, "gluon.first_forward") == []
    assert len(_named(process_trace, "gluon.param_init")) == 4
    assert not net[0]._first_forward_pending


def test_convert_block_is_one_span_with_its_leaves_and_bytes(process_trace):
    net = _small_net()
    net.initialize()
    net(mx.nd.random.uniform(shape=(1, 4)))
    amp.convert_block(net, "bfloat16")
    (cast,) = _named(process_trace, "amp.convert_block")
    assert cast["parent"] == "process"
    assert cast["args"]["dtype"] == "bfloat16" and cast["args"]["leaves"] == 4
    assert cast["args"]["bytes"] == 2 * (32 + 8 + 32 + 4)


@pytest.fixture
def built_step(process_trace):
    net = _small_net()
    net.initialize()
    net(mx.nd.random.uniform(shape=(1, 4)))
    step = make_fused_train_step(net, mx.gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1})
    return process_trace, step


def test_build_leaves_one_span_with_its_three_children(built_step):
    trace, step = built_step
    (build,) = _named(trace, "fused_step.build")
    assert build["parent"] == "process"
    assert build["args"]["site"] == "fused_step:HybridSequential"
    children = [r for r in trace.process_spans()
                if r["parent_id"] == build["span_id"]]
    assert [r["name"] for r in children] == [
        "fused_step.state_copy", "fused_step.place", "fused_step.program"]
    copy, place, _ = children
    # 4 parameters, their 4 momenta and the key: 76 values of 4 bytes + 8
    assert copy["args"]["leaves"] == 9
    assert copy["args"]["bytes"] == place["args"]["bytes"] == 2 * 76 * 4 + 8
    assert place["args"]["devices"] == 1
    assert build["t0"] <= copy["t0"] <= copy["t1"] <= place["t0"] \
        <= place["t1"] <= children[2]["t0"] <= build["t1"]
    assert _named(trace, "fused_step.first_call") == []


def test_first_call_holds_the_calls_spans_and_the_second_leaves_none(
        built_step):
    trace, step = built_step
    x, y = _batch(2)
    step(x, y).block_until_ready()
    (first,) = _named(trace, "fused_step.first_call")
    assert first["parent"] == "process"
    assert first["args"]["site"] == "fused_step:HybridSequential"
    (call,) = _named(trace, "fused_step.call")
    assert call["parent_id"] == first["span_id"]
    inside = {r["name"]: r for r in trace.process_spans()
              if r["parent_id"] == call["span_id"]}
    assert sorted(inside) == ["executor.call", "fused_step.analyses",
                              "fused_step.key_split"]
    # the step's compile is recorded under the jitted call that paid it
    compiled = [r for r in _named(trace, "jit.compile")
                if r["args"]["site"] == first["args"]["site"]]
    assert len(compiled) == 1
    assert compiled[0]["parent_id"] == inside["executor.call"]["span_id"]
    assert compiled[0]["args"]["fun"] == "step"
    assert compiled[0]["args"]["backend_compile_s"] > 0
    assert first["t0"] <= compiled[0]["t1"] <= first["t1"]
    held = len(trace.process_spans())
    for _ in range(3):
        step(x, y)
    step(x, y).block_until_ready()
    assert len(trace.process_spans()) == held
    assert trace.spans() == [] and not trace.active()


def test_a_recompile_leaves_one_jit_compile_and_a_flight_event(built_step):
    from incubator_mxnet_tpu import flightrec
    trace, step = built_step
    site = "fused_step:HybridSequential"
    step(*_batch(2)).block_until_ready()
    step(*_batch(2)).block_until_ready()
    held = trace.process_spans()
    events = len(flightrec.events(name="jit.compiled"))
    step(*_batch(6)).block_until_ready()        # a new batch shape
    new = trace.process_spans()[len(held):]
    # a recompile is no first call: the one record, under the root
    assert [(r["name"], r["parent"], r["args"]["site"]) for r in new] == [
        ("jit.compile", "process", site)]
    assert new[0]["args"]["trace_s"] > 0 and not new[0]["args"]["cache_hit"]
    assert abs((new[0]["t1"] - new[0]["t0"]) - sum(
        new[0]["args"][k] for k in ("trace_s", "lower_s",
                                    "backend_compile_s"))) < 1e-6
    fresh = flightrec.events(name="jit.compiled")[events:]
    assert [(e.category, e.fields["site"], e.fields["fun"])
            for e in fresh] == [("compile", site, "step")]
    assert step._executor.compile_count == 2
