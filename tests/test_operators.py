"""Operator tests (reference tests/python/unittest/test_operator.py).

Small shapes so the finite-difference checker stays fast; numeric
gradients validate the registered vjp of each op family.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, nd, profiler
from incubator_mxnet_tpu.ops import index_ops
from incubator_mxnet_tpu.ops.nn_ops import dropout as _dropout, dropout_masks
from incubator_mxnet_tpu.test_utils import (assert_almost_equal,
                                            check_numeric_gradient)


# ---------------------------------------------------------------- elemwise

def test_unary_math_matches_numpy():
    x = onp.array([0.2, 0.5, 1.3], "float32")
    a = nd.array(x)
    for name, ref in [("exp", onp.exp), ("log", onp.log), ("sqrt", onp.sqrt),
                      ("tanh", onp.tanh), ("abs", onp.abs),
                      ("sigmoid", lambda v: 1 / (1 + onp.exp(-v)))]:
        assert_almost_equal(getattr(nd, name)(a), ref(x), rtol=1e-5)


def test_activation_family():
    x = nd.array([-2.0, -0.5, 0.0, 1.5])
    assert_almost_equal(nd.relu(x), onp.maximum(x.asnumpy(), 0))
    assert_almost_equal(nd.leaky_relu(x, slope=0.1),
                        onp.where(x.asnumpy() > 0, x.asnumpy(),
                                  0.1 * x.asnumpy()))
    out = nd.softmax(nd.array([[1.0, 2.0, 3.0]]))
    assert abs(out.asnumpy().sum() - 1.0) < 1e-6
    ls = nd.log_softmax(nd.array([[1.0, 2.0, 3.0]]))
    assert_almost_equal(onp.exp(ls.asnumpy()), out.asnumpy(), rtol=1e-5)


def test_elemwise_grads():
    a = nd.array([[0.4, 0.8], [1.2, 1.6]])
    check_numeric_gradient(lambda x: (nd.exp(x)).sum(), [a.copy()])
    check_numeric_gradient(lambda x: (nd.tanh(x) * x).sum(), [a.copy()])
    check_numeric_gradient(lambda x: nd.sigmoid(x).sum(), [a.copy()])


def test_binary_broadcast_grads():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = nd.array([0.5, 0.25])
    check_numeric_gradient(lambda x, y: (x * y).sum(), [a.copy(), b.copy()])
    check_numeric_gradient(lambda x, y: (x / (y + 1)).sum(),
                           [a.copy(), b.copy()])


def test_clip_where_maximum():
    a = nd.array([-1.0, 0.5, 2.0])
    assert nd.clip(a, 0.0, 1.0).asnumpy().tolist() == [0, 0.5, 1.0]
    assert nd.maximum(a, 0).asnumpy().tolist() == [0, 0.5, 2.0]
    w = nd.where(a > 0, a, nd.zeros_like(a))
    assert w.asnumpy().tolist() == [0, 0.5, 2.0]


# ---------------------------------------------------------------- reductions

def test_reduction_ops():
    x = onp.arange(12, dtype="float32").reshape(3, 4)
    a = nd.array(x)
    assert_almost_equal(nd.sum(a, axis=0), x.sum(0))
    assert_almost_equal(nd.mean(a, axis=1, keepdims=True),
                        x.mean(1, keepdims=True))
    assert_almost_equal(nd.prod(a + 1, axis=1), (x + 1).prod(1), rtol=1e-4)
    assert_almost_equal(nd.logsumexp(a, axis=1),
                        onp.log(onp.exp(x).sum(1)), rtol=1e-5)
    assert nd.norm(a).asscalar() == pytest.approx(onp.linalg.norm(x), rel=1e-5)


def test_reduction_grad():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    check_numeric_gradient(lambda x: nd.sum(x * x), [a.copy()])
    check_numeric_gradient(lambda x: nd.mean(x, axis=0).sum(), [a.copy()])


# ---------------------------------------------------------------- nn ops

def test_fully_connected():
    x = nd.array(onp.random.rand(2, 3).astype("float32"))
    w = nd.array(onp.random.rand(4, 3).astype("float32"))
    b = nd.array(onp.random.rand(4).astype("float32"))
    out = nd.FullyConnected(x, w, b, num_hidden=4)
    ref = x.asnumpy() @ w.asnumpy().T + b.asnumpy()
    assert_almost_equal(out, ref, rtol=1e-5)


def test_convolution_matches_reference_impl():
    # 1 input channel, identity-ish kernel check vs scipy-style manual conv
    x = onp.random.rand(1, 1, 5, 5).astype("float32")
    w = onp.random.rand(2, 1, 3, 3).astype("float32")
    out = nd.Convolution(nd.array(x), nd.array(w), None, kernel=(3, 3),
                         num_filter=2, no_bias=True)
    assert out.shape == (1, 2, 3, 3)
    # manual correlation at (0,0)
    expect = (x[0, 0, :3, :3] * w[0, 0]).sum()
    assert out.asnumpy()[0, 0, 0, 0] == pytest.approx(expect, rel=1e-4)


def test_convolution_grad():
    x = nd.array(onp.random.rand(1, 1, 4, 4).astype("float32"))
    w = nd.array(onp.random.rand(1, 1, 3, 3).astype("float32") * 0.5)
    check_numeric_gradient(
        lambda a, b: nd.Convolution(a, b, None, kernel=(3, 3), num_filter=1,
                                    no_bias=True).sum(),
        [x, w], rtol=2e-2, atol=5e-3)


def test_pooling():
    x = onp.arange(16, dtype="float32").reshape(1, 1, 4, 4)
    mx_max = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                        pool_type="max")
    assert mx_max.asnumpy()[0, 0].tolist() == [[5, 7], [13, 15]]
    mx_avg = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                        pool_type="avg")
    assert mx_avg.asnumpy()[0, 0].tolist() == [[2.5, 4.5], [10.5, 12.5]]
    glob = nd.Pooling(nd.array(x), global_pool=True, pool_type="max",
                      kernel=(1, 1))
    assert glob.asnumpy().ravel().tolist() == [15]


def test_batchnorm_inference_and_training():
    x = nd.array(onp.random.rand(4, 3, 2, 2).astype("float32"))
    gamma, beta = nd.ones((3,)), nd.zeros((3,))
    mean, var = nd.zeros((3,)), nd.ones((3,))
    out = nd.BatchNorm(x, gamma, beta, mean, var, use_global_stats=True)
    assert_almost_equal(out, x.asnumpy() / onp.sqrt(1 + 1e-5), rtol=1e-4)


def test_batchnorm_onepass_matches_twopass():
    """Training-mode batch stats: the one-pass E[x^2]-mu^2 form (the
    TPU default — no fp32 activation materialized) must match the
    two-pass E[(x-mu)^2] form, fwd and grad, in fp32 AND in bf16 (the
    production training dtype, where the square rounds to bf16)."""
    from incubator_mxnet_tpu.ops import nn_ops
    import jax, jax.numpy as jnp
    x32 = onp.random.randn(8, 5, 6, 6).astype("float32") * 3 + 1.5
    g = onp.random.rand(5).astype("float32") + 0.5
    b = onp.random.randn(5).astype("float32")

    def run(mode, dtype):
        saved = nn_ops._BN_STATS_MODE
        nn_ops._BN_STATS_MODE = mode
        try:
            def f(x, g, b):
                out = nn_ops.batch_norm.fn(
                    jnp.asarray(x, dtype), jnp.asarray(g), jnp.asarray(b),
                    jnp.zeros(5), jnp.ones(5), training=True)
                return out[0] if isinstance(out, tuple) else out
            y, vjp = jax.vjp(f, x32, g, b)
            grads = vjp(jnp.ones_like(y))
            return [onp.asarray(t, "float32") for t in (y,) + grads]
        finally:
            nn_ops._BN_STATS_MODE = saved

    for dtype, rtol, atol in (("float32", 1e-4, 1e-4),
                              ("bfloat16", 2e-2, 2e-2)):
        one = run("onepass", dtype)
        two = run("twopass", dtype)
        for a, c in zip(one, two):
            assert_almost_equal(a, c, rtol=rtol, atol=atol)


def test_layer_norm_matches_numpy():
    x = onp.random.rand(2, 5).astype("float32")
    g = onp.ones(5, "float32")
    b = onp.zeros(5, "float32")
    out = nd.LayerNorm(nd.array(x), nd.array(g), nd.array(b))
    mu = x.mean(-1, keepdims=True)
    sd = x.std(-1, keepdims=True)
    assert_almost_equal(out, (x - mu) / (sd + 1e-5), rtol=1e-3, atol=1e-3)


def test_dropout_modes():
    x = nd.ones((100,))
    from incubator_mxnet_tpu import autograd
    out = nd.Dropout(x, p=0.5)  # inference: identity
    assert_almost_equal(out, x)
    with autograd.record():
        out = nd.Dropout(x, p=0.5)
    kept = (out.asnumpy() != 0).mean()
    assert 0.2 < kept < 0.8
    assert out.asnumpy().max() == pytest.approx(2.0)  # inverted scaling


@pytest.mark.parametrize("axes", [(), (0,), (1, 2), (-1,)])
@pytest.mark.parametrize("p", [0.1, 0.5])
def test_dropout_backward_reads_the_forward_mask(p, axes):
    """The gradient of the sum is the forward's own mask, scaled: one mask
    is drawn and kept, none is drawn again for the backward pass."""
    x, key = jnp.ones((64, 8, 16), "float32"), jax.random.PRNGKey(11)
    out = _dropout(x, key, p=p, axes=axes)
    grad = jax.grad(lambda v: _dropout(v, key, p=p, axes=axes).sum())(x)
    kept = onp.asarray(out) != 0
    assert 0 < kept.sum() < kept.size
    scale = onp.float32(1) / onp.float32(1 - p)
    assert onp.array_equal(onp.asarray(grad), onp.where(kept, scale, 0))
    # a mask broadcast along `axes` is one draw for the whole axis
    for a in axes:
        assert (kept == onp.take(kept, [0], axis=a)).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dropout_mask_is_a_function_of_the_key(dtype):
    x = jnp.ones((64, 128), dtype)
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    a, again, b = (_dropout(x, k, p=0.5) for k in (k1, k1, k2))
    assert a.dtype == x.dtype
    assert onp.array_equal(onp.asarray(a != 0), onp.asarray(again != 0))
    differ = onp.asarray((a != 0) != (b != 0)).mean()
    assert 0.4 < differ < 0.6      # independent masks differ on half


@pytest.mark.parametrize("seed", [0, 1, 2147483659])
def test_dropout_keep_share_and_mean_within_four_sigma(seed):
    """Exact Bernoulli(1 - p): over 2**20 draws the kept share lies within
    four binomial standard deviations of 0.9, and the mean of the scaled
    output of ones within the matching bound of 1."""
    n, p = 2 ** 20, 0.1
    out = onp.asarray(_dropout(jnp.ones((1024, 1024), "float32"),
                               jax.random.PRNGKey(seed), p=p))
    sigma = (p * (1 - p) / n) ** 0.5
    assert abs((out != 0).mean() - (1 - p)) < 4 * sigma
    assert abs(out.astype("float64").mean() - 1.0) < 4 * sigma / (1 - p)
    assert set(onp.unique(out)) == {onp.float32(0),
                                    onp.float32(1) / onp.float32(0.9)}


@pytest.mark.parametrize("kw", [
    {"mode": "inference"}, {"mode": "always"}, {"mode": "eval"},
    {"mode": "training", "p": 0.0}])
def test_dropout_op_is_the_identity_outside_training(kw):
    """The registered op drops only under ``mode="training"`` with p > 0
    (``nd.Dropout`` maps the reference's ``always`` onto it)."""
    x = jnp.arange(24, dtype="float32").reshape(4, 6)
    out = _dropout(x, jax.random.PRNGKey(0), **{"p": 0.5, **kw})
    assert onp.array_equal(onp.asarray(out), onp.asarray(x))


def test_dropout_nd_modes_follow_autograd():
    x = nd.ones((32, 32))
    assert_almost_equal(nd.Dropout(x, p=0.5), x)            # not training
    assert (nd.Dropout(x, p=0.5, mode="always").asnumpy() == 0).any()
    with autograd.record(train_mode=False):
        assert_almost_equal(nd.Dropout(x, p=0.5), x)
    with autograd.record():
        assert (nd.Dropout(x, p=0.5).asnumpy() == 0).any()


def test_dropout_eager_recorded_and_jitted_draw_one_mask_for_one_key():
    """Eager under ``autograd.record`` (``jax.vjp`` over the op's body, op
    by op) and the same op inside ``jax.jit`` give one mask for one key,
    and the recorded backward pass reads it."""
    key = jax.random.PRNGKey(42)
    x = nd.ones((48, 96))
    x.attach_grad()
    with autograd.record():
        y = nd.Dropout(x, key=nd.array(onp.asarray(key), dtype="uint32"),
                       p=0.3)
    y.backward()
    jitted = jax.jit(lambda v, k: _dropout(v, k, p=0.3))(x.data, key)
    assert onp.array_equal(y.asnumpy(), onp.asarray(jitted))
    assert onp.array_equal(x.grad.asnumpy() != 0, y.asnumpy() != 0)


def test_dropout_masks_counter_names_each_signature_once():
    dropout_masks(reset=True)
    key = jax.random.PRNGKey(0)
    for _ in range(2):
        _dropout(jnp.ones((4, 8, 16), "bfloat16"), key, p=0.1)
    _dropout(jnp.ones((4, 8, 16), "float32"), key, p=0.1, axes=(1,))
    _dropout(jnp.ones((4, 8, 16), "float32"), key, p=0.1, mode="inference")
    assert profiler.provider_stats()["dropout_masks"] == {
        "4x1x16 p0.1 float32": {"elements": 64, "kept_bytes": 64,
                                "generator": "rng_bit_generator"},
        "4x8x16 p0.1 bfloat16": {"elements": 512, "kept_bytes": 512,
                                 "generator": "rng_bit_generator"}}
    assert "dropout_masks" in profiler.dumps()
    assert dropout_masks(reset=True) and not dropout_masks()


def test_embedding_and_one_hot():
    w = nd.array(onp.arange(12, dtype="float32").reshape(4, 3))
    idx = nd.array([0, 3], dtype="int32")
    out = nd.Embedding(idx, w, input_dim=4, output_dim=3)
    assert out.asnumpy().tolist() == [[0, 1, 2], [9, 10, 11]]


# The gradient of `Embedding` by its table, routed by shape (PR 35).  The
# route asks `jax.default_backend()`; these tests answer "tpu" for it as
# tests/test_tpu_compile.py's `for_the_chip` does, and no switch of the
# op's.  At toy shapes the matmul is the cheaper by the model.

@pytest.fixture
def on_the_chip(monkeypatch):
    monkeypatch.setattr(index_ops.jax, "default_backend", lambda: "tpu")


_embedding = index_ops.embedding.fn


def _take_reversed(ids, weight):
    return index_ops._take_rows(weight, ids)

EMBEDDING_IDS = {
    "duplicates": onp.array([3, 0, 3, 3, 10, 0], "int32"),
    "out_of_range": onp.array([-4, 0, 11, 25, 10, -1], "int32"),
    "float_ids": onp.array([3., 0., 3., 12., -2., 7.], "float32"),
    "rank2": onp.array([[3, 0, 3], [10, 12, -1]], "int32"),
    "rank3": onp.array([[[3, 0], [3, 10]], [[10, 3], [5, 5]]], "int32"),
}


def _table_grad(lookup, ids, weight, g):
    out, vjp = jax.vjp(lambda w: lookup(jnp.asarray(ids), w), weight)
    return out, vjp(g)[0]


@pytest.mark.parametrize("case", sorted(EMBEDDING_IDS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_embedding_matmul_route_gradient_is_the_scatters(on_the_chip, dtype,
                                                         case):
    ids = EMBEDDING_IDS[case]
    rng = onp.random.default_rng(35)
    weight = jnp.asarray(rng.standard_normal((11, 8)), dtype)
    # sixteenths: every sum of duplicates is exact in float32
    g = jnp.asarray(onp.round(16 * rng.standard_normal(ids.shape + (8,)))
                    / 16, dtype)
    index_ops.embedding_grads(reset=True)
    out, grad = _table_grad(_embedding, ids, weight, g)
    (entry,) = index_ops.embedding_grads().values()
    assert entry["route"] == "matmul"
    want_out, want = _table_grad(_take_reversed, ids, weight, g)
    assert out.dtype == grad.dtype == weight.dtype
    assert onp.array_equal(onp.asarray(out, "float32"),
                           onp.asarray(want_out, "float32"))
    # summed in float32 and rounded once, whatever the table's dtype
    _, exact = _table_grad(_take_reversed, ids, weight.astype("float32"),
                           g.astype("float32"))
    assert onp.array_equal(onp.asarray(grad, "float32"),
                           onp.asarray(exact.astype(dtype), "float32"))
    # the scatter rounds once a duplicate: equal in float32, close in bfloat16
    assert_almost_equal(onp.asarray(grad, "float32"),
                        onp.asarray(want, "float32"),
                        rtol=0 if dtype == "float32" else 2 ** -6, atol=0)


@pytest.mark.parametrize("ids_dtype,zero", [("int32", jax.dtypes.float0),
                                            ("float32", "float32")])
def test_embedding_ids_get_the_zero_cotangent_of_their_dtype(
        on_the_chip, ids_dtype, zero):
    ids = jnp.asarray([3, 0, 3, 12], ids_dtype)
    weight = jnp.ones((11, 8), "float32")
    out, vjp = jax.vjp(_embedding, ids, weight)
    by_ids, by_table = vjp(jnp.ones_like(out))
    assert by_ids.dtype == zero and by_ids.shape == ids.shape
    if zero == "float32":           # a float0 array holds nothing to read
        assert not onp.asarray(by_ids).any()
    assert by_table.sum() == out.size


def test_embedding_output_used_twice_gets_the_sum(on_the_chip):
    """The routed decoder's pattern: the embedding feeds the trunk and the
    MTP module, so the backward sees the sum of two cotangents."""
    ids = jnp.asarray(EMBEDDING_IDS["rank2"])
    rng = onp.random.default_rng(36)
    weight, a, b = (jnp.asarray(rng.standard_normal(s), "float32")
                    for s in ((11, 8), (2, 3, 8), (2, 3, 8)))

    def loss(lookup, w):
        e = lookup(ids, w)
        return jnp.sum(e * a) + jnp.sum(jnp.tanh(e) * b)

    got = jax.grad(lambda w: loss(_embedding, w))(weight)
    want = jax.grad(lambda w: loss(_take_reversed, w))(weight)
    assert_almost_equal(onp.asarray(got), onp.asarray(want), rtol=1e-6,
                        atol=1e-6)


def test_embedding_eager_recorded_backward_takes_the_route(on_the_chip):
    """`nd.Embedding` under `autograd.record` with MXNet's float ids."""
    ids = onp.array([[4., 1., 4.], [6., 9., 0.]], "float32")
    w = nd.array(onp.arange(35, dtype="float32").reshape(7, 5))
    w.attach_grad()
    with autograd.record():
        out = nd.Embedding(nd.array(ids), w, input_dim=7, output_dim=5)
    out.backward()
    counts = onp.bincount(onp.clip(ids, 0, 6).astype(int).ravel(),
                          minlength=7)
    assert onp.array_equal(w.grad.asnumpy(),
                           onp.repeat(counts[:, None], 5, 1))
    assert index_ops.embedding_grads()["6 -> 7x5 float32"]["route"] == \
        "matmul"


@pytest.mark.parametrize("signature,route,form", [
    ((16384, 30522, 768), "scatter", "sorted"),            # BERT-base
    ((8194, 16160, 2048), "scatter", "sorted"),            # JoyAI, cut
    ((4096, 32640, 5120), "matmul", "sorted_gathered"),    # Falcon-H1, cut
    ((8192, 129280, 2048), "scatter", "in_place"),         # JoyAI, published
    ((4096, 261120, 5120), "scatter", "in_place"),         # Falcon, published
    ((4096, 32768, 5120), "scatter", "in_place"),          # 128 rows more
    ((4096, 32640, 2560), "matmul", "sorted"),             # a slow narrow row
    ((4096, 32640, 4096), "scatter", "sorted"),
])
def test_embedding_grad_route_compares_costs(on_the_chip, signature, route,
                                             form):
    entry = index_ops.embedding_grad_route(*signature, "bfloat16")
    assert (entry["route"], entry["scatter_form"]) == (route, form)
    assert entry["matmul_flops"] == 2 * onp.prod(signature, dtype="int64")
    cheaper = min(entry["est_matmul_ms"], entry["est_scatter_ms"])
    assert entry[f"est_{route}_ms"] == cheaper


def test_embedding_grad_route_off_the_tpu_and_in_an_unmeasured_dtype(
        monkeypatch):
    falcon = (4096, 32640, 5120)
    assert index_ops.embedding_grad_route(*falcon, "bfloat16")["route"] == \
        "scatter"                                   # the CPU has no cliff
    monkeypatch.setattr(index_ops.jax, "default_backend", lambda: "tpu")
    assert index_ops.embedding_grad_route(*falcon, "float32")["route"] == \
        "matmul"
    half = index_ops.embedding_grad_route(*falcon, "float16")
    assert half["route"] == "scatter" and half["est_matmul_ms"] is None


def test_embedding_at_berts_signature_traces_to_bare_take(on_the_chip):
    def traced(fn, ids, rows, width):
        return str(jax.make_jaxpr(fn)(
            jax.ShapeDtypeStruct((ids,), "int32"),
            jax.ShapeDtypeStruct((rows, width), "bfloat16")))

    def bare(data, weight):
        return jnp.take(weight, data.astype(jnp.int32), axis=0, mode="clip")

    assert traced(_embedding, 16384, 30522, 768) == \
        traced(bare, 16384, 30522, 768)
    assert "custom_vjp" in traced(_embedding, 4096, 32640, 5120)
    assert "custom_vjp" not in traced(bare, 4096, 32640, 5120)


def test_embedding_grads_counter_names_each_route_once(on_the_chip):
    index_ops.embedding_grads(reset=True)
    table = jax.ShapeDtypeStruct((30522, 768), "bfloat16")
    for _ in range(2):
        jax.eval_shape(_embedding, jax.ShapeDtypeStruct((32, 512), "int32"),
                       table)
    jax.eval_shape(_embedding, jax.ShapeDtypeStruct((1, 4096), "int32"),
                   jax.ShapeDtypeStruct((32640, 5120), "bfloat16"))
    assert profiler.provider_stats()["embedding_grads"] == {
        "16384 -> 30522x768 bfloat16": {
            "route": "scatter", "ids": 16384, "table_rows": 30522,
            "width": 768, "matmul_flops": 768111280128,
            "est_matmul_ms": 4.267, "est_scatter_ms": 0.645,
            "scatter_form": "sorted"},
        "4096 -> 32640x5120 bfloat16": {
            "route": "matmul", "ids": 4096, "table_rows": 32640,
            "width": 5120, "matmul_flops": 1369020825600,
            "est_matmul_ms": 7.606, "est_scatter_ms": 56.712,
            "scatter_form": "sorted_gathered"}}
    assert "embedding_grads" in profiler.dumps()
    assert index_ops.embedding_grads(reset=True)
    assert not index_ops.embedding_grads()


def test_softmax_output_and_ctc_exist():
    x = nd.array(onp.random.rand(2, 4).astype("float32"))
    label = nd.array([1, 3])
    out = nd.SoftmaxOutput(x, label)
    assert out.shape == (2, 4)
    assert_almost_equal(out.asnumpy().sum(1), onp.ones(2), rtol=1e-5)


# ---------------------------------------------------------------- shape ops

def test_shape_manipulation():
    a = nd.arange(0, 24).reshape((2, 3, 4))
    assert nd.transpose(a).shape == (4, 3, 2)
    assert nd.swapaxes(a, 0, 2).shape == (4, 3, 2)
    assert nd.expand_dims(a, axis=1).shape == (2, 1, 3, 4)
    assert nd.squeeze(nd.expand_dims(a, 0)).shape == (2, 3, 4)
    assert nd.flip(a, axis=0).asnumpy()[0, 0, 0] == 12
    assert nd.tile(nd.ones((2,)), reps=(3,)).shape == (6,)
    assert nd.repeat(nd.array([1, 2]), repeats=2).asnumpy().tolist() == \
        [1, 1, 2, 2]
    assert nd.depth_to_space(nd.ones((1, 4, 2, 2)), block_size=2).shape == \
        (1, 1, 4, 4)
    assert nd.space_to_depth(nd.ones((1, 1, 4, 4)), block_size=2).shape == \
        (1, 4, 2, 2)


def test_slice_ops():
    a = nd.arange(0, 20).reshape((4, 5))
    s = nd.slice(a, begin=(1, 0), end=(3, 2))
    assert s.asnumpy().tolist() == [[5, 6], [10, 11]]
    sa = nd.slice_axis(a, axis=1, begin=1, end=3)
    assert sa.shape == (4, 2)
    sl = nd.slice_like(a, nd.zeros((2, 2)))
    assert sl.shape == (2, 2)


def test_gather_scatter_nd():
    data = nd.array([[1.0, 2], [3, 4]])
    indices = nd.array([[1, 0], [0, 1]], dtype="int32")
    out = nd.gather_nd(data, indices)
    assert out.asnumpy().tolist() == [3, 2]
    sc = nd.scatter_nd(nd.array([9.0, 8]), indices, shape=(2, 2))
    assert sc.asnumpy()[1, 0] == 9 and sc.asnumpy()[0, 1] == 8


# ---------------------------------------------------------------- ordering

def test_topk_sort_argsort():
    a = nd.array([[3.0, 1, 2], [6, 5, 4]])
    t = nd.topk(a, k=2, ret_typ="value")
    assert t.asnumpy().tolist() == [[3, 2], [6, 5]]
    s = nd.sort(a, axis=1)
    assert s.asnumpy()[0].tolist() == [1, 2, 3]
    ai = nd.argsort(a, axis=1)
    assert ai.asnumpy()[0].tolist() == [1, 2, 0]


# ---------------------------------------------------------------- sequence

def test_sequence_ops():
    # (seq_len, batch, feat)
    x = nd.array(onp.arange(12, dtype="float32").reshape(3, 2, 2))
    length = nd.array([2, 3])
    masked = nd.SequenceMask(x, sequence_length=length,
                             use_sequence_length=True, value=-1)
    assert masked.asnumpy()[2, 0].tolist() == [-1, -1]
    assert masked.asnumpy()[2, 1].tolist() == [10, 11]
    last = nd.SequenceLast(x, sequence_length=length,
                           use_sequence_length=True)
    assert last.asnumpy()[0].tolist() == [4, 5]
    rev = nd.SequenceReverse(x, sequence_length=length,
                             use_sequence_length=True)
    assert rev.asnumpy()[0, 0].tolist() == [4, 5]


# ---------------------------------------------------------------- control flow

def test_foreach_cumsum():
    from incubator_mxnet_tpu.ops import control_flow as cf
    data = nd.array([[1.0], [2.0], [3.0]])
    init = nd.array([0.0])

    def body(x, state):
        s = state[0] + x
        return s, [s]

    outs, final = cf.foreach(body, data, [init])
    assert final[0].asnumpy().tolist() == [6]
    assert outs.asnumpy().ravel().tolist() == [1, 3, 6]


def test_while_loop_countdown():
    from incubator_mxnet_tpu.ops import control_flow as cf
    final = cf.while_loop(
        cond_fn=lambda i, s: (i < 4).sum(),
        body_fn=lambda i, s: [i + 1, s + i],
        loop_vars=[nd.array([0.0]), nd.array([0.0])],
        max_iterations=10)
    assert final[1].asnumpy().tolist() == [6]  # 0+1+2+3


def test_cond_branches():
    from incubator_mxnet_tpu.ops import control_flow as cf
    x = nd.array([2.0])
    out = cf.cond(x.sum() > 1, lambda: x * 10, lambda: x - 10)
    assert out.asnumpy().tolist() == [20]


# ---------------------------------------------------------------- linalg

def test_linalg_ops():
    a = onp.array([[2.0, 0], [1, 3]], "float32")
    assert nd.linalg_det(nd.array(a)).asscalar() == pytest.approx(6.0)
    inv = nd.linalg_inverse(nd.array(a))
    assert_almost_equal(inv.asnumpy() @ a, onp.eye(2), atol=1e-5)
    g = nd.linalg_gemm2(nd.array(a), nd.array(a))
    assert_almost_equal(g, a @ a, rtol=1e-5)
    spd = a @ a.T + onp.eye(2, dtype="float32")
    l = nd.linalg_potrf(nd.array(spd))
    assert_almost_equal(l.asnumpy() @ l.asnumpy().T, spd, rtol=1e-4)


def test_dot_and_batch_dot():
    a = nd.array(onp.random.rand(2, 3).astype("float32"))
    b = nd.array(onp.random.rand(3, 4).astype("float32"))
    assert_almost_equal(nd.dot(a, b), a.asnumpy() @ b.asnumpy(), rtol=1e-5)
    x = nd.array(onp.random.rand(5, 2, 3).astype("float32"))
    y = nd.array(onp.random.rand(5, 3, 2).astype("float32"))
    assert_almost_equal(nd.batch_dot(x, y),
                        onp.matmul(x.asnumpy(), y.asnumpy()), rtol=1e-5)


# ---------------------------------------------------------------- random

def test_random_ops_statistics():
    mx.random.seed(42)
    u = nd.random.uniform(0, 1, shape=(2000,))
    assert 0.45 < u.asnumpy().mean() < 0.55
    n = nd.random.normal(0, 1, shape=(2000,))
    assert abs(n.asnumpy().mean()) < 0.1
    r = nd.random.randint(0, 5, shape=(100,))
    assert r.asnumpy().min() >= 0 and r.asnumpy().max() < 5


def test_random_seed_reproducible():
    mx.random.seed(7)
    a = nd.random.uniform(shape=(5,)).asnumpy()
    mx.random.seed(7)
    b = nd.random.uniform(shape=(5,)).asnumpy()
    assert (a == b).all()


# ---------------------------------------------------------------- misc

def test_cast_and_identity():
    a = nd.array([1.5, 2.5])
    assert nd.cast(a, "int32").asnumpy().tolist() == [1, 2]
    assert nd.identity(a).asnumpy().tolist() == [1.5, 2.5]
    assert nd.BlockGrad(a).asnumpy().tolist() == [1.5, 2.5]


def test_smooth_l1():
    x = nd.array([-2.0, -0.5, 0.5, 2.0])
    out = nd.smooth_l1(x, scalar=1.0)
    expect = onp.where(onp.abs(x.asnumpy()) < 1,
                       0.5 * x.asnumpy() ** 2,
                       onp.abs(x.asnumpy()) - 0.5)
    assert_almost_equal(out, expect, rtol=1e-5)


def test_spatial_transformer_family():
    """STN ops (reference bilinear_sampler.cc / grid_generator.cc /
    spatial_transformer.cc / upsampling.cc)."""
    import numpy as onp
    from incubator_mxnet_tpu import nd

    rng = onp.random.RandomState(0)
    data = nd.array(rng.rand(2, 3, 5, 5).astype(onp.float32))
    ident = nd.array(onp.tile(onp.array([1, 0, 0, 0, 1, 0], onp.float32),
                              (2, 1)))
    out = nd.SpatialTransformer(data, ident, target_shape=(5, 5))
    onp.testing.assert_allclose(out.asnumpy(), data.asnumpy(),
                                rtol=1e-4, atol=1e-5)
    # horizontal-flip affine: x' = -x
    flip = nd.array(onp.tile(onp.array([-1, 0, 0, 0, 1, 0], onp.float32),
                             (2, 1)))
    out2 = nd.SpatialTransformer(data, flip, target_shape=(5, 5))
    onp.testing.assert_allclose(out2.asnumpy(),
                                data.asnumpy()[:, :, :, ::-1],
                                rtol=1e-4, atol=1e-5)
    # grid_generator warp mode: zero flow == identity sampling
    zero_flow = nd.zeros((2, 2, 5, 5))
    grid = nd.GridGenerator(zero_flow, transform_type="warp")
    out3 = nd.BilinearSampler(data, grid)
    onp.testing.assert_allclose(out3.asnumpy(), data.asnumpy(),
                                rtol=1e-4, atol=1e-5)
    # gradients flow through the sampler
    import jax, jax.numpy as jnp
    from incubator_mxnet_tpu.ops.registry import get_op
    bs = get_op("BilinearSampler")
    g = jax.grad(lambda d: jnp.sum(bs.fn(d, grid.data)))(data.data)
    assert float(jnp.abs(g).sum()) > 0


def test_upsampling_bilinear_and_masked_softmax():
    import numpy as onp
    from incubator_mxnet_tpu import nd

    x = nd.array(onp.arange(8, dtype=onp.float32).reshape(1, 2, 2, 2))
    up = nd.UpSampling(x, scale=2, sample_type="bilinear")
    assert up.shape == (1, 2, 4, 4)
    m = nd.masked_softmax(nd.ones((1, 3)),
                          nd.array(onp.array([[1, 0, 1]], onp.float32)))
    onp.testing.assert_allclose(m.asnumpy(), [[0.5, 0.0, 0.5]], rtol=1e-5)
