"""Pallas kernel correctness vs jnp references (interpret mode on CPU —
identical kernel code paths as on TPU, per ops/pallas_kernels.py)."""
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from incubator_mxnet_tpu.ops import pallas_kernels as pk


def _rand(*shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(onp.random.RandomState(seed).randn(*shape), dtype)


# ---------------- softmax ----------------------------------------------

@pytest.mark.parametrize("shape,axis", [
    ((4, 10), -1), ((3, 5, 7), -1), ((6, 130), -1), ((2, 3, 129), 1),
])
def test_fused_softmax_matches_jnp(shape, axis):
    x = _rand(*shape)
    got = pk.fused_softmax(x, axis)
    want = jax.nn.softmax(x, axis=axis)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)


def test_fused_softmax_grad():
    x = _rand(5, 33, seed=1)

    def f_pallas(x):
        return (pk.fused_softmax(x, -1) * jnp.arange(33)).sum()

    def f_ref(x):
        return (jax.nn.softmax(x, axis=-1) * jnp.arange(33)).sum()

    onp.testing.assert_allclose(onp.asarray(jax.grad(f_pallas)(x)),
                                onp.asarray(jax.grad(f_ref)(x)),
                                rtol=1e-4, atol=1e-6)


def test_fused_softmax_extreme_values():
    x = jnp.asarray([[1e4, 1e4 + 1, -1e4], [0.0, 0.0, 0.0]], jnp.float32)
    got = pk.fused_softmax(x, -1)
    want = jax.nn.softmax(x, axis=-1)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)


# ---------------- layer norm -------------------------------------------

@pytest.mark.parametrize("shape", [(4, 16), (2, 3, 20), (5, 128), (3, 257)])
def test_fused_layer_norm_matches_reference(shape):
    x = _rand(*shape, seed=2)
    c = shape[-1]
    gamma = _rand(c, seed=3) * 0.1 + 1.0
    beta = _rand(c, seed=4) * 0.1
    got = pk.fused_layer_norm(x, gamma, beta, 1e-5)

    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    want = (x - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=1e-5)


def test_fused_layer_norm_grads():
    x = _rand(6, 37, seed=5)
    gamma = _rand(37, seed=6) * 0.2 + 1.0
    beta = _rand(37, seed=7) * 0.2

    def f_pallas(x, g, b):
        return (pk.fused_layer_norm(x, g, b, 1e-5) ** 2).sum()

    def f_ref(x, g, b):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b
        return (y ** 2).sum()

    got = jax.grad(f_pallas, argnums=(0, 1, 2))(x, gamma, beta)
    want = jax.grad(f_ref, argnums=(0, 1, 2))(x, gamma, beta)
    for g_, w_ in zip(got, want):
        onp.testing.assert_allclose(onp.asarray(g_), onp.asarray(w_),
                                    rtol=1e-3, atol=1e-4)


# ---------------- flash attention --------------------------------------
# One entry point, ``dot_product_attention``: MXNET_USE_PALLAS=1 takes the
# kernel pair (interpreted here), =0 the XLA composition, which is the
# oracle — output and all three gradients are held to its autodiff.

def _attend(monkeypatch, flag, *qkv_mask, **kw):
    from incubator_mxnet_tpu.ops import nn_ops
    monkeypatch.setenv("MXNET_USE_PALLAS", flag)
    return nn_ops.dot_product_attention.fn(*qkv_mask, **kw)


def _qkv(b, h, tq, tk, d, dtype=jnp.float32, seed=8):
    return (_rand(b, h, tq, d, dtype=dtype, seed=seed) * 0.5,
            _rand(b, h, tk, d, dtype=dtype, seed=seed + 1) * 0.5,
            _rand(b, h, tk, d, dtype=dtype, seed=seed + 2),
            _rand(b, h, tq, d, dtype=dtype, seed=seed + 3))


F32, BF16 = jnp.float32, jnp.bfloat16
# (b, h, tq, tk, d, dtype): one block and ragged lengths, BERT's head
# (one 512-key block, a plain softmax), cross lengths, several blocks
ATTN = [(2, 3, 64, 64, 32, F32), (2, 3, 200, 200, 64, F32),
        (1, 2, 512, 512, 64, F32), (1, 2, 512, 512, 64, BF16),
        (1, 2, 200, 200, 32, BF16), (1, 2, 70, 150, 32, F32),
        (1, 2, 96, 96, 32, F32), (1, 2, 640, 384, 32, F32)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("b,h,tq,tk,d,dtype", ATTN)
def test_flash_attention_matches_composition(monkeypatch, b, h, tq, tk, d,
                                             dtype, causal):
    q, k, v, ct = _qkv(b, h, tq, tk, d, dtype)
    f32 = lambda a: onp.asarray(a.astype(F32))

    def run(flag):
        out, vjp = jax.vjp(lambda *a: _attend(monkeypatch, flag, *a,
                                              causal=causal), q, k, v)
        return (out,) + vjp(ct)

    # bfloat16: the kernel rounds exp(s - max) where the composition
    # rounds the normalised probability, half a bfloat16 ulp apart
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == BF16 else \
        dict(rtol=2e-4, atol=2e-5)
    for name, got, want in zip(("out", "dq", "dk", "dv"), run("1"),
                               run("0")):
        assert got.dtype == want.dtype and got.shape == want.shape
        onp.testing.assert_allclose(f32(got), f32(want), err_msg=name,
                                    **tol)


@pytest.mark.parametrize("blocks", [(128, 128, 128 * 128),
                                    (256, 128, 2 * 256 * 128),
                                    (128, 256, 4 * 128 * 256)])
def test_flash_attention_online_rescale_and_head_groups(monkeypatch, blocks):
    """Smaller blocks than the module's: several key blocks (the online
    rescale forward, dq and dk/dv summed over blocks backward), causal
    blocks skipped, several heads a grid step."""
    for name, val in zip(("_ATTN_BQ", "_ATTN_BK", "_ATTN_SCORES"), blocks):
        monkeypatch.setattr(pk, name, val)
    q, k, v, ct = _qkv(1, 4, 384, 512, 32, seed=30)
    for causal in (False, True):
        for got, want in zip(
                jax.vjp(lambda *a: pk.flash_attention(*a, causal=causal),
                        q, k, v)[1](ct),
                jax.vjp(lambda *a: _attn_ref(*a, causal), q, k, v)[1](ct)):
            onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                        rtol=2e-4, atol=2e-5)
        onp.testing.assert_allclose(
            onp.asarray(pk.flash_attention(q, k, v, causal=causal)),
            onp.asarray(_attn_ref(q, k, v, causal)), rtol=2e-4, atol=2e-5)


# The schedule of a causal call over several blocks each way, at reduced
# block sizes: (bq, bk, heads _ATTN_SCORES allows, VMEM the plan may ask
# for or None, b, h, tq, tk, d, dv) and what ``attention_plans`` must
# read of it: (heads_fwd, heads_bwd, live pairs, all pairs, diagonal).
LIVE = {
    "4x4": ((128, 128, 4, None, 1, 4, 512, 512, 32, 32), (4, 4, 10, 16, 4)),
    "3x3": ((128, 128, 2, None, 1, 2, 384, 384, 32, 32), (2, 2, 6, 9, 3)),
    "more_q_blocks": ((128, 128, 4, None, 1, 4, 512, 256, 32, 32),
                      (4, 4, 7, 8, 2)),
    # keys past the last query are seen by no one: cut, their dk, dv zero
    "more_key_blocks": ((128, 128, 4, None, 1, 4, 256, 512, 32, 32),
                        (4, 4, 3, 4, 2)),
    "ragged_last_key_block": ((128, 128, 4, None, 1, 4, 500, 500, 32, 32),
                              (4, 4, 10, 16, 4)),
    # the padded keys of the last key block sit below the diagonal
    "ragged_below_diagonal": ((128, 128, 4, None, 1, 4, 512, 300, 32, 32),
                              (4, 4, 9, 12, 3)),
    # latent attention's 192 / 128 scaled down; six heads take three a step
    "narrower_v": ((128, 128, 4, None, 2, 3, 512, 512, 48, 32),
                   (3, 3, 10, 16, 4)),
    # the backward's whole-head dq leaves it fewer heads than the forward
    "heads_apart": ((128, 128, 4, 1 << 20, 1, 4, 512, 512, 48, 32),
                    (4, 1, 10, 16, 4)),
    "wide_q_blocks": ((256, 128, 2, None, 1, 2, 512, 512, 32, 32),
                      (2, 2, 6, 8, 4)),
    "wide_key_blocks": ((128, 256, 2, None, 1, 2, 512, 512, 32, 32),
                        (2, 2, 6, 8, 4)),
}


@pytest.mark.parametrize("case", LIVE)
def test_flash_attention_live_grid(monkeypatch, case):
    """A causal call of several blocks each way runs a grid over the live
    (q block, key block) pairs alone, masks only the blocks the diagonal
    crosses (and a padded last key block), and takes heads a step for
    each kernel apart: output, dq, dk, dv against the composition."""
    (bq, bk, heads, vmem, b, h, tq, tk, d, dv), want = LIVE[case]
    monkeypatch.setattr(pk, "_ATTN_BQ", bq)
    monkeypatch.setattr(pk, "_ATTN_BK", bk)
    monkeypatch.setattr(pk, "_ATTN_SCORES", heads * bq * bk)
    if vmem:
        monkeypatch.setattr(pk, "_ATTN_VMEM_MOST", vmem)
        monkeypatch.setattr(pk, "_ATTN_VMEM_OWN", 0)
    q, k = _rand(b, h, tq, d, seed=60) * 0.5, _rand(b, h, tk, d, seed=61) * 0.5
    v, ct = _rand(b, h, tk, dv, seed=62), _rand(b, h, tq, dv, seed=63)
    pk.attention_plans(reset=True)
    out, vjp = jax.vjp(lambda *a: pk.flash_attention(*a, causal=True),
                       q, k, v)
    (plan,) = pk.attention_plans().values()
    assert (plan["heads_fwd"], plan["heads_bwd"], plan["live_pairs"],
            plan["pairs"], plan["diagonal_pairs"]) == want
    assert plan["grid_steps_fwd"] == b * h // want[0] * want[2]
    assert plan["grid_steps_bwd"] == b * h // want[1] * want[2]
    ref, ref_vjp = jax.vjp(lambda *a: _attn_ref(*a, True), q, k, v)
    for name, got, exp in zip(("out", "dq", "dk", "dv"), (out, *vjp(ct)),
                              (ref, *ref_vjp(ct))):
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(exp),
                                    rtol=2e-4, atol=2e-5, err_msg=name)


def test_attention_plans_of_the_benchmark_cells():
    """The counter that says how the schedule engaged, for the two
    signatures the benchmark's cells trace (abstractly: nothing runs).
    The routed decoder's: 36 live pairs of 64, 8 on the diagonal, several
    heads a step in both kernels.  BERT's: one dense pair, four heads a
    step both ways, the grid it always had."""
    from incubator_mxnet_tpu import profiler

    def plan_of(b, h, t, d, dv, causal):
        pk.attention_plans(reset=True)
        qk = jax.ShapeDtypeStruct((b, h, t, d), BF16)
        v = jax.ShapeDtypeStruct((b, h, t, dv), BF16)
        jax.eval_shape(lambda q, k, v: jax.vjp(
            lambda *a: pk.flash_attention(*a, causal=causal), q, k, v)[1](
                v), qk, qk, v)
        (sig, plan), = pk.attention_plans().items()
        return sig, plan

    sig, plan = plan_of(2, 32, 4096, 192, 128, True)
    assert sig == "bh64 d192/128 t4096x4096 causal bfloat16"
    assert plan == {"heads_fwd": 4, "heads_bwd": 4, "grid_steps_fwd": 576,
                    "grid_steps_bwd": 576, "pairs": 64, "live_pairs": 36,
                    "diagonal_pairs": 8}
    # a stats provider of profiler.dumps(), beside kernel_routes
    assert profiler.provider_stats()["attention_plans"] == {sig: plan}
    assert "attention_plans" in profiler.dumps()
    sig, plan = plan_of(32, 12, 512, 64, 64, False)
    assert sig == "bh384 d64/64 t512x512 dense bfloat16"
    assert plan == {"heads_fwd": 4, "heads_bwd": 4, "grid_steps_fwd": 96,
                    "grid_steps_bwd": 96, "pairs": 1, "live_pairs": 1,
                    "diagonal_pairs": 0}


def _attn_ref(q, k, v, causal):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        mask = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def test_flash_attention_under_jit_and_vmap():
    q, k, v, _ = _qkv(2, 2, 64, 64, 32, seed=17)
    jitted = jax.jit(lambda q, k, v: pk.flash_attention(q, k, v, causal=True))
    onp.testing.assert_allclose(onp.asarray(jitted(q, k, v)),
                                onp.asarray(_attn_ref(q, k, v, True)),
                                rtol=1e-4, atol=1e-4)
    mapped = jax.vmap(lambda q, k, v: pk.flash_attention(q, k, v))
    onp.testing.assert_allclose(
        onp.asarray(mapped(q[None], k[None], v[None])[0]),
        onp.asarray(_attn_ref(q, k, v, False)), rtol=1e-4, atol=1e-4)


def _pallas_calls(fn, *args):
    """Names of the ``pallas_call``s in fn's jaxpr, inner programs too."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return sorted(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def test_dot_product_attention_routing(monkeypatch):
    """What the entry point routes where, read from the jaxpr of its
    gradient and from the ``kernel_routes`` counter."""
    from incubator_mxnet_tpu import profiler
    q, k, v, _ = _qkv(1, 2, 128, 128, 32, seed=50)
    mask = jnp.ones((1, 1, 1, 128), bool)

    def grad_of(flag, *args, **kw):
        pk.kernel_routes(reset=True)
        calls = _pallas_calls(jax.grad(
            lambda *a: _attend(monkeypatch, flag, *a, **kw).sum(),
            argnums=(0, 1, 2)), *args)
        return calls, pk.kernel_routes()["flash_attention"]

    pair = ["flash_attention_bwd", "flash_attention_fwd"]
    assert grad_of("1", q, k, v) == (pair, {"kernel": 1})
    assert grad_of("1", q, k, v, causal=True) == (pair, {"kernel": 1})
    # a mask (BERT's key-padding mask is one), float16: the composition,
    # whatever the flag says
    assert grad_of("1", q, k, v, mask) == ([], {"xla:mask": 1})
    half = [a.astype(jnp.float16) for a in (q, k, v)]
    assert grad_of("1", *half) == ([], {"xla:dtype": 1})
    assert grad_of("0", q, k, v) == ([], {"xla:flag": 1})
    # off a TPU the default is the composition, as for every other op
    assert grad_of("auto", q, k, v) == ([], {"xla:no_tpu": 1})
    with pk.gspmd_trace():
        monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
        assert grad_of("auto", q, k, v) == ([], {"xla:gspmd": 1})
    # the counter is a stats provider of profiler.dumps()
    assert profiler.provider_stats()["kernel_routes"] == {
        "flash_attention": {"xla:gspmd": 1}}
    assert "kernel_routes" in profiler.dumps()


def test_nn_ops_dispatch_to_pallas(monkeypatch):
    """ops.softmax / ops.layer_norm route through the Pallas kernels when
    MXNET_USE_PALLAS=1 and produce reference results."""
    from incubator_mxnet_tpu.ops import nn_ops
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")
    x = _rand(4, 50, seed=20)
    onp.testing.assert_allclose(
        onp.asarray(nn_ops.softmax(x, axis=-1)),
        onp.asarray(jax.nn.softmax(x, -1)), rtol=1e-5, atol=1e-6)
    g = _rand(50, seed=21) * 0.1 + 1.0
    b = _rand(50, seed=22) * 0.1
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    want = (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b
    onp.testing.assert_allclose(
        onp.asarray(nn_ops.layer_norm(x, g, b, axis=-1, eps=1e-5)),
        onp.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("flag", ["auto", "1"])
def test_transformer_flash_attention_matches_gspmd(monkeypatch, flag):
    from incubator_mxnet_tpu.models.transformer import (TransformerConfig,
                                                        TransformerLM)
    monkeypatch.setenv("MXNET_USE_PALLAS", flag)
    cfg = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
               max_len=32, dtype="float32")
    m_g = TransformerLM(TransformerConfig(**cfg, attention="gspmd"))
    m_f = TransformerLM(TransformerConfig(**cfg, attention="flash"))
    params = m_g.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(onp.random.RandomState(0).randint(0, 64, (2, 17)))
    out_g = m_g.apply(params, tokens)
    out_f = m_f.apply(params, tokens)
    onp.testing.assert_allclose(onp.asarray(out_g), onp.asarray(out_f),
                                rtol=1e-4, atol=1e-4)


def test_fused_softmax_xent_matches_reference():
    """fused_softmax_xent == -log_softmax[label] fwd+bwd, incl. padded
    widths, and the SoftmaxCrossEntropyLoss fast path stays equal to
    the log_softmax+pick formulation."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.pallas_kernels import fused_softmax_xent
    rng = onp.random.RandomState(0)
    for n, c in ((4, 7), (10, 300), (16, 1024)):
        x = jnp.asarray(rng.randn(n, c), jnp.float32)
        lbl = jnp.asarray(rng.randint(0, c, (n,)), jnp.int32)
        loss = fused_softmax_xent(x, lbl)
        ref = -jax.nn.log_softmax(x)[jnp.arange(n), lbl]
        onp.testing.assert_allclose(onp.asarray(loss), onp.asarray(ref),
                                    rtol=1e-5, atol=1e-6)
        g = jax.grad(lambda x: fused_softmax_xent(x, lbl).sum())(x)
        gref = jax.grad(
            lambda x: (-jax.nn.log_softmax(x)[jnp.arange(n), lbl]).sum())(x)
        onp.testing.assert_allclose(onp.asarray(g), onp.asarray(gref),
                                    rtol=1e-4, atol=1e-6)


def test_fused_softmax_xent_label_clip_semantics():
    """Out-of-range labels clamp like the generic pick(mode='clip')
    path — an ignore-marker label of -1 or an off-by-one vocab must not
    poison the loss with the padding value."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.pallas_kernels import fused_softmax_xent
    rng = onp.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 10), jnp.float32)
    lbl = jnp.asarray([0, -1, 10, 9], jnp.int32)
    loss = onp.asarray(fused_softmax_xent(x, lbl))
    clipped = jnp.clip(lbl, 0, 9)
    ref = onp.asarray(-jax.nn.log_softmax(x)[jnp.arange(4), clipped])
    onp.testing.assert_allclose(loss, ref, rtol=1e-5, atol=1e-6)
    assert (onp.abs(loss) < 1e3).all()  # no padding leak
    g = jax.grad(lambda x: fused_softmax_xent(x, lbl).sum())(x)
    assert onp.isfinite(onp.asarray(g)).all()


def test_softmax_ce_loss_fast_path_parity():
    from incubator_mxnet_tpu import nd, autograd, gluon
    rng = onp.random.RandomState(1)
    pred = nd.array(rng.randn(6, 50).astype("f"))
    label = nd.array(rng.randint(0, 50, (6,)).astype("f"))
    fast = gluon.loss.SoftmaxCrossEntropyLoss()
    slow = gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    # 3-D input exercises the generic path; 2-D the fused path
    out_fast = fast(pred, label)
    pred3 = nd.array(rng.randn(2, 3, 50).astype("f"))
    label3 = nd.array(rng.randint(0, 50, (2, 3)).astype("f"))
    out_gen = slow(pred3, label3)
    assert out_gen.shape == (2,)
    # fused == generic on the same 2-D input
    import jax.numpy as jnp
    ref = -jnp.take_along_axis(
        jax.nn.log_softmax(pred.data), label.data.astype(jnp.int32)[:, None],
        axis=1)[:, 0]
    onp.testing.assert_allclose(out_fast.asnumpy(), onp.asarray(ref),
                                rtol=1e-5, atol=1e-6)
    # gradient flows through the fused path
    pred.attach_grad()
    with autograd.record():
        loss = fast(pred, label).mean()
    loss.backward()
    assert float(nd.sum(nd.abs(pred.grad)).asnumpy()) > 0


def test_fused_rms_norm_matches_reference():
    """fused_rms_norm == plain RMSNorm formula, fwd + both gradients,
    incl. padded widths and a 3-D batch."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.pallas_kernels import fused_rms_norm
    rng = onp.random.RandomState(2)
    for shape in ((4, 7), (10, 300), (2, 3, 129)):
        x = jnp.asarray(rng.randn(*shape), jnp.float32)
        gamma = jnp.asarray(rng.rand(shape[-1]) + 0.5, jnp.float32)

        def ref(x, gamma):
            ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            return x * jax.lax.rsqrt(ms + 1e-6) * gamma

        got = fused_rms_norm(x, gamma, 1e-6)
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(
            ref(x, gamma)), rtol=1e-5, atol=1e-6)
        gx, gg = jax.grad(lambda x, g: fused_rms_norm(x, g, 1e-6).sum(),
                          argnums=(0, 1))(x, gamma)
        rx, rg = jax.grad(lambda x, g: ref(x, g).sum(),
                          argnums=(0, 1))(x, gamma)
        onp.testing.assert_allclose(onp.asarray(gx), onp.asarray(rx),
                                    rtol=1e-4, atol=1e-5)
        onp.testing.assert_allclose(onp.asarray(gg), onp.asarray(rg),
                                    rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch rule: the platform and MXNET_USE_PALLAS, nothing else
# ---------------------------------------------------------------------------

def _route(monkeypatch, x=None):
    """Which side `dispatch` takes for a toy op: 'kernel', 'xla', or
    'lowering picks' (both, under lax.platform_dependent)."""
    x = jnp.ones(3) if x is None else x
    jaxpr = str(jax.make_jaxpr(lambda x: pk.dispatch(
        lambda x: x + 1.0, lambda x: x + 2.0, x))(x))
    if "branches_platforms" in jaxpr:
        assert "('tpu',)" in jaxpr
        return "lowering picks"
    return "kernel" if "1.0" in jaxpr else "xla"


def test_dispatch_is_platform_flag_and_mesh(monkeypatch):
    monkeypatch.delenv("MXNET_USE_PALLAS", raising=False)
    assert _route(monkeypatch) == "xla"       # auto, no TPU backend
    assert pk.interpret_mode()
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    # auto in a process that has a TPU: the lowering picks, so a
    # computation placed on the host's CPU (mx.cpu() arrays on a TPU
    # machine) gets the composition and not a Mosaic kernel
    assert _route(monkeypatch) == "lowering picks"
    assert not pk.interpret_mode()
    with pk.gspmd_trace():                    # GSPMD partitions: no Mosaic
        assert _route(monkeypatch) == "xla"
        with pk.gspmd_trace(False):           # nesting cannot switch it off
            assert _route(monkeypatch) == "xla"
    assert _route(monkeypatch) == "lowering picks"
    monkeypatch.setenv("MXNET_USE_PALLAS", "0")
    assert _route(monkeypatch) == "xla"
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "cpu")
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")
    assert _route(monkeypatch) == "kernel"


def test_dispatch_on_a_tpu_process_runs_the_composition_on_the_cpu(
        monkeypatch):
    """What chip_smoke.py's host-CPU reference step hit on the chip: with
    a TPU as default backend the loss op took the Pallas kernel for a
    computation placed on the CPU ("Only interpret mode is supported on
    CPU backend").  Here the CPU is all there is, and it must still get
    the XLA side when the process says it has a TPU."""
    from incubator_mxnet_tpu.ops import nn_ops
    monkeypatch.delenv("MXNET_USE_PALLAS", raising=False)
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    logits, labels = _rand(16, 10, seed=30), jnp.arange(16) % 10
    loss = jax.jit(nn_ops.softmax_xent.fn)(logits, labels)
    want = -jax.nn.log_softmax(logits)[jnp.arange(16), labels]
    onp.testing.assert_allclose(onp.asarray(loss), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)


def test_interpret_mode_lets_backend_init_errors_out(monkeypatch):
    """A backend that cannot initialize is an error, not "no TPU"."""
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(pk.jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pk.interpret_mode()
    monkeypatch.delenv("MXNET_USE_PALLAS", raising=False)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pk.dispatch(lambda x: x, lambda x: x, jnp.ones(3))


def test_flash_attention_xla_route_when_pallas_off_on_tpu(monkeypatch):
    q, k, v, _ = _qkv(1, 2, 16, 16, 8, seed=0)
    ref = onp.asarray(_attn_ref(q, k, v, True))
    # MXNET_USE_PALLAS=0 is the composition on real hardware too
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pk, "flash_attention",
        lambda *a, **kw: pytest.fail("kernel ran with MXNET_USE_PALLAS=0"))
    out = onp.asarray(_attend(monkeypatch, "0", q, k, v, causal=True))
    onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("norm", ["layer_norm", "rms_norm"])
def test_norm_grads_ragged_last_row_block(norm):
    """264 rows = one full 256-row block + a ragged 8-row one: the
    per-block (8, cols) dgamma/dbeta partials must not see the rows the
    last block reads past the array."""
    x = _rand(264, 130, seed=40)
    g = _rand(130, seed=41) * 0.1 + 1.0
    b = _rand(130, seed=42) * 0.1
    ct = _rand(264, 130, seed=43)
    if norm == "layer_norm":
        def ref(x, g, b):
            mean = x.mean(-1, keepdims=True)
            var = ((x - mean) ** 2).mean(-1, keepdims=True)
            return (x - mean) * jax.lax.rsqrt(var + 1e-5) * g + b
        got = jax.grad(lambda *a: (pk.fused_layer_norm(*a) * ct).sum(),
                       argnums=(0, 1, 2))(x, g, b)
        want = jax.grad(lambda *a: (ref(*a) * ct).sum(),
                        argnums=(0, 1, 2))(x, g, b)
    else:
        def ref(x, g):
            ms = (x * x).mean(-1, keepdims=True)
            return x * jax.lax.rsqrt(ms + 1e-6) * g
        got = jax.grad(lambda *a: (pk.fused_rms_norm(*a) * ct).sum(),
                       argnums=(0, 1))(x, g)
        want = jax.grad(lambda *a: (ref(*a) * ct).sum(),
                        argnums=(0, 1))(x, g)
    for g_, w_ in zip(got, want):
        onp.testing.assert_allclose(onp.asarray(g_), onp.asarray(w_),
                                    rtol=1e-4, atol=1e-4)
