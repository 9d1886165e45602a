"""Fused train step (fuse.py) — the path every benchmark cell trains through.

The whole-step program (forward + backward + optimizer + BN stat
updates, donated buffers) must match the eager Trainer path formula-
for-formula; these tests pin that equivalence per optimizer and the
BN-stat round-trip that a measured step's correctness rests on.
"""
import jax
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd, gluon
from incubator_mxnet_tpu.fuse import make_fused_train_step
from incubator_mxnet_tpu.gluon import nn


def _net(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    # use_bias=False: BN exactly cancels a conv bias, so its gradient
    # is numerical noise and Adam would amplify path-dependent rounding
    # into full-size steps — not a real divergence, just ill-posed
    net.add(nn.Conv2D(4, 3, padding=1, in_channels=3, use_bias=False),
            nn.BatchNorm(in_channels=4), nn.Activation("relu"),
            nn.GlobalAvgPool2D(), nn.Flatten(), nn.Dense(5, in_units=4))
    net.initialize()
    net(nd.random.uniform(shape=(1, 3, 8, 8)))  # materialize shapes
    return net


def _data(bs=4, seed=1):
    rng = onp.random.RandomState(seed)
    x = nd.array(rng.rand(bs, 3, 8, 8).astype("f"))
    y = nd.array(rng.randint(0, 5, (bs,)).astype("i4"))
    return x, y


@pytest.mark.parametrize("opt,params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
    ("adamw", {"learning_rate": 0.01, "wd": 0.01}),
])
def test_fused_step_matches_eager_trainer(opt, params):
    """N fused steps == N eager record/backward/Trainer.step steps."""
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _data()

    net_e = _net()
    trainer = gluon.Trainer(net_e.collect_params(), opt, dict(params))
    for _ in range(3):
        with autograd.record():
            loss_e = loss_fn(net_e(x), y).mean()
        loss_e.backward()
        trainer.step(1)  # fused grads are means; batch already averaged

    net_f = _net()
    step = make_fused_train_step(net_f, loss_fn, opt, dict(params))
    for _ in range(3):
        loss_f = step(x, y)
    step.write_back()

    onp.testing.assert_allclose(float(loss_f), float(loss_e.asnumpy()),
                                rtol=1e-4)
    for (n1, p1), (n2, p2) in zip(net_e.collect_params().items(),
                                  net_f.collect_params().items()):
        onp.testing.assert_allclose(p1.data().asnumpy(),
                                    p2.data().asnumpy(), rtol=2e-3,
                                    atol=2e-4, err_msg=f"{opt}:{n1}")


def test_fused_step_updates_bn_stats():
    net = _net()
    step = make_fused_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "sgd", {"learning_rate": 0.1})
    x, y = _data()
    mean_before = {k: v.copy() for k, v in step.aux.items()
                   if "running_mean" in k or "moving_mean" in k}
    assert mean_before, "expected BN aux states in the fused step"
    for _ in range(2):
        step(x, y)
    for k, v0 in mean_before.items():
        assert float(abs(step.aux[k] - v0).sum()) > 0, k
    # write_back pushes aux into the Block
    step.write_back()
    for name, p in net.collect_params().items():
        if name in mean_before:
            onp.testing.assert_allclose(p.data().asnumpy(),
                                        onp.asarray(step.aux[name]))


def test_fused_step_loss_decreases():
    net = _net()
    step = make_fused_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 "adam", {"learning_rate": 1e-2})
    x, y = _data(bs=8)
    first = float(step(x, y))
    last = first
    for _ in range(80):
        last = float(step(x, y))
        if last < first * 0.7:
            break
    assert last < first * 0.7, (first, last)


def _stack(recompute):
    """Two residual MLP blocks and a head, no aux state; ``recompute`` names
    the blocks that ask for it."""
    class Residual(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.up = nn.Dense(32, in_units=16, flatten=False)
            self.down = nn.Dense(16, in_units=32, flatten=False)

        def forward(self, x):
            return x + self.down(self.up(x).tanh())

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Flatten(), nn.Dense(16, in_units=192), Residual(), Residual(),
            nn.Dense(5, in_units=16))
    net.initialize()
    for i in recompute:
        net[i].recompute()
    return net


@pytest.mark.parametrize("recompute", [(2,), (2, 3)],
                         ids=["one_block", "every_block"])
def test_fused_step_recompute_matches_plain(recompute):
    """Blocks under ``recompute()`` run again in the backward pass: the
    schedule changes, the update does not.  Same seed, same data: the same
    losses, and the recomputed instructions are in the step's program."""
    def run(blocks):
        step = make_fused_train_step(
            _stack(blocks), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9})
        x, y = _data(bs=8)
        losses = [float(step(x, y)) for _ in range(3)]
        text = str(jax.make_jaxpr(step.step_fn)(
            step.params, step.aux, step.opt_state, x.data, y.data,
            step._key))
        return losses, text.count("remat2[")

    (plain, none), (again, some) = run(()), run(recompute)
    assert plain == again, (plain, again)
    assert none == 0 and some >= len(recompute)


def test_recompute_carries_a_blocks_aux_state_updates_out():
    """BatchNorm's moving statistics leave a checkpointed region as further
    outputs of it: the same losses and the same statistics as without."""
    def run(recompute):
        mx.random.seed(0)
        net = _net()
        net[1].recompute(recompute)          # the BatchNorm
        step = make_fused_train_step(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1})
        x, y = _data(bs=8)
        losses = [float(step(x, y)) for _ in range(3)]
        return losses, {n: onp.asarray(v) for n, v in step.aux.items()}

    (plain, stats), (again, stats_again) = run(False), run(True)
    assert plain == again and stats
    for name, value in stats.items():
        assert (value == stats_again[name]).all(), name
    assert any((v != 0).any() and (v != 1).any() for v in stats.values())


def test_fused_step_rejects_unknown_optimizer():
    net = _net()
    with pytest.raises(ValueError, match="fused step supports"):
        make_fused_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "ftrl", {})
