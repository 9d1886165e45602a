"""Model-family tests: BERT and LSTM-LM (BASELINE.json configs 3 and 5;
reference counterparts: gluon-nlp BERT-base pretraining and
example/rnn's LSTM LM).  SSD has its own suite in test_contrib_det.py;
TransformerLM sharding is covered in test_parallel.py.
"""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd, gluon


def _tiny_bert(**kw):
    from incubator_mxnet_tpu.models.bert import BERTModel
    cfg = dict(vocab_size=50, num_layers=2, units=16, hidden_size=32,
               num_heads=2, max_length=24, dropout=0.0)
    cfg.update(kw)
    net = BERTModel(**cfg)
    net.initialize()
    return net


def _overfit(step_fn, steps, ratio):
    """Run the train loop until the loss dips below first*ratio (early
    exit) or steps run out; returns (first, final)."""
    first = final = None
    for _ in range(steps):
        v = step_fn()
        if first is None:
            first = v
        elif v < first * ratio:
            final = v
            break
    return first, final if final is not None else v


def test_bert_forward_shapes():
    net = _tiny_bert()
    B, T = 3, 10
    tokens = nd.array(onp.random.RandomState(0).randint(0, 50, (B, T))
                      .astype(onp.int32))
    types = nd.zeros(shape=(B, T), dtype="int32")
    seq, nsp = net(tokens, types)  # (mlm_logits, nsp_logits)
    assert seq.shape == (B, T, 50)      # MLM logits over vocab
    assert nsp.shape == (B, 2)          # NSP head


def test_bert_valid_length_masks_attention():
    """Padding tokens beyond valid_length must not change the prefix
    outputs (attention-mask semantics)."""
    net = _tiny_bert()
    rng = onp.random.RandomState(1)
    B, T, VL = 2, 12, 5
    base = rng.randint(1, 50, (B, T)).astype(onp.int32)
    pad_a = base.copy()
    pad_b = base.copy()
    pad_b[:, VL:] = 7  # different padding content
    vl = nd.array(onp.full((B,), VL, onp.float32))
    seq_a = net(nd.array(pad_a), None, vl)[0].asnumpy()
    seq_b = net(nd.array(pad_b), None, vl)[0].asnumpy()
    onp.testing.assert_allclose(seq_a[:, :VL], seq_b[:, :VL], rtol=1e-4,
                                atol=1e-5)


def test_bert_mlm_overfits_tiny_batch():
    """Masked-LM objective memorizes a fixed batch (config-3 smoke)."""
    net = _tiny_bert()
    rng = onp.random.RandomState(2)
    B, T = 4, 8
    tokens = rng.randint(1, 50, (B, T)).astype(onp.int32)
    labels = tokens.copy()
    masked = tokens.copy()
    masked[:, ::2] = 0  # mask half the positions
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = nd.array(masked)
    y = nd.array(labels.reshape(-1))
    def step():
        with autograd.record():
            seq = net(x)[0]
            loss = loss_fn(seq.reshape(B * T, -1), y).mean()
        loss.backward()
        trainer.step(B)
        return float(loss.asnumpy())

    first, final = _overfit(step, 40, 0.5)
    assert final < first * 0.5, (first, final)


def test_bert_amp_bf16_conversion():
    """AMP bf16 conversion runs on BERT and keeps LN/softmax healthy."""
    from incubator_mxnet_tpu import amp
    net = _tiny_bert()
    tokens = nd.array(onp.random.RandomState(3).randint(0, 50, (2, 6))
                      .astype(onp.int32))
    ref_seq = net(tokens)[0]
    amp.convert_block(net, "bfloat16")
    out_seq = net(tokens)[0]
    assert out_seq.shape == ref_seq.shape
    assert onp.isfinite(out_seq.asnumpy()).all()
    # bf16 has ~3 decimal digits; just require correlation with fp32
    a, b = ref_seq.asnumpy().ravel(), out_seq.asnumpy().ravel()
    corr = onp.corrcoef(a, b)[0, 1]
    assert corr > 0.98, corr


@pytest.mark.slow   # ~69 s convergence run: the tier-1 budget's top
                    # hog (ISSUE 15 relief); the `slow` CI stage keeps it
def test_lstm_lm_overfits():
    from incubator_mxnet_tpu.models.lstm_lm import LSTMLanguageModel
    rng = onp.random.RandomState(4)
    net = LSTMLanguageModel(vocab_size=30, embed_size=16, hidden_size=32,
                            dropout=0.0)
    net.initialize()
    B, T = 4, 6
    seq = rng.randint(0, 30, (B, T + 1)).astype(onp.int32)
    # the model is time-major (LSTM layout=TNC): inputs (T, B), and the
    # flattened logits follow T*B order
    x = nd.array(seq[:, :-1].T.copy())
    y = nd.array(seq[:, 1:].T.reshape(-1))
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    def step():
        with autograd.record():
            out = net(x)
            logits = out[0] if isinstance(out, tuple) else out
            loss = loss_fn(logits.reshape(B * T, -1), y).mean()
        loss.backward()
        trainer.step(B)
        return float(loss.asnumpy())

    first, final = _overfit(step, 150, 0.4)
    assert final < first * 0.4, (first, final)


def test_resnet_s2d_stem_variant():
    """resnet50_v1(stem='s2d') — the MLPerf space-to-depth stem: same
    output contract as the classic stem, stem conv reads the s2d-packed
    12-channel input, and the fused train step runs end to end."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.fuse import make_fused_train_step

    mx.random.seed(0)
    net = vision.resnet50_v1(stem="s2d")
    net.initialize(ctx=mx.cpu())
    x = nd.random.uniform(shape=(2, 3, 64, 64))
    out = net(x)
    assert out.shape == (2, 1000)
    # the stem conv consumes the 12-channel s2d layout
    stem = net.features._children["0"]
    assert stem.conv.weight.shape == (64, 12, 4, 4)
    # spatial contract matches the classic stem stage by stage
    plain = vision.resnet50_v1()
    plain.initialize(ctx=mx.cpu())
    assert plain(x).shape == out.shape

    step = make_fused_train_step(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9})
    y = nd.random.randint(0, 1000, shape=(2,))
    l0 = float(step(x.data, y.data))
    l1 = float(step(x.data, y.data))
    assert onp.isfinite(l0) and onp.isfinite(l1)
