"""BERT (``models.bert.BERTModel``) at the sizes of the .json beside this
file (a test's toy configuration gives its own sizes to the same code)."""
import numpy as onp


def build(seed, config):
    """The net on the host, initialised through Gluon from the seed the
    runner gave ``mx.random`` (every shape is given, so nothing is deferred);
    the loss and the optimizer of the configuration."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.models.bert import BERTModel
    net = BERTModel(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        units=config["hidden_size"],
        hidden_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        max_length=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        dropout=config["hidden_dropout_prob"])
    net.initialize()
    return {"net": net, "loss": gluon.loss.SoftmaxCrossEntropyLoss(),
            "optimizer": config["optimizer"],
            "optimizer_params": config["optimizer_params"]}


def make_batch(seed, i, batch, config, traffic):
    """Batch ``i`` of the pool for ``seed``: ``batch`` sequences of
    ``seq_len`` ordinary tokens drawn uniformly, ``mask_fraction`` of them
    replaced by the mask id; the labels are the tokens before masking."""
    rng = onp.random.default_rng([seed, i])
    shape = (batch, traffic["seq_len"])
    labels = rng.integers(config["first_ordinary_token_id"],
                          config["vocab_size"], shape, dtype=onp.int32)
    masked = rng.random(shape) < config["mask_fraction"]
    tokens = onp.where(masked, onp.int32(config["mask_token_id"]), labels)
    return tokens, labels


def n_classes(config):
    return config["vocab_size"]


def matmul_params(config):
    """Parameters that multiply every token: per layer the query/key/value
    and output projections (4 h^2) and the two feed-forward matrices
    (2 h f), and the vocabulary decoder (h V), which this objective applies
    at every position.  Embedding look-ups are not multiplications; the
    pooler and next-sentence head see one token a sequence and get no
    gradient."""
    h, f = config["hidden_size"], config["intermediate_size"]
    return (config["num_hidden_layers"] * (4 * h * h + 2 * h * f)
            + h * config["vocab_size"])


def flops_per_sample(config, traffic):
    """Model FLOPs to train on one sequence of ``seq_len`` tokens: 6 per
    matmul parameter per token (2 forward, 4 backward), and attention's two
    score-sized products, 2 x 2 s h forward a layer a token, three times
    that with the backward pass: 12 L s h a token."""
    s = traffic["seq_len"]
    per_token = (6 * matmul_params(config)
                 + 12 * config["num_hidden_layers"] * s
                 * config["hidden_size"])
    return per_token * s
