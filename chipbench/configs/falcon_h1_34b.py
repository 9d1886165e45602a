"""Falcon-H1-34B-Instruct cut to one pipeline stage of four whole layers
(``models.falcon_h1.FalconH1Decoder``) at the sizes of the .json beside this
file, which states the cut; a test's toy configuration gives its own sizes
to the same code.  The plain reference is ``falcon_h1_34b_ref.py``; the
operation and byte counts of the two kernels whose roofline shares the
benchmark reports are at the end of this file."""
import math

from chipbench.configs import falcon_h1_34b_ref as reference  # noqa: F401
from chipbench.configs.joyai_llm_flash import make_batch as markov_batch


def build(seed, config):
    """The net on the host, initialised through Gluon from the seed the
    runner gave ``mx.random`` (every shape is given, nothing is deferred);
    the next-token loss and the optimizer of the configuration."""
    from incubator_mxnet_tpu import gluon, initializer
    from incubator_mxnet_tpu.models.falcon_h1 import FalconH1Decoder
    net = FalconH1Decoder(
        vocab_size=config["vocab_size"], units=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=config["rope_theta"],
        hidden_size=config["intermediate_size"],
        d_ssm=config["mamba_d_ssm"], ssm_heads=config["mamba_n_heads"],
        d_state=config["mamba_d_state"], n_groups=config["mamba_n_groups"],
        d_conv=config["mamba_d_conv"], chunk_size=config["mamba_chunk_size"],
        epsilon=config["rms_norm_eps"],
        **{key: config[key] for key in (
            "embedding_multiplier", "lm_head_multiplier", "ssm_in_multiplier",
            "ssm_out_multiplier", "attention_in_multiplier",
            "attention_out_multiplier", "key_multiplier", "mlp_multipliers",
            "ssm_multipliers")},
        recompute=config["recompute"] == "blocks")
    net.initialize(initializer.Normal(config["initializer_std"]))
    return {"net": net,
            "loss": gluon.loss.WeightedHeadsSoftmaxCELoss((1.0,)),
            "optimizer": config["optimizer"],
            "optimizer_params": config["optimizer_params"]}


def make_batch(seed, i, batch, config, traffic):
    """Batch ``i`` of the pool for ``seed``: ``batch`` documents from PR 30's
    order-1 Markov source over the held slice of the vocabulary
    (``joyai_llm_flash.make_batch``: every token with ``successors`` equally
    likely successors, the table the seed's).  The net sees the first
    ``seq_len`` tokens; the labels are the tokens one ahead, ``(batch, 1,
    seq_len)``."""
    tokens, labels = markov_batch(seed, i, batch, config, traffic)
    return tokens[:, :-1], labels[:, :1]


def n_classes(config):
    return config["vocab_size"]


def uniform_loss(config):
    """The loss of uniform logits: what the first step of a freshly
    initialised net reads, nearly."""
    return math.log(config["vocab_size"])


def attention_params(config):
    h, d = config["hidden_size"], config["head_dim"]
    return (2 * h * d * config["num_attention_heads"]
            + 2 * h * d * config["num_key_value_heads"])


def mixer_matmul_params(config):
    """The mixer's two projections; its convolution, decay, skip and norm
    multiply no token by a matrix."""
    d = config["mamba_d_ssm"]
    return config["hidden_size"] * (
        2 * d + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
        + config["mamba_n_heads"] + d)


def layer_params(config):
    """Every parameter of one block."""
    h, d = config["hidden_size"], config["mamba_d_ssm"]
    conv = d + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return (attention_params(config) + mixer_matmul_params(config)
            + conv * (config["mamba_d_conv"] + 1)
            + 3 * config["mamba_n_heads"] + d
            + 3 * h * config["intermediate_size"] + 2 * h)


def total_params(config):
    h = config["hidden_size"]
    return (config["num_hidden_layers"] * layer_params(config)
            + 2 * config["vocab_size"] * h + h)


def matmul_params(config):
    """Parameters that multiply every token of the cut model: attention, the
    mixer's projections and the MLP in every block, and the head.  Embedding
    look-ups are not multiplications."""
    h = config["hidden_size"]
    return (config["num_hidden_layers"] * (
        attention_params(config) + mixer_matmul_params(config)
        + 3 * h * config["intermediate_size"])
        + h * config["vocab_size"])


def attention_flops_per_token(config, seq_len):
    """Causal attention's two score-sized products, forward and backward
    (three times the forward), at half the square: 2 · 2 · D · s / 2 forward
    a query head a token."""
    return (3 * config["num_attention_heads"] * 2 * config["head_dim"]
            * seq_len)


def scan_flops_per_token(config):
    """The chunked scan's four products a token a layer, forward and
    backward (three times the forward): scores ``C Bᵀ`` a group (2 Q N) and
    ``(L ⊙ C Bᵀ)(Δ x)`` a head (2 Q P) at half the chunk's square — the
    masked half of a diagonal block is no work —, the chunk's own state and
    the carried state's output a head (2 P N each)."""
    q, n, p = (config["mamba_chunk_size"], config["mamba_d_state"],
               config["mamba_d_head"])
    heads, groups = config["mamba_n_heads"], config["mamba_n_groups"]
    return 3 * (groups * q * n + heads * q * p + heads * 4 * p * n)


def flops_per_sample(config, traffic):
    """Model FLOPs to train on one document of ``seq_len`` positions: 6 per
    matmul parameter a token, and causal attention and the chunked scan in
    every block.  What the backward pass computes again is not counted."""
    s = traffic["seq_len"]
    return s * (6 * matmul_params(config) + config["num_hidden_layers"] * (
        attention_flops_per_token(config, s) + scan_flops_per_token(config)))


# ---- what the two kernels with a roofline share have to do, a step: the
# model's work at the published chunk and widths, whatever implements it,
# and never padding or what is run twice

def ssm_scan_work(config, traffic):
    """``(operations, bytes)`` of ``ssd_scan`` over one step, forward and
    backward, in every block.  Operations: :func:`scan_flops_per_token`.
    Bytes: x, B, C and y and their four gradients read or written once, and
    x, B and C read once more by the backward pass (bfloat16); Δ, read
    twice, and its gradient (float32).  The chunk states are not counted:
    whether they travel through memory or are computed again is the
    implementation's choice."""
    tokens = traffic["batch"] * traffic["seq_len"]
    d = config["mamba_d_ssm"]
    bc = config["mamba_n_groups"] * config["mamba_d_state"]
    ops = config["num_hidden_layers"] * tokens * scan_flops_per_token(config)
    per_token = (2 * (3 * (d + 2 * bc) + 2 * d)
                 + 4 * 3 * config["mamba_n_heads"])
    return ops, config["num_hidden_layers"] * tokens * per_token


def gqa_attention_work(config, traffic):
    """``(operations, bytes)`` of causal attention over one step, forward
    and backward, in every block: the two forward and four backward
    score-sized products at half the square (a kernel that computes the
    probabilities again in its backward pass does a fifth, which is not the
    model's work and is not counted); q, the output and their gradients read
    or written once, q and the output read once more by the backward pass;
    k and v, read once a group, and their gradients likewise (bfloat16)."""
    b, s = traffic["batch"], traffic["seq_len"]
    d = config["head_dim"]
    ops = (config["num_hidden_layers"] * b * s
           * attention_flops_per_token(config, s))
    moved = (config["num_hidden_layers"] * 2 * b * s * d * 3 * 2
             * (config["num_attention_heads"]
                + config["num_key_value_heads"]))
    return ops, moved
