"""JoyAI-LLM-Flash cut to one chip's share of a 16-way expert deployment
(``models.mla_moe.MLAMoEDecoder``) at the sizes of the .json beside this
file, which states the cut; a test's toy configuration gives its own sizes
to the same code.  The plain reference is ``joyai_llm_flash_ref.py``; the
operation and byte counts of the two kernels whose roofline shares the
benchmark reports are at the end of this file."""
import math

import numpy as onp

from chipbench.configs import joyai_llm_flash_ref as reference  # noqa: F401


def build(seed, config):
    """The net on the host, initialised through Gluon from the seed the
    runner gave ``mx.random`` (every shape is given, nothing is deferred);
    the two-head loss and the optimizer of the configuration."""
    from incubator_mxnet_tpu import gluon, initializer
    from incubator_mxnet_tpu.models.mla_moe import MLAMoEDecoder
    net = MLAMoEDecoder(
        vocab_size=config["vocab_size"], units=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        dense_hidden_size=config["intermediate_size"],
        expert_hidden_size=config["moe_intermediate_size"],
        n_experts=config["router_outputs"],
        held=tuple(config["held_experts"]),
        top_k=config["num_experts_per_tok"],
        scale=config["routed_scaling_factor"],
        gamma=config["bias_update_gamma"],
        capacity_factor=config["buffer_factor"],
        first_dense=config["first_k_dense_replace"],
        n_shared=config["n_shared_experts"],
        epsilon=config["rms_norm_eps"])
    net.initialize(initializer.Normal(config["initializer_std"]))
    net.embed.weight.initialize(
        initializer.Normal(config["embedding_std"]), force_reinit=True)
    return {"net": net,
            "loss": gluon.loss.WeightedHeadsSoftmaxCELoss(
                (1.0, config["mtp_loss_weight"])),
            "optimizer": config["optimizer"],
            "optimizer_params": config["optimizer_params"]}


def make_batch(seed, i, batch, config, traffic):
    """Batch ``i`` of the pool for ``seed``: ``batch`` documents of
    ``seq_len + 2`` tokens from an order-1 Markov source over the held
    slice of the vocabulary, every token with ``successors`` equally likely
    successors (the table is the seed's, the same for every batch).  The
    net sees the first ``seq_len + 1``; the labels are the tokens one and
    two ahead, ``(batch, 2, seq_len)``."""
    vocab, fan, length = (config["vocab_size"], traffic["successors"],
                          traffic["seq_len"] + 2)
    table = onp.random.default_rng([seed, 2 ** 31]).integers(
        0, vocab, (vocab, fan), dtype=onp.int32)
    rng = onp.random.default_rng([seed, i])
    tokens = onp.empty((batch, length), onp.int32)
    tokens[:, 0] = rng.integers(0, vocab, batch)
    picks = rng.integers(0, fan, (batch, length))
    for t in range(1, length):
        tokens[:, t] = table[tokens[:, t - 1], picks[:, t]]
    labels = onp.stack([tokens[:, 1:-1], tokens[:, 2:]], axis=1)
    return tokens[:, :-1], labels


def n_classes(config):
    return config["vocab_size"]


def uniform_loss(config):
    """The loss of uniform logits in both heads: what the first step of a
    freshly initialised net reads, nearly."""
    return (1 + config["mtp_loss_weight"]) * math.log(config["vocab_size"])


def held_share(config):
    """Experts a token's row visits on this chip under balanced routing."""
    return (config["num_experts_per_tok"] * config["held_experts"][1]
            / config["router_outputs"])


def attention_params(config):
    h, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (h * config["q_lora_rank"] + config["q_lora_rank"] * heads * qk
            + h * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * heads
            * (config["qk_nope_head_dim"] + config["v_head_dim"])
            + heads * config["v_head_dim"] * h)


def expert_params(config):
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def routed_blocks(config):
    return (config["num_hidden_layers"] - config["first_k_dense_replace"]
            + config["num_nextn_predict_layers"])


def matmul_params(config):
    """Parameters that multiply every token of the cut model, the routed
    experts at the ``held_share`` a balanced router sends here: attention
    in every block; the dense layers' SwiGLU; in every routed block the
    router, the shared expert and the held experts' share; the MTP
    projection; the head once for each of the two logit tensors.  Embedding
    look-ups are not multiplications."""
    h = config["hidden_size"]
    dense = config["first_k_dense_replace"]
    routed = routed_blocks(config)
    return ((dense + routed) * attention_params(config)
            + dense * 3 * h * config["intermediate_size"]
            + routed * (h * config["router_outputs"]
                        + (config["n_shared_experts"] + held_share(config))
                        * expert_params(config))
            + config["num_nextn_predict_layers"] * 2 * h * h
            + (1 + config["num_nextn_predict_layers"]) * h
            * config["vocab_size"])


def attention_flops_per_token(config, seq_len):
    """Causal attention's two score-sized products, forward and backward
    (three times the forward), at half the square: 2 · (qk + v) · s / 2
    forward a head a token."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (3 * config["num_attention_heads"] * (qk + config["v_head_dim"])
            * seq_len)


def flops_per_sample(config, traffic):
    """Model FLOPs to train on one sequence of ``seq_len`` positions: 6 per
    matmul parameter a token, and causal attention in every block."""
    s = traffic["seq_len"]
    blocks = config["first_k_dense_replace"] + routed_blocks(config)
    return s * (6 * matmul_params(config)
                + blocks * attention_flops_per_token(config, s))


# ---- what the two kernels with a roofline share have to do, a step:
# the model's work (balanced load, real widths, the causal half), whatever
# implements it, and never the padding of a buffer

def moe_experts_work(config, traffic):
    """``(operations, bytes)`` of the held experts' SwiGLU over one step's
    rows, forward and backward, in every routed block.  Operations: 6 a
    parameter a routed row.  Bytes: every held expert's weights read in
    the forward and in the backward pass and their gradients written once
    (bfloat16), and each routed row's input, output and their gradients
    read or written once."""
    tokens = traffic["batch"] * traffic["seq_len"]
    rows = tokens * held_share(config)
    weights = config["held_experts"][1] * expert_params(config)
    blocks = routed_blocks(config)
    ops = blocks * 6 * expert_params(config) * rows
    moved = blocks * 2 * (3 * weights + 4 * rows * config["hidden_size"])
    return ops, moved


def mla_attention_work(config, traffic):
    """``(operations, bytes)`` of causal attention over one step, forward
    and backward, in every block: the score-sized products at half the
    square, 2 of them forward and 4 backward (a kernel that computes the
    probabilities again in its backward pass does a fifth, which is not
    the model's work and is not counted); q, k, v, the output and their
    four gradients read or written once, and q, k, v and the output read
    once more by the backward pass (bfloat16)."""
    b, s = traffic["batch"], traffic["seq_len"]
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    blocks = config["first_k_dense_replace"] + routed_blocks(config)
    ops = blocks * b * s * attention_flops_per_token(config, s)
    moved = blocks * 2 * b * heads * s * 3 * (2 * qk + 2 * v)
    return ops, moved
