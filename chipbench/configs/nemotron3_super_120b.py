"""NVIDIA-Nemotron-3-Super-120B-A12B cut to one chip's share of a 32-way
expert deployment (``models.nemotron_h.NemotronHDecoder``) at the sizes of
the .json beside this file, which states the cut; a test's toy configuration
gives its own sizes to the same code.  The plain reference is
``nemotron3_super_120b_ref.py``; the operation and byte counts of the three
kernels whose roofline shares the benchmark reports are at the end of this
file."""
import math

from chipbench.configs import falcon_h1_34b as _hybrid
from chipbench.configs import nemotron3_super_120b_ref as reference  # noqa: F401
from chipbench.configs.joyai_llm_flash import make_batch  # noqa: F401


def build(seed, config):
    """The net on the host, initialised through Gluon from the seed the
    runner gave ``mx.random`` (every shape is given, nothing is deferred);
    the two-head loss and the optimizer of the configuration.  Every matrix
    starts at ``initializer_std``; the projections that write a branch's
    result into the residual stream at ``residual_out_std`` (the
    configuration's ``rescale_prenorm_residual``) and the embedding at
    ``embedding_std``; the mixer's own parameters as Mamba-2's do."""
    from incubator_mxnet_tpu import gluon, initializer
    from incubator_mxnet_tpu.models.nemotron_h import NemotronHDecoder
    net = NemotronHDecoder(
        vocab_size=config["vocab_size"], units=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        mtp_pattern=config["mtp_hybrid_override_pattern"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ssm=config["mamba_num_heads"] * config["mamba_head_dim"],
        ssm_heads=config["mamba_num_heads"],
        d_state=config["ssm_state_size"], n_groups=config["n_groups"],
        d_conv=config["conv_kernel"], chunk_size=config["chunk_size"],
        expert_hidden_size=config["moe_intermediate_size"],
        latent_size=config["moe_latent_size"],
        shared_hidden_size=config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        n_experts=config["router_outputs"],
        held=tuple(config["held_experts"]),
        top_k=config["num_experts_per_tok"],
        scale=config["routed_scaling_factor"],
        gamma=config["bias_update_gamma"],
        capacity_factor=config["buffer_factor"],
        epsilon=config["layer_norm_epsilon"],
        recompute=config["recompute"] == "layers")
    # the exceptions first: initialize() leaves alone what is initialised
    net.embed.weight.initialize(initializer.Normal(config["embedding_std"]))
    out = initializer.Normal(config["residual_out_std"])
    for name, param in net.collect_params().items():
        if name.endswith(("out_proj.weight", "attn.o.weight",
                          "latent_up.weight", "shared.down.weight")):
            param.initialize(out)
    net.initialize(initializer.Normal(config["initializer_std"]))
    return {"net": net,
            "loss": gluon.loss.WeightedHeadsSoftmaxCELoss(
                (1.0, config["mtp_loss_weight"])),
            "optimizer": config["optimizer"],
            "optimizer_params": config["optimizer_params"]}


def n_classes(config):
    return config["vocab_size"]


def uniform_loss(config):
    """What the first step of a freshly initialised net reads, nearly, in
    both heads: the loss of uniform logits, ``ln V``, and half the variance
    of the logits the head's initial weights give a normed input
    (``initializer_std² · hidden_size``: independent Gaussian logits read
    ``ln V + σ²/2``)."""
    spread = config["initializer_std"] ** 2 * config["hidden_size"]
    return (1 + config["mtp_loss_weight"]) * (
        math.log(config["vocab_size"]) + spread / 2)


def kinds(config):
    """How many layers of each kind the cut model runs, the MTP body's
    included: ``{"M": 5, "E": 6, "*": 2}``."""
    pattern = (config["hybrid_override_pattern"]
               + config["mtp_hybrid_override_pattern"]
               * config["num_nextn_predict_layers"])
    return {kind: pattern.count(kind) for kind in "ME*"}


def held_share(config):
    """Experts a token's row visits on this chip under balanced routing."""
    return (config["num_experts_per_tok"] * config["held_experts"][1]
            / config["router_outputs"])


def expert_params(config):
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def _as_hybrid(config, layers):
    """The keys ``falcon_h1_34b``'s counts read, for ``layers`` layers of
    this configuration's attention and mixer."""
    return {"hidden_size": config["hidden_size"],
            "head_dim": config["head_dim"],
            "num_attention_heads": config["num_attention_heads"],
            "num_key_value_heads": config["num_key_value_heads"],
            "mamba_d_ssm": config["mamba_num_heads"]
            * config["mamba_head_dim"],
            "mamba_d_head": config["mamba_head_dim"],
            "mamba_n_heads": config["mamba_num_heads"],
            "mamba_n_groups": config["n_groups"],
            "mamba_d_state": config["ssm_state_size"],
            "mamba_chunk_size": config["chunk_size"],
            "mamba_d_conv": config["conv_kernel"],
            "num_hidden_layers": layers}


def layer_params(config):
    """Every parameter of one layer of each kind, its norm included; ``E``
    as ``(outside the experts, one expert)``.  The router's selection bias
    is the published model's ``e_score_correction_bias`` and is counted; the
    five counters of ``moe_stats`` are not the model's."""
    h = config["hidden_size"]
    one = _as_hybrid(config, 1)
    conv = one["mamba_d_ssm"] + 2 * one["mamba_n_groups"] * one[
        "mamba_d_state"]
    mamba = (_hybrid.mixer_matmul_params(one)
             + conv * (config["conv_kernel"] + 1)
             + 3 * config["mamba_num_heads"] + one["mamba_d_ssm"] + h)
    outside = (config["router_outputs"] * (h + 1)
               + 2 * h * config["moe_latent_size"]
               + 2 * h * config["n_shared_experts"]
               * config["moe_shared_expert_intermediate_size"] + h)
    return {"M": mamba, "*": _hybrid.attention_params(one) + h,
            "E": (outside, expert_params(config))}


def total_params(config):
    """The cut model's parameters: the trunk's layers, the embedding, the
    head and the final norm, and the MTP module (its layers, its 2h → h
    projection and three norms)."""
    h, per = config["hidden_size"], layer_params(config)
    outside, expert = per["E"]
    moe = outside + config["held_experts"][1] * expert
    count = lambda pattern: sum(
        moe if kind == "E" else per[kind] for kind in pattern)
    return (count(config["hybrid_override_pattern"])
            + 2 * config["vocab_size"] * h + h
            + config["num_nextn_predict_layers"] * (
                count(config["mtp_hybrid_override_pattern"])
                + 2 * h * h + 3 * h))


def matmul_params(config):
    """Parameters that multiply every token of the cut model, the routed
    experts at the ``held_share`` a balanced router sends here: attention's
    four projections; the mixer's two; in every routed layer the router, the
    two latent projections, the shared expert and the held experts' share;
    the MTP projection; the head once for each of the two logit tensors.
    Embedding look-ups are not multiplications."""
    h, n = config["hidden_size"], kinds(config)
    one = _as_hybrid(config, 1)
    moe = (h * config["router_outputs"] + 2 * h * config["moe_latent_size"]
           + 2 * h * config["n_shared_experts"]
           * config["moe_shared_expert_intermediate_size"]
           + held_share(config) * expert_params(config))
    return (n["*"] * _hybrid.attention_params(one)
            + n["M"] * _hybrid.mixer_matmul_params(one) + n["E"] * moe
            + config["num_nextn_predict_layers"] * 2 * h * h
            + (1 + config["num_nextn_predict_layers"]) * h
            * config["vocab_size"])


def flops_per_sample(config, traffic):
    """Model FLOPs to train on one document of ``seq_len`` positions: 6 per
    matmul parameter a token (the held experts only), causal attention in
    every ``*`` layer and the chunked scan in every ``M`` layer.  What the
    backward pass computes again is not counted."""
    s, n = traffic["seq_len"], kinds(config)
    one = _as_hybrid(config, 1)
    return s * (6 * matmul_params(config)
                + n["*"] * _hybrid.attention_flops_per_token(one, s)
                + n["M"] * _hybrid.scan_flops_per_token(one))


# ---- what the three kernels with a roofline share have to do, a step: the
# model's work at the published widths (balanced load, the causal half, the
# published chunk), whatever implements it, and never padding or what is run
# twice

def ssm_scan_work(config, traffic):
    """``(operations, bytes)`` of ``ssd_scan`` over one step, forward and
    backward, in every ``M`` layer: ``falcon_h1_34b.ssm_scan_work`` at this
    configuration's 128 heads x 64, 8 groups, state 128."""
    return _hybrid.ssm_scan_work(_as_hybrid(config, kinds(config)["M"]),
                                 traffic)


def gqa_attention_work(config, traffic):
    """``(operations, bytes)`` of causal attention over one step, forward
    and backward, in every ``*`` layer (the MTP body's too):
    ``falcon_h1_34b.gqa_attention_work`` at 32 query over 2 key heads."""
    return _hybrid.gqa_attention_work(_as_hybrid(config, kinds(config)["*"]),
                                      traffic)


def latent_moe_experts_work(config, traffic):
    """``(operations, bytes)`` of the held experts' two ungated products over
    one step's rows, forward and backward, in every ``E`` layer.
    Operations: 6 a parameter a routed row.  Bytes: every held expert's
    weights read once in the forward and once in the backward pass and their
    gradients written once (bfloat16), and each routed row's latent input,
    output and their gradients read or written once."""
    tokens = traffic["batch"] * traffic["seq_len"]
    rows = tokens * held_share(config)
    weights = config["held_experts"][1] * expert_params(config)
    layers = kinds(config)["E"]
    ops = layers * 6 * expert_params(config) * rows
    moved = layers * 2 * (3 * weights + 4 * rows * config["moe_latent_size"])
    return ops, moved
