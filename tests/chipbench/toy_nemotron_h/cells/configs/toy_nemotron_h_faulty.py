"""The toy configuration with one fault planted in the *system* and none in
the reference, so that the tests can see the comparison that decides
``correct`` refuse it.  ``TOY_NEMOTRON_H_FAULT`` (a name of ``FAULTS``; the
tests set it, nothing else reads it) chooses the fault; without it this is
``toy_nemotron_h`` again."""
import os

from chipbench.configs import nemotron3_super_120b as sound
from chipbench.configs.nemotron3_super_120b import (  # noqa: F401
    flops_per_sample, gqa_attention_work, latent_moe_experts_work,
    make_batch, n_classes, reference, ssm_scan_work, uniform_loss)

FAULTS = ("latent_projection_skipped", "relu2_taken_as_relu",
          "top_k_taken_as_half", "scaling_factor_left_out", "rotary_applied",
          "gate_after_norm", "state_left_unchanged")


def build(seed, config):
    fault = os.environ.get("TOY_NEMOTRON_H_FAULT", "")
    assert fault in FAULTS + ("",), fault
    changed = dict(config)
    if fault == "scaling_factor_left_out":
        changed["routed_scaling_factor"] = 1.0
    if fault == "top_k_taken_as_half":
        changed["num_experts_per_tok"] = config["num_experts_per_tok"] // 2
    built = sound.build(seed, changed)
    if fault == "state_left_unchanged":     # the optimizer is the step's alone
        built["optimizer_params"] = dict(built["optimizer_params"],
                                         learning_rate=0.0)
    _plant(fault, built["net"])
    return built


def _plant(fault, net):
    """Change what the system computes, in place, and nothing it holds."""
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.ops.registry import invoke

    class SlicedDown(nn.Dense):
        # the experts read the input's first columns, not its projection
        def forward(self, x):
            return x[..., :self._units] + 0.0 * super().forward(x)

    class ReLU(nn.ReLU2MLP):
        def forward(self, x):
            return self.down(invoke("relu", self.up(x)))

    class GateAfterNorm(nn.Mamba2Mixer):
        def gated(self, y, z):
            return self.norm(y) * invoke("silu", z)

    def visit(block):
        for child in block._children.values():
            visit(child)
        if fault == "rotary_applied" and isinstance(
                block, nn.GroupedQueryAttention):
            block._theta = 10000.0
        if fault == "gate_after_norm" and isinstance(block, nn.Mamba2Mixer):
            block.__class__ = GateAfterNorm
        if fault == "relu2_taken_as_relu" and isinstance(block, nn.ReLU2MLP):
            block.__class__ = ReLU
        if fault == "latent_projection_skipped" and isinstance(
                block, nn.RoutedFFN):
            block.latent_down.__class__ = SlicedDown

    visit(net)
