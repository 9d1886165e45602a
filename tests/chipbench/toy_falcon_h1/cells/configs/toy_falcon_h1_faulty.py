"""The toy configuration with one fault planted in the *system* and none in
the reference, so that the tests can see the comparison that decides
``correct`` refuse it.  ``TOY_FALCON_H1_FAULT`` (a name of ``FAULTS``; the
tests set it, nothing else reads it) chooses the fault; without it this is
``toy_falcon_h1`` again."""
import os

from chipbench.configs import falcon_h1_34b as sound
from chipbench.configs.falcon_h1_34b import (  # noqa: F401
    flops_per_sample, gqa_attention_work, make_batch, n_classes, reference,
    ssm_scan_work, uniform_loss)

FAULTS = ("state_left_unchanged", "learning_rate_times_three",
          "half_the_sequence", "labels_one_late", "mixer_branch_dropped",
          "head_multiplier_left_out")


def build(seed, config):
    fault = os.environ.get("TOY_FALCON_H1_FAULT", "")
    assert fault in FAULTS + ("",), fault
    changed = dict(config)
    if fault == "mixer_branch_dropped":
        changed["ssm_out_multiplier"] = 0.0
    if fault == "head_multiplier_left_out":
        changed["lm_head_multiplier"] = 1.0
    built = sound.build(seed, changed)
    rate = {"state_left_unchanged": 0.0, "learning_rate_times_three": 3.0}
    if fault in rate:           # the optimizer exists in the timed step only
        built["optimizer_params"] = dict(
            built["optimizer_params"], learning_rate=rate[fault]
            * config["optimizer_params"]["learning_rate"])
    if fault in ("half_the_sequence", "labels_one_late"):
        built["loss"] = _faulty_loss(fault)
    return built


def _faulty_loss(fault):
    """The next-token loss over the first half of the positions only, or
    against the token after the next."""
    from incubator_mxnet_tpu import gluon

    class FaultyLoss(gluon.loss.WeightedHeadsSoftmaxCELoss):
        def forward(self, pred, label):
            if fault == "half_the_sequence":
                half = pred.shape[1] // 2
                return super().forward(pred[:, :half], label[:, :, :half])
            return super().forward(pred[:, :-1], label[:, :, 1:])

    return FaultyLoss((1.0,))
