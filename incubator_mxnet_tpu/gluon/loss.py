"""Loss functions (reference python/mxnet/gluon/loss.py)."""
from __future__ import annotations

from ..ndarray import NDArray
from ..ops.registry import invoke
from .block import HybridBlock

__all__ = ["Loss", "L2Loss", "L1Loss", "SigmoidBinaryCrossEntropyLoss",
           "SigmoidBCELoss", "SoftmaxCrossEntropyLoss", "SoftmaxCELoss",
           "WeightedHeadsSoftmaxCELoss",
           "KLDivLoss", "HuberLoss", "HingeLoss", "SquaredHingeLoss",
           "LogisticLoss", "TripletLoss", "CosineEmbeddingLoss", "CTCLoss",
           "PoissonNLLLoss", "SDMLLoss"]


def _apply_weighting(loss, weight=None, sample_weight=None):
    if sample_weight is not None:
        loss = loss * sample_weight
    if weight is not None:
        loss = loss * weight
    return loss


def _reshape_like(pred, label):
    if label.shape != pred.shape:
        label = label.reshape(pred.shape)
    return label


class Loss(HybridBlock):
    """Base loss (reference loss.py:54): weight + batch_axis, mean over
    non-batch axes."""

    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis

    def _mean(self, loss):
        axes = tuple(i for i in range(loss.ndim) if i != self._batch_axis)
        if axes:
            return invoke("mean", loss, axis=axes)
        return loss


class L2Loss(Loss):
    def __init__(self, weight=1.0, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = invoke("square", label - pred)
        loss = _apply_weighting(loss, self._weight / 2, sample_weight)
        return self._mean(loss)


class L1Loss(Loss):
    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = invoke("abs", label - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class SigmoidBinaryCrossEntropyLoss(Loss):
    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def forward(self, pred, label, sample_weight=None, pos_weight=None):
        label = _reshape_like(pred, label)
        if not self._from_sigmoid:
            # max(x,0) - x*z + log(1+exp(-|x|)) — numerically stable BCE
            loss = invoke("relu", pred) - pred * label + \
                invoke("log1p", invoke("exp", -invoke("abs", pred)))
            if pos_weight is not None:
                loss = loss + (pos_weight - 1) * label * (
                    invoke("log1p", invoke("exp", -invoke("abs", pred))) +
                    invoke("relu", -pred))
        else:
            eps = 1e-12
            loss = -(invoke("log", pred + eps) * label +
                     invoke("log", 1.0 - pred + eps) * (1.0 - label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """Softmax CE (reference loss.py SoftmaxCrossEntropyLoss)."""

    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._axis = axis
        self._sparse_label = sparse_label
        self._from_logits = from_logits

    def forward(self, pred, label, sample_weight=None):
        if (self._sparse_label and not self._from_logits and pred.ndim == 2
                and self._axis in (-1, 1)):
            # fused path: one Pallas pass, softmax never materialized
            # (ops/nn_ops.py softmax_xent; XLA fallback built in)
            loss = invoke("softmax_xent", pred, label)
            loss = invoke("reshape", loss, shape=(-1, 1))
            loss = _apply_weighting(loss, self._weight, sample_weight)
            return self._mean(loss)
        if not self._from_logits:
            pred = invoke("log_softmax", pred, axis=self._axis)
        if self._sparse_label:
            loss = -invoke("pick", pred, label, axis=self._axis, keepdims=True)
        else:
            label = _reshape_like(pred, label)
            loss = -invoke("sum", pred * label, axis=self._axis, keepdims=True)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class WeightedHeadsSoftmaxCELoss(Loss):
    """Softmax cross-entropy of several heads over one label tensor, each
    head with its weight: ``Σ_h weights[h] · CE(preds[h], label[:, h])``,
    as a model with a multi-token-prediction head trains (main head on the
    next token, the extra head on the one after, weight lambda).

    ``forward(*preds, label)``: every ``preds[h]`` is ``(B, T, classes)``
    and ``label`` ``(B, heads, T)``; returns the loss of each sequence,
    ``(B,)``.  ``takes_all_outputs`` tells ``fuse.FusedTrainStep`` to hand
    over every output of the net, not only the first."""

    takes_all_outputs = True

    def __init__(self, weights=(1.0, 0.3), batch_axis=0, **kwargs):
        super().__init__(None, batch_axis, **kwargs)
        self._weights = tuple(weights)

    def forward(self, *args):
        *preds, label = args
        assert len(preds) == len(self._weights)
        total = None
        for h, (pred, weight) in enumerate(zip(preds, self._weights)):
            rows = invoke("softmax_xent",
                          pred.reshape((-1, pred.shape[-1])),
                          label[:, h].reshape((-1,)))
            part = invoke("mean", rows.reshape(pred.shape[:2]).astype(
                "float32"), axis=1) * weight
            total = part if total is None else total + part
        return total


class KLDivLoss(Loss):
    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0,
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def forward(self, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = invoke("log_softmax", pred, axis=self._axis)
        loss = label * (invoke("log", label + 1e-12) - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class HuberLoss(Loss):
    def __init__(self, rho=1.0, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = invoke("abs", label - pred)
        loss = invoke("where", loss > self._rho,
                      loss - 0.5 * self._rho,
                      (0.5 / self._rho) * invoke("square", loss))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class HingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = invoke("relu", self._margin - pred * label)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class SquaredHingeLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = invoke("square", invoke("relu", self._margin - pred * label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class LogisticLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, label_format="signed",
                 **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._label_format = label_format

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        if self._label_format == "signed":
            label = (label + 1.0) / 2.0
        loss = invoke("relu", pred) - pred * label + \
            invoke("log1p", invoke("exp", -invoke("abs", pred)))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class TripletLoss(Loss):
    def __init__(self, margin=1, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, positive, negative, sample_weight=None):
        positive = _reshape_like(pred, positive)
        negative = _reshape_like(pred, negative)
        loss = invoke("sum", invoke("square", pred - positive) -
                      invoke("square", pred - negative),
                      axis=tuple(range(1, pred.ndim)))
        loss = invoke("relu", loss + self._margin)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return loss


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, margin=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, input1, input2, label, sample_weight=None):
        def cos_sim(a, b):
            num = invoke("sum", a * b, axis=-1)
            den = invoke("norm", a, axis=-1) * invoke("norm", b, axis=-1)
            return num / (den + 1e-12)

        sim = cos_sim(input1, input2)
        label = label.reshape((-1,))
        loss = invoke("where", label == 1, 1.0 - sim,
                      invoke("relu", sim - self._margin))
        return _apply_weighting(loss, self._weight, sample_weight)


class CTCLoss(Loss):
    """CTC (reference loss.py CTCLoss; op src/operator/nn/ctc_loss.cc)."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None, **kwargs):
        super().__init__(weight, 0, **kwargs)
        self._layout = layout
        self._label_layout = label_layout

    def forward(self, pred, label, pred_lengths=None, label_lengths=None,
                sample_weight=None):
        from .. import ndarray as nd
        if self._layout == "NTC":
            pred = pred.transpose((1, 0, 2))
        if self._label_layout == "TN":
            label = label.transpose((1, 0))
        B = pred.shape[1]
        if pred_lengths is None:
            pred_lengths = nd.full((B,), pred.shape[0], dtype="int32",
                                   ctx=pred.ctx)
        if label_lengths is None:
            label_lengths = nd.full((B,), label.shape[1], dtype="int32",
                                    ctx=pred.ctx)
        loss = invoke("ctc_loss", pred, label, pred_lengths, label_lengths)
        return _apply_weighting(loss, self._weight, sample_weight)


class PoissonNLLLoss(Loss):
    """Poisson negative log likelihood (reference loss.py:800):
    from_logits → exp(pred) - target*pred; else pred - target*log(pred+eps);
    compute_full adds the Stirling approximation for target > 1."""

    def __init__(self, weight=None, from_logits=True, batch_axis=0,
                 compute_full=False, **kwargs):
        super().__init__(weight=weight, batch_axis=batch_axis, **kwargs)
        self._from_logits = from_logits
        self._compute_full = compute_full

    def forward(self, pred, target, sample_weight=None, epsilon=1e-08):
        import math as _math
        target = _reshape_like(pred, target)
        if self._from_logits:
            loss = invoke("exp", pred) - target * pred
        else:
            loss = pred - target * invoke("log", pred + epsilon)
        if self._compute_full:
            # guard the masked-out region: 0*log(0) would NaN the whole
            # mean even though the mask zeroes it (the reference formula
            # has this hazard; evaluate Stirling on clamped targets)
            safe_t = invoke("maximum", target, invoke("ones_like", target))
            stirling = (safe_t * invoke("log", safe_t) - safe_t
                        + 0.5 * invoke("log", 2 * _math.pi * safe_t))
            loss = loss + stirling * (target > 1)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return invoke("mean", loss)


class SDMLLoss(Loss):
    """Batchwise Smoothed Deep Metric Learning loss (reference
    loss.py:935): aligned batches x1/x2, softmax over negative pairwise
    euclidean distances against a label-smoothed identity target via KL
    divergence (Pereyra et al., arXiv:1701.06548)."""

    def __init__(self, smoothing_parameter=0.3, weight=1.0, batch_axis=0,
                 **kwargs):
        super().__init__(weight=weight, batch_axis=batch_axis, **kwargs)
        self.kl_loss = KLDivLoss(from_logits=True)
        self.smoothing_parameter = smoothing_parameter

    def forward(self, x1, x2):
        batch_size, dim = x1.shape
        # distances/labels via recorded ops so gradients flow
        x1e = invoke("broadcast_to", invoke("expand_dims", x1, axis=1),
                     shape=(batch_size, batch_size, dim))
        x2e = invoke("broadcast_to", invoke("expand_dims", x2, axis=0),
                     shape=(batch_size, batch_size, dim))
        distances = invoke("sum", invoke("square", x1e - x2e), axis=2)
        gold = invoke("eye", N=batch_size)
        labels = (gold * (1 - self.smoothing_parameter)
                  + (1 - gold) * self.smoothing_parameter
                  / (batch_size - 1))
        log_probs = invoke("log_softmax", -distances, axis=1)
        return self.kl_loss(log_probs, labels) * batch_size
