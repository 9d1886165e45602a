"""ResNet V1/V2 (reference gluon/model_zoo/vision/resnet.py).

The flagship benchmark model (BASELINE config 2: ResNet-50).  Identical
architecture to the reference zoo: V1 = post-activation (He et al. 2015),
V2 = pre-activation (He et al. 2016), thumbnail variant for CIFAR.

TPU extensions (reference-compatible additions, not divergences):
- ``layout="NHWC"``: channel-minor data layout end to end (the
  reference's Conv2D layout knob, its cuDNN fp16 fast path; here the
  layout the Pallas fused-block kernels read).
- ``fused=True`` (+ NHWC): bottleneck training forwards run the fused
  Pallas path (ops/fused_block.py + ops/fused_conv.py) — convs emit BN
  batch stats from their epilogues and apply the previous BN's
  normalize+ReLU in their prologues, eliminating the BN-structured HBM
  traffic the round-4 roofline identified.  BottleneckV1 (resnet
  50/101/152 v1) and the pre-activation BottleneckV2 (v2 family, whose
  bn->relu->conv ordering maps directly onto the prologue) are both
  covered; stride-2 v2 3x3s keep an XLA conv (the kernel is s1-only).
"""
from __future__ import annotations

from ... import nn
from ...block import HybridBlock, register_state_update
from ....ops.registry import invoke

__all__ = ["ResNetV1", "ResNetV2", "get_resnet",
           "resnet18_v1", "resnet34_v1", "resnet50_v1", "resnet101_v1",
           "resnet152_v1", "resnet18_v2", "resnet34_v2", "resnet50_v2",
           "resnet101_v2", "resnet152_v2"]


def _bn_axis(layout):
    return -1 if layout == "NHWC" else 1


def _check_fused(fused, layout, cls):
    """fused=True must never silently degrade to the plain path: a
    measurement of a net built with fused=True has to mean that the
    fused kernels actually ran."""
    if not fused:
        return
    if cls not in ("BottleneckV1", "BottleneckV2"):
        raise ValueError(
            f"fused=True is implemented for the bottleneck blocks only "
            f"(ResNet-50/101/152 v1 and v2); {cls} has no fused path")
    if layout != "NHWC":
        raise ValueError(
            "fused=True requires layout='NHWC' (the fused matmul+BN "
            "kernels read channel-minor [M, C] views)")


def _conv3x3(channels, stride, in_channels, layout="NCHW"):
    return nn.Conv2D(channels, kernel_size=3, strides=stride, padding=1,
                     use_bias=False, in_channels=in_channels, layout=layout)


class BasicBlockV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        _check_fused(fused, layout, type(self).__name__)
        ax = _bn_axis(layout)
        self.body = nn.HybridSequential()
        self.body.add(_conv3x3(channels, stride, in_channels, layout))
        self.body.add(nn.BatchNorm(axis=ax))
        self.body.add(nn.Activation("relu"))
        self.body.add(_conv3x3(channels, 1, channels, layout))
        self.body.add(nn.BatchNorm(axis=ax))
        if downsample:
            self.downsample = nn.HybridSequential()
            self.downsample.add(nn.Conv2D(channels, kernel_size=1,
                                          strides=stride, use_bias=False,
                                          in_channels=in_channels,
                                          layout=layout))
            self.downsample.add(nn.BatchNorm(axis=ax))
        else:
            self.downsample = None
        self.relu = nn.Activation("relu")

    def forward(self, x):
        residual = x
        x_out = self.body(x)
        if self.downsample is not None:
            residual = self.downsample(residual)
        return self.relu(x_out + residual)


def _bn_args(bn):
    return (bn.gamma.data(), bn.beta.data(),
            bn.running_mean.data(), bn.running_var.data())


def _bns_uniform(bns):
    """The fused registry ops take ONE eps/momentum and always use
    batch stats; a BN mutated after construction (use_global_stats, or
    a differing eps/momentum) must route the block through the layer
    path instead of being silently mis-normalized (ADVICE r4)."""
    ref = bns[0]
    return all(not getattr(bn, "_use_global_stats", False)
               and bn._epsilon == ref._epsilon
               and bn._momentum == ref._momentum for bn in bns)


def _invoke_fused_bottleneck(x, op, pairs, extra_args, state_bns, stride):
    """Assemble (x, [w_i, bn_i params]..., extra) for a fused-bottleneck
    registry op, invoke it, and route the returned moving stats through
    register_state_update (the BatchNorm contract).  Shared by the V1
    and V2 blocks so the arg marshaling cannot drift."""
    from ....ops import fused_block  # noqa: F401 — registers the ops
    args = [x]
    for conv, bn in pairs:
        args.append(conv.weight.data())
        args.extend(_bn_args(bn))
    args.extend(extra_args)
    outs = invoke(op, *args, stride=stride, eps=pairs[0][1]._epsilon,
                  momentum=pairs[0][1]._momentum)
    for i, bn in enumerate(state_bns):
        register_state_update(bn.running_mean, outs[1 + 2 * i])
        register_state_update(bn.running_var, outs[2 + 2 * i])
    return outs[0]


class BottleneckV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        _check_fused(fused, layout, "BottleneckV1")
        ax = _bn_axis(layout)
        self._stride = stride
        self._fused = bool(fused)
        self.body = nn.HybridSequential()
        self.body.add(nn.Conv2D(channels // 4, kernel_size=1, strides=stride,
                                use_bias=False, layout=layout))
        self.body.add(nn.BatchNorm(axis=ax))
        self.body.add(nn.Activation("relu"))
        self.body.add(_conv3x3(channels // 4, 1, channels // 4, layout))
        self.body.add(nn.BatchNorm(axis=ax))
        self.body.add(nn.Activation("relu"))
        self.body.add(nn.Conv2D(channels, kernel_size=1, strides=1,
                                use_bias=False, layout=layout))
        self.body.add(nn.BatchNorm(axis=ax))
        if downsample:
            self.downsample = nn.HybridSequential()
            self.downsample.add(nn.Conv2D(channels, kernel_size=1,
                                          strides=stride, use_bias=False,
                                          in_channels=in_channels,
                                          layout=layout))
            self.downsample.add(nn.BatchNorm(axis=ax))
        else:
            self.downsample = None
        self.relu = nn.Activation("relu")

    def _finish_deferred(self, x):
        """Resolve deferred parameter shapes without running the body
        (the fused path bypasses the child layers' forwards)."""
        ci = x.shape[-1]
        cm = self.body[0]._channels
        co = self.body[6]._channels
        for conv, cin in ((self.body[0], ci), (self.body[3], cm),
                          (self.body[6], cm)):
            if conv.weight._data is None:
                conv.weight.shape = ((conv._channels,) + conv._kernel
                                     + (cin // conv._groups,))
                conv.weight._finish_deferred_init()
        for bn, c in ((self.body[1], cm), (self.body[4], cm),
                      (self.body[7], co)):
            for p in (bn.gamma, bn.beta, bn.running_mean, bn.running_var):
                if p._data is None:
                    p.shape = (c,)
                    p._finish_deferred_init()
        if self.downsample is not None:
            dconv, dbn = self.downsample[0], self.downsample[1]
            if dconv.weight._data is None:
                dconv.weight.shape = ((dconv._channels,) + dconv._kernel
                                      + (ci // dconv._groups,))
                dconv.weight._finish_deferred_init()
            for p in (dbn.gamma, dbn.beta, dbn.running_mean,
                      dbn.running_var):
                if p._data is None:
                    p.shape = (co,)
                    p._finish_deferred_init()

    def _forward_fused(self, x):
        self._finish_deferred(x)
        bn1, bn2, bn3 = self.body[1], self.body[4], self.body[7]
        pairs = ((self.body[0], bn1), (self.body[3], bn2),
                 (self.body[6], bn3))
        if self.downsample is not None:
            dconv, dbn = self.downsample[0], self.downsample[1]
            return _invoke_fused_bottleneck(
                x, "_fused_bottleneck_v1_proj", pairs,
                (dconv.weight.data(),) + _bn_args(dbn),
                (bn1, bn2, bn3, dbn), self._stride)
        return _invoke_fused_bottleneck(
            x, "_fused_bottleneck_v1", pairs, (), (bn1, bn2, bn3),
            self._stride)

    def _fused_bns_uniform(self):
        bns = [self.body[1], self.body[4], self.body[7]]
        if self.downsample is not None:
            bns.append(self.downsample[1])
        return _bns_uniform(bns)

    def forward(self, x):
        if self._fused:
            from .... import autograd
            if autograd.is_training() and self._fused_bns_uniform():
                return self._forward_fused(x)
        residual = x
        x_out = self.body(x)
        if self.downsample is not None:
            residual = self.downsample(residual)
        return self.relu(x_out + residual)


class BasicBlockV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        _check_fused(fused, layout, "BasicBlockV2")
        ax = _bn_axis(layout)
        self.bn1 = nn.BatchNorm(axis=ax)
        self.conv1 = _conv3x3(channels, stride, in_channels, layout)
        self.bn2 = nn.BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels, 1, channels, layout)
        self.relu = nn.Activation("relu")
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride, use_bias=False,
                                        in_channels=in_channels,
                                        layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.relu(self.bn1(x))
        if self.downsample is not None:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.relu(self.bn2(x))
        x = self.conv2(x)
        return x + residual


class BottleneckV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        _check_fused(fused, layout, "BottleneckV2")
        ax = _bn_axis(layout)
        self._stride = stride
        self._fused = bool(fused)
        self.bn1 = nn.BatchNorm(axis=ax)
        self.conv1 = nn.Conv2D(channels // 4, 1, 1, use_bias=False,
                               layout=layout)
        self.bn2 = nn.BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels // 4, stride, channels // 4, layout)
        self.bn3 = nn.BatchNorm(axis=ax)
        self.conv3 = nn.Conv2D(channels, 1, 1, use_bias=False, layout=layout)
        self.relu = nn.Activation("relu")
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride, use_bias=False,
                                        in_channels=in_channels,
                                        layout=layout)
        else:
            self.downsample = None

    def _finish_deferred(self, x):
        """Resolve deferred parameter shapes without running the child
        layers (the fused path bypasses their forwards)."""
        ci = x.shape[-1]
        cm = self.conv1._channels
        co = self.conv3._channels
        for conv, cin in ((self.conv1, ci), (self.conv2, cm),
                          (self.conv3, cm)):
            if conv.weight._data is None:
                conv.weight.shape = ((conv._channels,) + conv._kernel
                                     + (cin // conv._groups,))
                conv.weight._finish_deferred_init()
        # pre-activation: bn1 spans the block INPUT channels
        for bn, c in ((self.bn1, ci), (self.bn2, cm), (self.bn3, cm)):
            for p in (bn.gamma, bn.beta, bn.running_mean, bn.running_var):
                if p._data is None:
                    p.shape = (c,)
                    p._finish_deferred_init()
        if self.downsample is not None and \
                self.downsample.weight._data is None:
            d = self.downsample
            d.weight.shape = ((d._channels,) + d._kernel
                              + (ci // d._groups,))
            d.weight._finish_deferred_init()

    def _fused_bns_uniform(self):
        return _bns_uniform((self.bn1, self.bn2, self.bn3))

    def _forward_fused(self, x):
        self._finish_deferred(x)
        pairs = ((self.conv1, self.bn1), (self.conv2, self.bn2),
                 (self.conv3, self.bn3))
        state_bns = (self.bn1, self.bn2, self.bn3)  # v2: no shortcut BN
        if self.downsample is not None:
            return _invoke_fused_bottleneck(
                x, "_fused_bottleneck_v2_proj", pairs,
                (self.downsample.weight.data(),), state_bns,
                self._stride)
        return _invoke_fused_bottleneck(
            x, "_fused_bottleneck_v2", pairs, (), state_bns,
            self._stride)

    def forward(self, x):
        if self._fused:
            from .... import autograd
            if autograd.is_training() and self._fused_bns_uniform():
                return self._forward_fused(x)
        residual = x
        x = self.relu(self.bn1(x))
        if self.downsample is not None:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.relu(self.bn2(x))
        x = self.conv2(x)
        x = self.relu(self.bn3(x))
        x = self.conv3(x)
        return x + residual


class S2DStem(HybridBlock):
    """Space-to-depth ResNet stem (the MLPerf TPU trick): s2d(2) then a
    4x4/s1 conv over 12 channels replaces the 7x7/s2 conv over 3.

    Same function class and FLOPs as the classic stem (the 7x7 kernel
    embeds exactly into the s2d domain: a 4x4 kernel over the 2x2-packed
    input covers an 8x8 window of the image), but it reads 12*16=192
    taps instead of 3*49=147 over a C=3 input that packs the 128-lane
    MXU at 2.3% density.  Whether it is faster in a whole step has not
    been measured on the chip (ROADMAP D2).  Select with
    resnet50_v1(stem="s2d").
    """

    def __init__(self, channels, layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        if layout != "NCHW":
            raise ValueError("stem='s2d' is NCHW-only (space_to_depth op "
                             "layout); use the conv7 stem with NHWC")
        self.conv = nn.Conv2D(channels, 4, 1, 2, use_bias=False,
                              in_channels=12)

    def forward(self, x):
        from .... import nd
        if x.shape[-1] % 2 or x.shape[-2] % 2:
            raise ValueError(
                f"stem='s2d' needs even spatial dims (got "
                f"{x.shape[-2:]}); use the default conv7 stem for odd "
                "crop sizes")
        y = nd.space_to_depth(x, block_size=2)
        y = self.conv(y)
        # pad 2 yields 113x113 for the canonical (2,1) asymmetric pad;
        # drop the last row/col (receptive-field shift the trained
        # weights absorb)
        return y[:, :, :-1, :-1]


def _add_stem(features, channels, thumbnail, stem, layout="NCHW"):
    if thumbnail:
        features.add(_conv3x3(channels, 1, 0, layout))
        return
    if stem == "s2d":
        features.add(S2DStem(channels, layout=layout))
    else:
        features.add(nn.Conv2D(channels, 7, 2, 3, use_bias=False,
                               layout=layout))
    features.add(nn.BatchNorm(axis=_bn_axis(layout)))
    features.add(nn.Activation("relu"))
    features.add(nn.MaxPool2D(3, 2, 1, layout=layout))


class ResNetV1(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 stem="conv7", layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        assert len(layers) == len(channels) - 1
        self._layout = layout
        self.features = nn.HybridSequential()
        _add_stem(self.features, channels[0], thumbnail, stem, layout)
        for i, num_layer in enumerate(layers):
            stride = 1 if i == 0 else 2
            self.features.add(self._make_layer(
                block, num_layer, channels[i + 1], stride,
                in_channels=channels[i], layout=layout, fused=fused))
        self.features.add(nn.GlobalAvgPool2D(layout=layout))
        self.output = nn.Dense(classes)

    def _make_layer(self, block, layers, channels, stride, in_channels=0,
                    layout="NCHW", fused=False):
        layer = nn.HybridSequential()
        layer.add(block(channels, stride, channels != in_channels,
                        in_channels=in_channels, layout=layout, fused=fused))
        for _ in range(layers - 1):
            layer.add(block(channels, 1, False, in_channels=channels,
                            layout=layout, fused=fused))
        return layer

    def forward(self, x):
        x = self.features(x)
        return self.output(x)


class ResNetV2(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 stem="conv7", layout="NCHW", fused=False, **kwargs):
        super().__init__(**kwargs)
        self._layout = layout
        self.features = nn.HybridSequential()
        self.features.add(nn.BatchNorm(axis=_bn_axis(layout), scale=False,
                                       center=False))
        _add_stem(self.features, channels[0], thumbnail, stem, layout)
        in_channels = channels[0]
        for i, num_layer in enumerate(layers):
            stride = 1 if i == 0 else 2
            self.features.add(self._make_layer(
                block, num_layer, channels[i + 1], stride,
                in_channels=in_channels, layout=layout, fused=fused))
            in_channels = channels[i + 1]
        self.features.add(nn.BatchNorm(axis=_bn_axis(layout)))
        self.features.add(nn.Activation("relu"))
        self.features.add(nn.GlobalAvgPool2D(layout=layout))
        self.features.add(nn.Flatten())
        self.output = nn.Dense(classes)

    _make_layer = ResNetV1._make_layer

    def forward(self, x):
        x = self.features(x)
        return self.output(x)


resnet_spec = {
    18: ("basic_block", [2, 2, 2, 2], [64, 64, 128, 256, 512]),
    34: ("basic_block", [3, 4, 6, 3], [64, 64, 128, 256, 512]),
    50: ("bottle_neck", [3, 4, 6, 3], [64, 256, 512, 1024, 2048]),
    101: ("bottle_neck", [3, 4, 23, 3], [64, 256, 512, 1024, 2048]),
    152: ("bottle_neck", [3, 8, 36, 3], [64, 256, 512, 1024, 2048]),
}
resnet_net_versions = [ResNetV1, ResNetV2]
resnet_block_versions = [
    {"basic_block": BasicBlockV1, "bottle_neck": BottleneckV1},
    {"basic_block": BasicBlockV2, "bottle_neck": BottleneckV2},
]


def get_resnet(version, num_layers, pretrained=False, ctx=None, **kwargs):
    block_type, layers, channels = resnet_spec[num_layers]
    resnet_class = resnet_net_versions[version - 1]
    block_class = resnet_block_versions[version - 1][block_type]
    net = resnet_class(block_class, layers, channels, **kwargs)
    if pretrained:
        raise RuntimeError("no pretrained weights in zero-egress environment")
    return net


def resnet18_v1(**kwargs):
    return get_resnet(1, 18, **kwargs)


def resnet34_v1(**kwargs):
    return get_resnet(1, 34, **kwargs)


def resnet50_v1(**kwargs):
    return get_resnet(1, 50, **kwargs)


def resnet101_v1(**kwargs):
    return get_resnet(1, 101, **kwargs)


def resnet152_v1(**kwargs):
    return get_resnet(1, 152, **kwargs)


def resnet18_v2(**kwargs):
    return get_resnet(2, 18, **kwargs)


def resnet34_v2(**kwargs):
    return get_resnet(2, 34, **kwargs)


def resnet50_v2(**kwargs):
    return get_resnet(2, 50, **kwargs)


def resnet101_v2(**kwargs):
    return get_resnet(2, 101, **kwargs)


def resnet152_v2(**kwargs):
    return get_resnet(2, 152, **kwargs)
