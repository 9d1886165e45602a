"""ResNet-50 v1 as the Gluon model zoo builds it, at the sizes of the .json
beside this file (a test's toy configuration gives its own sizes to the same
code)."""
import numpy as onp


def build(seed, config):
    """The net on the host, initialised through Gluon from the seed the
    runner gave ``mx.random``, with its deferred shapes resolved by one tiny
    eager forward pass; the loss and the optimizer of the configuration."""
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet
    blocks = {"bottleneck_v1": resnet.BottleneckV1,
              "basic_block_v1": resnet.BasicBlockV1}
    net = resnet.ResNetV1(blocks[config["block"]], config["layers"],
                          config["channels"], classes=config["classes"],
                          thumbnail=config["thumbnail"])
    net.initialize()
    net(nd.random.uniform(shape=(1, config["in_channels"], 32, 32)))
    return {"net": net, "loss": gluon.loss.SoftmaxCrossEntropyLoss(),
            "optimizer": config["optimizer"],
            "optimizer_params": config["optimizer_params"]}


def make_batch(seed, i, batch, config, traffic):
    """Batch ``i`` of the pool for ``seed``: uniform-noise images in the
    configuration's dtype, uniform labels."""
    import jax.numpy as jnp
    rng = onp.random.default_rng([seed, i])
    px = config["image_size"]
    x = rng.random((batch, config["in_channels"], px, px), dtype=onp.float32)
    y = rng.integers(0, config["classes"], (batch,), dtype=onp.int32)
    return x.astype(jnp.dtype(config["dtype"])), y


def n_classes(config):
    return config["classes"]


def flops_per_sample(config, traffic):
    """Model FLOPs to train on one image: 2 per multiply-add, and training
    is three forward passes' worth (forward, gradient by the input, gradient
    by the weights).  The count is for ``image_size`` 224; convolution work
    scales with the number of pixels."""
    scale = (config["image_size"] / 224.0) ** 2
    return 3 * 2 * config["forward_macs_per_image"] * scale
