"""Model-zoo inference throughput — the TPU counterpart of the
reference's headline perf script
(example/image-classification/benchmark_score.py, whose numbers fill
docs perf.md:165-215 and BASELINE.md).

For each (model, batch_size) it compiles the hybridized forward once
and reports img/s from a window closed by a host readback inside the
timed region (the device has to finish before its answer can be read).

Usage:
    python examples/benchmark_score.py                    # default set
    python examples/benchmark_score.py --models resnet50_v1 vgg16 \
        --batch-sizes 1 32 --image-shape 3,224,224 --dtype bfloat16
"""
import argparse
import time

import numpy as onp


def score(model_name, batch_size, image_shape, dtype, steps, warmup):
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, amp
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    accel = jax.devices()[0]
    cpu0 = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu0):  # eager setup off the accelerator
        net = getattr(vision, model_name)()
        net.initialize(ctx=mx.cpu())
        net(nd.random.uniform(shape=(1,) + image_shape))  # shape resolve
        if dtype == "bfloat16":
            amp.convert_block(net, "bfloat16")
        params, apply_fn = net.functional()
        x = jnp.asarray(
            onp.random.rand(batch_size, *image_shape),
            jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
    fwd = jax.jit(lambda p, x: apply_fn(p, x, training=False))
    params = jax.tree_util.tree_map(lambda t: jax.device_put(t, accel),
                                    params)
    x = jax.device_put(x, accel)

    out = fwd(params, x)
    float(jnp.asarray(out).ravel()[0])  # compile + sync
    for _ in range(warmup):
        out = fwd(params, x)
    float(jnp.asarray(out).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd(params, x)
    float(jnp.asarray(out).ravel()[0])  # sync INSIDE the timed region
    dt = time.perf_counter() - t0
    return batch_size * steps / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", nargs="+", default=[
        "alexnet", "vgg16", "inception_v3", "resnet50_v1", "resnet152_v1",
        "mobilenet1_0", "densenet121", "squeezenet1_1"])
    ap.add_argument("--batch-sizes", nargs="+", type=int,
                    default=[1, 32, 64, 128])
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    args = ap.parse_args()
    shape = tuple(int(d) for d in args.image_shape.split(","))
    print(f"image_shape={shape} dtype={args.dtype}")
    for model in args.models:
        for bs in args.batch_sizes:
            try:
                ips = score(model, bs, shape, args.dtype, args.steps,
                            args.warmup)
                print(f"{model:16s} bs={bs:4d}  {ips:10.1f} img/s",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — keep sweeping
                print(f"{model:16s} bs={bs:4d}  FAILED: {e}", flush=True)
    print("done")


if __name__ == "__main__":
    main()
