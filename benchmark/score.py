#!/usr/bin/env python
"""Inference throughput sweep across the model zoo — the reference
`example/image-classification/benchmark_score.py`, source of the
BASELINE.md inference tables (perf.md:165-210).

For each (model, batch_size): compile the hybridized forward once, then
time N batches closed by a host readback inside the timed region (the
device has to finish before its answer can be read) and print one JSON
line:
  {"model": ..., "batch": N, "img_per_sec": ..., "platform": ...}

Usage:
  python benchmark/score.py                          # default sweep
  python benchmark/score.py --models resnet50_v1,alexnet --batches 1,32
  python benchmark/score.py --cpu --image-size 64    # CPU smoke
  python benchmark/score.py --dtype bfloat16         # fp16-table analog
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the reference sweep (benchmark_score.py networks list)
DEFAULT_MODELS = ("alexnet", "vgg16", "inception_v3", "resnet50_v1",
                  "resnet152_v1", "mobilenet1_0", "densenet121",
                  "squeezenet1_0")


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--models", default=",".join(DEFAULT_MODELS))
    p.add_argument("--batches", default="1,32")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--json", default=None, help="also write results here")
    p.add_argument("--fuse-bn", action="store_true",
                   help="fold BatchNorm into convs before timing "
                        "(gluon.contrib.fuse_conv_bn inference transform)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as onp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp, nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    platform = jax.devices()[0].platform
    rows = []
    for name in args.models.split(","):
        builder = getattr(vision, name, None)
        if builder is None:
            print(f"# unknown model {name!r}, skipping", file=sys.stderr)
            continue
        for bs in (int(b) for b in args.batches.split(",")):
            mx.random.seed(0)
            size = 299 if name == "inception_v3" and args.image_size == 224 \
                else args.image_size
            net = builder()
            net.initialize(ctx=mx.cpu())
            net(nd.zeros((1, 3, size, size)))   # shape resolution
            if args.fuse_bn:
                from incubator_mxnet_tpu.gluon.contrib import fuse_conv_bn
                fuse_conv_bn(net)
            if args.dtype == "bfloat16":
                amp.convert_block(net, "bfloat16")
            net.hybridize(static_alloc=True)
            x = jnp.asarray(onp.random.rand(bs, 3, size, size),
                            jnp.float32)
            if args.dtype == "bfloat16":
                x = x.astype(jnp.bfloat16)
            xnd = nd.NDArray(x)
            out = net(xnd)                      # compile
            float(out.data.ravel()[0])
            for _ in range(args.warmup - 1):
                out = net(xnd)
            float(out.data.ravel()[0])
            t0 = time.perf_counter()
            for _ in range(args.steps):
                out = net(xnd)
            float(out.data.ravel()[0])          # host-readback sync
            dt = time.perf_counter() - t0
            rec = {"model": name, "batch": bs, "dtype": args.dtype,
                   "fuse_bn": bool(args.fuse_bn),
                   "image_size": size,
                   "img_per_sec": round(bs * args.steps / dt, 2),
                   "ms_per_batch": round(1000 * dt / args.steps, 2),
                   "platform": platform}
            rows.append(rec)
            print(json.dumps(rec), flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"platform": platform, "results": rows}, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
