#!/usr/bin/env python
"""Train ResNet-50 on the Pallas fused-bottleneck path (eager Trainer).

Demonstrates the user-facing API for the NHWC fused configuration (in no
cell of the benchmark: whether it beats the default is ROADMAP S4's A/B):

    net = vision.resnet50_v1(layout="NHWC", fused=True)

During training each BottleneckV1 runs `_fused_bottleneck_v1[_proj]`
(ops/fused_block.py): 1x1 convs emit their BN batch stats from the
matmul epilogue and apply the previous BN's normalize+ReLU in the
prologue; BN moving stats update through the normal gluon contract.
Inference (no autograd scope) uses the plain layer path.

Synthetic data; on CPU the kernels run in Pallas interpret mode, on a
TPU chip they compile under Mosaic (gated by the smoke manifest unless
MXNET_USE_PALLAS=1).

``--chunk-steps K`` (or ``MXNET_TRAIN_CHUNK_STEPS``) switches from the
eager Trainer to the whole-loop-compiled path: the fused train step
(fuse.py) scanned K steps per XLA dispatch (fuse_loop.py), batches fed
through the dataloader's device-side prefetch ring — one dispatch and
one scalar transfer per K steps instead of K (docs/performance.md
"Chunked training loop").

Usage:
  python examples/train_resnet_fused.py [--batch 8] [--image-size 64]
      [--steps 4] [--cpu] [--chunk-steps K]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--steps", type=int, default=4)
    p.add_argument("--classes", type=int, default=100)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--chunk-steps", type=int, default=0,
                   help="K > 0: fused step + lax.scan whole-loop "
                        "compilation, one XLA dispatch per K steps; "
                        "0 = eager Trainer (default)")
    args = p.parse_args(argv)

    if args.cpu:
        os.environ.setdefault("MXNET_USE_PALLAS", "1")  # interpret mode
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import numpy as onp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon, nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.resnet50_v1(classes=args.classes, layout="NHWC",
                             fused=True)
    net.initialize(ctx=mx.cpu())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    rng = onp.random.RandomState(0)
    x = nd.array(rng.rand(args.batch, args.image_size, args.image_size,
                          3).astype("float32"))
    y = nd.array(rng.randint(0, args.classes, args.batch).astype("int32"))

    extra = {}
    if args.chunk_steps > 0:
        from incubator_mxnet_tpu.fuse import make_fused_train_step
        net(x)                      # materialize deferred param shapes
        step = make_fused_train_step(
            net, loss_fn, "sgd",
            {"learning_rate": 0.01, "momentum": 0.9},
            chunk_steps=args.chunk_steps)
        loop = step.chunked_loop()
        batches = [(x, y)] * args.steps
        t0 = time.perf_counter()
        records = loop.run_epoch(batches)
        losses = [float(r["loss"]) for r in records]  # per-chunk means
        dt = time.perf_counter() - t0
        step.write_back()
        extra = {"chunk_steps": args.chunk_steps,
                 "chunks": loop.chunks_run,
                 "tail_steps": loop.tail_steps_run,
                 "loop_compiles": loop.compile_count}
    else:
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01, "momentum": 0.9})
        losses = []
        t0 = time.perf_counter()
        for step in range(args.steps):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(args.batch)
            losses.append(float(loss.mean().asnumpy()))
        dt = time.perf_counter() - t0

    assert all(onp.isfinite(l) for l in losses), losses
    # memorizing one fixed batch: training must reach a lower loss than
    # it started at SOME step (tiny-batch BN dynamics are oscillatory,
    # so the last step is not a reliable monotonicity probe)
    if len(losses) > 1:
        assert min(losses[1:]) < losses[0], losses
    print(json.dumps({
        "example": "train_resnet_fused",
        "platform": jax.devices()[0].platform,
        "losses": [round(l, 4) for l in losses],
        "img_per_sec": round(args.batch * args.steps / dt, 2),
        **extra,
    }))
    print("done")


if __name__ == "__main__":
    main()
