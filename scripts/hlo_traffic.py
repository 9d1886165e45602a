"""Static HBM-traffic analysis of the fused train step.

Traces the bench-identical fused ResNet-50 step (no chip needed — runs
on the CPU backend), lowers it to StableHLO, and tallies every tensor
type that appears, grouped by (dtype, shape).  The output answers two
questions the on-chip `perf_probe.py ablate` can't:

  1. Do any fp32 activation-sized tensors survive in the program?
     (round-4 finding: two-pass BatchNorm variance materialized 411 MB
     fp32 copies of the stem activation 7-9x; one-pass E[x^2]-mu^2
     stats were supposed to eliminate ALL of them)
  2. Which tensors dominate the byte footprint — i.e. where the next
     HBM-bandwidth lever is.

This is a *pre-fusion* census: XLA will fuse most elementwise chains so
the count of type-occurrences overestimates realized traffic, but a
dtype/shape class that does not appear at all cannot cost bandwidth,
and the relative ordering of the big classes tracks the ablate probe's
on-chip decomposition (docs/performance.md, round-4 findings).

Usage:  python scripts/hlo_traffic.py [--bs 128] [--stem conv7]
                                      [--remat dots] [--top 25]
"""
from __future__ import annotations

import argparse
import os
import re
import sys
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# StableHLO MLIR dtype spellings (iN is signless int, uiN unsigned)
DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "i32": 4,
               "ui32": 4, "i8": 1, "ui8": 1, "i1": 1, "i64": 8,
               "ui64": 8, "i16": 2, "ui16": 2}

TENSOR_RE = re.compile(
    r"tensor<([0-9x]+)x(f32|bf16|f16|f64|ui32|ui8|ui64|ui16|i32|i8|i1|i64|i16)>")


def census(hlo_text, min_mb=1.0):
    """Count occurrences of each (shape, dtype) tensor type >= min_mb."""
    counts = Counter()
    for m in TENSOR_RE.finditer(hlo_text):
        dims, dt = m.group(1), m.group(2)
        n = 1
        for d in dims.split("x"):
            if d:
                n *= int(d)
        mb = n * DTYPE_BYTES[dt] / 1e6
        if mb >= min_mb:
            counts[(dims, dt, round(mb, 1))] += 1
    return counts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=128)
    ap.add_argument("--stem", default="conv7")
    ap.add_argument("--layout", default="NCHW")
    ap.add_argument("--fused", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--min-mb", type=float, default=1.0)
    ap.add_argument("--dump", default=None,
                    help="also write the full StableHLO text here")
    args = ap.parse_args()

    import jax
    # an HLO traffic estimate needs only the trace: it runs on the host
    # and leaves the chip to whoever is measuring
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as onp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, amp
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    if args.fused:
        if os.environ.get("MXNET_USE_PALLAS", "").lower() in (
                "0", "false", "off"):
            sys.exit("--fused with MXNET_USE_PALLAS=0 would census the "
                     "XLA fallback under a fusedblk=True label")
        os.environ["MXNET_USE_PALLAS"] = "1"
    mx.random.seed(0)
    net = vision.resnet50_v1(stem=args.stem, layout=args.layout,
                             fused=args.fused)
    net.initialize(ctx=mx.cpu())
    nhwc = args.layout == "NHWC"
    net(nd.random.uniform(shape=(1, 32, 32, 3) if nhwc else (1, 3, 32, 32)))
    amp.convert_block(net, "bfloat16")
    step = make_fused_train_step(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        remat=args.remat)

    x = jax.ShapeDtypeStruct((args.bs, 224, 224, 3) if nhwc
                             else (args.bs, 3, 224, 224), jnp.bfloat16)
    y = jax.ShapeDtypeStruct((args.bs,), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    spec = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)  # noqa: E731
    tree = jax.tree_util.tree_map
    lowered = step._step_fn.lower(
        tree(spec, step.params), tree(spec, step.aux),
        tree(spec, step.opt_state), x, y, key)
    text = lowered.as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(text)

    counts = census(text, args.min_mb)
    rows = sorted(counts.items(), key=lambda kv: -kv[0][2] * kv[1])
    print(f"# fused step bs={args.bs} stem={args.stem} remat={args.remat} "
          f"layout={args.layout} fusedblk={args.fused}")
    print(f"# {len(text.splitlines())} HLO lines; tensor types >= "
          f"{args.min_mb} MB, sorted by MB x occurrences")
    print(f"{'shape':>28} {'dtype':>5} {'MB':>8} {'count':>5} {'MBxN':>9}")
    total_f32_act = 0.0
    for (dims, dt, mb), n in rows[:args.top]:
        print(f"{dims:>28} {dt:>5} {mb:>8.1f} {n:>5} {mb * n:>9.0f}")
    # fp32 activation check: anything fp32 with a leading batch dim and
    # >= 50 MB is an activation-sized master copy (params are < 10 MB)
    bad = [(d, m, n) for (d, dt, m), n in counts.items()
           if dt == "f32" and m >= 50.0]
    if bad:
        print("\nFP32 activation-sized types (pre-fusion; `convert`s that "
              "feed f32-accumulated\nreduces fuse away on TPU — only "
              "tensors with non-elementwise consumers cost HBM):")
        for d, m, n in sorted(bad, key=lambda r: -r[1] * r[2]):
            print(f"  {d} f32 {m:.0f} MB x{n}")
    else:
        print("\nFP32_ACTIVATIONS: none >= 50 MB (one-pass BN holding)")


if __name__ == "__main__":
    main()
