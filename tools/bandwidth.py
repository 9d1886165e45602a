#!/usr/bin/env python
"""Collective-bandwidth probe — the reference `tools/bandwidth/measure.py`
recast for the TPU mesh.

The reference measures KVStore push+pull time over a model's gradient
arrays and reports the algorithmic allreduce bandwidth
``size * 2(n-1)/n / t`` per GPU (measure.py:137-140).  That number is
half of this repo's north-star metric ("KVStore-equivalent allreduce
bandwidth over ICI", BASELINE.md).  TPU-first redesign: the collectives
are XLA ops (psum / all_gather / reduce_scatter / ppermute) jitted under
shard_map over a `jax.sharding.Mesh`, so what we time IS the compiled
collective the training step uses — there is no separate KVStore wire.

Modes:
  --sweep           message-size sweep per collective (default)
  --network resnet50  the reference mode: allreduce the model's actual
                      gradient shapes (fused flat buffer, KVStore-style)
  --json PATH       write results as JSON artifact

On a single-chip session the cross-device path can't be exercised for
real, so the tool defaults to a virtual device mesh
(--devices N -> xla_force_host_platform_device_count); numbers there
validate the machinery and give a host-collective floor.  On a pod
slice the same command reports real ICI bandwidth.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="collective bandwidth probe")
    p.add_argument("--devices", type=int, default=0,
                   help="force an N-device virtual CPU mesh (0 = use "
                        "whatever jax.devices() offers)")
    p.add_argument("--collectives", type=str,
                   default="psum,all_gather,reduce_scatter,ppermute")
    p.add_argument("--min-mb", type=float, default=0.25)
    p.add_argument("--max-mb", type=float, default=64.0)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--network", type=str, default=None,
                   help="measure the gradient-allreduce of this model "
                        "zoo network instead of a size sweep")
    p.add_argument("--dtype", type=str, default="float32")
    p.add_argument("--json", type=str, default=None)
    return p.parse_args(argv)


def _mk_collective(kind, mesh, axis="x"):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis]
    if kind == "psum":
        fn, in_spec, out_spec = (
            lambda x: jax.lax.psum(x, axis), P(axis), P(axis))
    elif kind == "all_gather":
        fn, in_spec, out_spec = (
            lambda x: jax.lax.all_gather(x, axis, tiled=True),
            P(axis), P(axis))
    elif kind == "reduce_scatter":
        fn, in_spec, out_spec = (
            lambda x: jax.lax.psum_scatter(x, axis, tiled=True),
            P(axis), P(axis))
    elif kind == "ppermute":
        perm = [(i, (i + 1) % n) for i in range(n)]
        fn, in_spec, out_spec = (
            lambda x: jax.lax.ppermute(x, axis, perm), P(axis), P(axis))
    else:
        raise ValueError(kind)
    sm = jax.shard_map(fn, mesh=mesh, in_specs=in_spec,
                       out_specs=out_spec, check_vma=False)
    jitted = jax.jit(sm,
                     in_shardings=NamedSharding(mesh, in_spec),
                     out_shardings=NamedSharding(mesh, out_spec))
    return jitted


# Algorithmic bytes moved per device, as a fraction of the buffer size
# (ring-algorithm accounting, same convention as reference
# measure.py:139 and nccl-tests).
ALGO_FACTOR = {
    "psum": lambda n: 2.0 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
}


def _time_collective(jitted, x, iters, warmup):
    """Per-device execution is in-order, so dispatching N same-input
    calls and host-reading the LAST output times all N (chaining
    out=jitted(out) would be wrong here: all_gather/reduce_scatter
    change the shape every call).  The sync is a host readback of
    the last output: the device has to finish before it can be read."""
    import jax.numpy as jnp
    for _ in range(warmup):
        out = jitted(x)
    float(jnp.ravel(out)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jitted(x)
    float(jnp.ravel(out)[0])
    dt = (time.perf_counter() - t0) / iters
    return dt


def run_sweep(args, mesh):
    import jax.numpy as jnp
    import numpy as onp

    n = mesh.shape["x"]
    dtype = jnp.dtype(args.dtype)
    rows = []
    mb = args.min_mb
    sizes = []
    while mb <= args.max_mb + 1e-9:
        sizes.append(mb)
        mb *= 4
    for kind in args.collectives.split(","):
        jitted = _mk_collective(kind, mesh)  # jit cache shared across sizes
        for mb in sizes:
            nelem = int(mb * 1e6 / dtype.itemsize)
            # divisible by n^2: sharding splits by n, and the per-shard
            # reduce_scatter splits by n again
            nelem = max(nelem // (n * n) * n * n, n * n)
            x = jnp.asarray(onp.ones((nelem,), onp.float32), dtype)
            dt = _time_collective(jitted, x, args.iters, args.warmup)
            nbytes = nelem * dtype.itemsize
            bw = nbytes * ALGO_FACTOR[kind](n) / dt / 1e9
            rows.append({"collective": kind, "mb": round(mb, 3),
                         "time_us": round(dt * 1e6, 1),
                         "algo_gb_s": round(bw, 6)})
            print(f"{kind:15s} {mb:9.2f} MB  {dt * 1e6:10.1f} us  "
                  f"{bw:8.3f} GB/s", flush=True)
    return rows


def run_network(args, mesh):
    """Reference measure.py mode: allreduce the model's real gradient
    set, both per-array (KVStore push/pull granularity) and fused."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    net = getattr(vision, args.network)()
    net.initialize(ctx=mx.cpu())
    net(nd.random.uniform(shape=(1, 3, 224, 224)))
    shapes = [tuple(p.shape) for p in net.collect_params().values()
              if p.grad_req != "null"]
    total = sum(int(jnp.prod(jnp.array(s))) for s in shapes)
    dtype = jnp.dtype(args.dtype)
    size_mb = total * dtype.itemsize / 1e6
    n = mesh.shape["x"]
    print(f"{args.network}: {len(shapes)} arrays, {size_mb:.1f} MB total",
          flush=True)

    jitted = _mk_collective("psum", mesh)
    # fused: one flat buffer, the fuse.py/multi-tensor path
    nelem = max(total // n * n, n)
    x = jnp.ones((nelem,), dtype)
    dt_fused = _time_collective(jitted, x, args.iters, args.warmup)
    bw_fused = nelem * dtype.itemsize * ALGO_FACTOR["psum"](n) / dt_fused / 1e9

    # per-array: one collective per parameter, KVStore granularity.
    # Warm EVERY distinct shape so no compile lands in the timed region,
    # and sync via host readback of the last output.
    bufs = [jnp.ones((max(int(jnp.prod(jnp.array(s))) // n * n, n),), dtype)
            for s in shapes]
    for b in bufs:
        out = jitted(b)
    float(jnp.ravel(out)[0])
    t0 = time.perf_counter()
    for _ in range(args.iters):
        outs = [jitted(b) for b in bufs]
    float(jnp.ravel(outs[-1])[0])
    dt_per = (time.perf_counter() - t0) / args.iters
    bw_per = total * dtype.itemsize * ALGO_FACTOR["psum"](n) / dt_per / 1e9

    rows = [{"collective": "psum_fused", "mb": round(size_mb, 1),
             "time_us": round(dt_fused * 1e6, 1),
             "algo_gb_s": round(bw_fused, 6)},
            {"collective": "psum_per_array", "mb": round(size_mb, 1),
             "time_us": round(dt_per * 1e6, 1),
             "algo_gb_s": round(bw_per, 6),
             "arrays": len(shapes)}]
    for r in rows:
        print(f"{r['collective']:15s} {r['mb']:9.1f} MB  "
              f"{r['time_us']:10.1f} us  {r['algo_gb_s']:8.3f} GB/s",
              flush=True)
    return rows


def main(argv=None):
    args = parse_args(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}")
    import jax
    if args.devices:
        # --devices asks for that many virtual HOST devices: a CPU mesh
        jax.config.update("jax_platforms", "cpu")
    from jax.sharding import Mesh
    import numpy as onp

    devs = jax.devices()
    mesh = Mesh(onp.array(devs), ("x",))
    print(f"mesh: {len(devs)}x {devs[0].platform}", flush=True)

    rows = run_network(args, mesh) if args.network else run_sweep(args, mesh)
    out = {"platform": devs[0].platform, "n_devices": len(devs),
           "dtype": args.dtype, "results": rows}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {args.json}", flush=True)
    return out


if __name__ == "__main__":
    main()
