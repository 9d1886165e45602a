#!/usr/bin/env python
"""Cold-start benchmark: process-start -> first-inference across the
three cache layers (ROADMAP item 2 — replica cold-start from minutes to
seconds).

Each scenario is a FRESH subprocess that imports the framework, loads
an exported artifact into a ``ModelRepository`` (load + per-bucket
warmup — exactly what a serving replica spawn or rolling reload pays),
and runs one inference.  The clock starts in the parent immediately
before the subprocess is spawned, so interpreter start + imports are on
the bill — this is the number an autoscaler waits on:

  cold   no persistent cache, no AOT: every warmup bucket is a fresh
         XLA compilation (the pre-PR-10 reality for every replica)
  warm   the persistent cache (``JAX_COMPILATION_CACHE_DIR``) seeded by
         a prior process on the same host: XLA compilation becomes a
         persistent-cache read
         (replica #2..N, elastic worker joins, rolling reloads)
  aot    the artifact ships per-bucket compiled executables
         (``export_model(aot_buckets=...)``): load + warmup is pure
         deserialization — the subprocess must report
         ``mxnet_serving_compile_total == 0`` from process start

plus the negative control the CI stage gates on: a corrupted AOT blob
must fall back to recompilation (loudly), never crash the load.

Emits a BENCH-style JSON record; ``--check`` enforces the ISSUE 10
floors (warm and aot both >= --floor x cold, AOT compile_total == 0,
corrupt-blob fallback serves).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _toy_artifact(prefix, width, depth, aot_buckets=None):
    """Compile-heavy MLP: one python-level layer loop unrolls into
    ``depth`` matmul+tanh pairs, so XLA compile time — the thing the
    caches remove — dominates the subprocess budget the way a real
    model's does, while trace/run stay cheap."""
    import jax.numpy as jnp
    import numpy as onp
    from incubator_mxnet_tpu import deploy

    def fwd(params, x):
        y = x
        for w in params["layers"]:
            y = jnp.tanh(y @ w)
        return y

    rng = onp.random.RandomState(0)
    params = {"layers": [rng.randn(width, width).astype(onp.float32)
                         * (1.0 / width ** 0.5) for _ in range(depth)]}
    x = rng.randn(1, width).astype(onp.float32)
    deploy.export_model(fwd, (x,), prefix, params=params,
                        aot_buckets=aot_buckets)
    return prefix


def _zoo_artifact(prefix, model, aot_buckets=None):
    os.environ["MXNET_EXPORT_AOT_BUCKETS"] = (
        ",".join(str(b) for b in aot_buckets) if aot_buckets else "")
    from scripts.export_model_zoo import main as export_main
    export_main(["--model", model, "--out", prefix,
                 "--image-size", "32", "--classes", "10"])
    return prefix


# The child measures process-start -> first-inference THROUGH the
# serving repository (load + warmup + one predict) and reports the
# serving metrics snapshot, so the parent gates on the same counters
# /metrics exposes.
_CHILD = r"""
import json, os, sys, time
import jax
repo_root, prefix, t0 = sys.argv[1], sys.argv[2], float(sys.argv[3])
sys.path.insert(0, repo_root)
import numpy as onp
from incubator_mxnet_tpu.serving import ModelRepository
from incubator_mxnet_tpu.serving.metrics import ServingMetrics
metrics = ServingMetrics()
repo = ModelRepository(metrics=metrics)
repo.load("m", prefix)
meta = repo.get("m").predictor.meta
row = tuple(onp.zeros(tuple(s["shape"][1:]), s["dtype"])
            for s in meta["inputs"])
out = repo.predict("m", row)
ms = (time.time() - t0) * 1000.0
snap = metrics.snapshot()
print(json.dumps({
    "first_inference_ms": round(ms, 1),
    "compile_total": snap["compile_total"],
    "cold_start_ms": snap.get("m.cold_start_ms"),
    "aot_loads": snap.get("m.aot_loads", 0),
    "aot_load_failures": snap.get("m.aot_load_failures", 0),
    "platform": jax.devices()[0].platform,
}), flush=True)
"""


def _measure(prefix, buckets, cache_dir=None, timeout=900):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the persistent cache is on by default (a host-level directory, or
    # <checkout>/.jax_cache): the "cold" legs switch it off outright, or
    # it would silently warm the baseline and sink the --check floors
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true" if cache_dir else "false"
    if cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["MXNET_SERVING_BATCH_BUCKETS"] = ",".join(str(b) for b in buckets)
    env["MXNET_SERVING_MAX_BATCH"] = str(max(buckets))
    env["MXNET_SERVING_WARMUP"] = "1"
    t0 = time.time()  # mxlint: allow-wall-clock(t0 crosses the process boundary into the child as an epoch timestamp; monotonic bases are not portably comparable across processes)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, REPO, prefix, repr(t0)],
        env=env, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"cold-start subprocess failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench(args):
    buckets = [int(b) for b in args.buckets.split(",")]
    workdir = os.path.join(args.workdir, "coldstart_bench")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    plain = os.path.join(workdir, "model_plain")
    aot = os.path.join(workdir, "model_aot")
    if args.model_zoo:
        _zoo_artifact(plain, args.model_zoo)
        _zoo_artifact(aot, args.model_zoo, aot_buckets=buckets)
    else:
        _toy_artifact(plain, args.width, args.depth)
        _toy_artifact(aot, args.width, args.depth, aot_buckets=buckets)

    cache_dir = os.path.join(workdir, "xla_cache")
    os.makedirs(cache_dir)

    cold = min((_measure(plain, buckets)
                for _ in range(args.trials)),
               key=lambda r: r["first_inference_ms"])
    _measure(plain, buckets, cache_dir=cache_dir)   # seed the cache
    warm = min((_measure(plain, buckets, cache_dir=cache_dir)
                for _ in range(args.trials)),
               key=lambda r: r["first_inference_ms"])
    aot_rec = min((_measure(aot, buckets)
                   for _ in range(args.trials)),
                  key=lambda r: r["first_inference_ms"])

    # negative control: a corrupted AOT blob must degrade to recompile
    corrupt = os.path.join(workdir, "model_corrupt")
    for f in os.listdir(workdir):
        if f.startswith("model_aot."):
            shutil.copy(os.path.join(workdir, f),
                        os.path.join(workdir,
                                     "model_corrupt" + f[len("model_aot"):]))
    blob = corrupt + f".aot.b{buckets[0]}"
    with open(blob, "wb") as f:
        f.write(b"MXTAOT1\ngarbage-not-a-valid-envelope")
    corrupt_rec = _measure(corrupt, buckets)

    cold_ms = cold["first_inference_ms"]
    warm_ms = warm["first_inference_ms"]
    aot_ms = aot_rec["first_inference_ms"]
    rec = {
        "bench": "coldstart",
        "metric": "warm_speedup_x",
        "value": round(cold_ms / warm_ms, 2),
        "unit": "x_vs_cold",
        "cold_ms": cold_ms,
        "warm_ms": warm_ms,
        "aot_ms": aot_ms,
        "aot_speedup_x": round(cold_ms / aot_ms, 2),
        "aot_vs_warm_x": round(warm_ms / aot_ms, 2),
        "cold_compile_total": cold["compile_total"],
        "warm_compile_total": warm["compile_total"],
        "aot_compile_total": aot_rec["compile_total"],
        "aot_loads": aot_rec["aot_loads"],
        "corrupt_fallback_ok": (corrupt_rec["aot_load_failures"] >= 1
                                and corrupt_rec["compile_total"] > 0),
        "corrupt_ms": corrupt_rec["first_inference_ms"],
        "buckets": buckets,
        "model": args.model_zoo or f"mlp{args.width}x{args.depth}",
        "trials": args.trials,
        "platform": cold["platform"],
    }
    failures = []
    if args.check:
        if rec["value"] < args.floor:
            failures.append(
                f"warm-cache speedup {rec['value']}x < {args.floor}x "
                "floor (persistent compile cache not effective)")
        if rec["aot_speedup_x"] < args.floor:
            failures.append(
                f"AOT speedup {rec['aot_speedup_x']}x < {args.floor}x "
                "floor")
        if rec["aot_compile_total"] != 0:
            failures.append(
                f"AOT replica compiled {rec['aot_compile_total']} "
                "executable(s) — must be 0 from process start")
        if aot_rec["aot_loads"] < len(buckets):
            failures.append(
                f"only {aot_rec['aot_loads']}/{len(buckets)} AOT "
                "buckets loaded")
        if not rec["corrupt_fallback_ok"]:
            failures.append(
                "corrupted AOT blob did not fall back to recompilation "
                f"(failures={corrupt_rec['aot_load_failures']}, "
                f"compile_total={corrupt_rec['compile_total']})")
        if aot_ms > warm_ms * args.aot_tolerance:
            failures.append(
                f"AOT ({aot_ms}ms) slower than warm cache ({warm_ms}ms) "
                f"beyond the {args.aot_tolerance}x tolerance")
    if not args.keep:
        shutil.rmtree(workdir, ignore_errors=True)
    return rec, failures


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--buckets", default="1,2,4,8",
                   help="serving padding buckets = AOT bucket set")
    p.add_argument("--width", type=int, default=256,
                   help="toy MLP width")
    p.add_argument("--depth", type=int, default=96,
                   help="toy MLP depth (layers unroll: compile weight)")
    p.add_argument("--trials", type=int, default=1,
                   help="subprocess runs per scenario; best reported")
    p.add_argument("--model-zoo", default=None, metavar="MODEL",
                   help="bench a model_zoo artifact instead of the MLP")
    p.add_argument("--check", action="store_true",
                   help="enforce the ISSUE 10 cold-start floors")
    p.add_argument("--floor", type=float, default=3.0,
                   help="min warm/AOT speedup vs cold (--check)")
    p.add_argument("--aot-tolerance", type=float, default=1.15,
                   help="AOT must be at least this close to (or faster "
                        "than) the warm cache (--check)")
    p.add_argument("--keep", action="store_true",
                   help="keep the workdir (artifacts + cache)")
    p.add_argument("--output", default=None)
    p.add_argument("--workdir", default="/tmp")
    args = p.parse_args(argv)

    rec, failures = bench(args)
    line = json.dumps(rec)
    print(line, flush=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")
    if failures:
        print(f"[coldstart_bench] FAIL: {failures}", file=sys.stderr,
              flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
