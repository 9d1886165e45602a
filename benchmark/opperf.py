#!/usr/bin/env python
"""Per-operator latency benchmark (reference benchmark/opperf/).

Walks the op registry, generates inputs per op (curated specs for layer
ops, shape heuristics for tensor ops), and times forward and backward
so that a timed region cannot end before the device does: every
measurement chains through device values and ends with a host readback
INSIDE the timed region (docs/performance.md "Closing a timed window").

Usage:
  python benchmark/opperf.py [--output opperf.json] [--ops relu,dot,...]
                             [--steps 50] [--warmup 5]

Output JSON: {"platform", "n_ops", "results": {op: {fwd_ms, bwd_ms,
inputs}}, "skipped": {op: reason}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as onp


def _sync(val):
    leaf = jax.tree_util.tree_leaves(val)[0]
    onp.asarray(jax.device_get(jnp.ravel(leaf)[:1].astype(jnp.float32)))


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------

def default_specs(n=1024):
    """Curated (args, kwargs) generators per op; keys are canonical op
    names.  Mirrors the reference's opperf default input registry
    (benchmark/opperf/rules/default_params.py)."""
    f = jnp.float32
    rng = onp.random.RandomState(0)

    def arr(*shape, dtype=f):
        return jnp.asarray(rng.rand(*shape), dtype)

    B, C, H, W = 32, 64, 56, 56
    specs = {
        "FullyConnected": (lambda: ([arr(B, 512), arr(1024, 512),
                                     arr(1024)], {"num_hidden": 1024})),
        "Convolution": (lambda: ([arr(B, C, H, W), arr(128, C, 3, 3)],
                                 {"kernel": (3, 3), "num_filter": 128,
                                  "pad": (1, 1), "no_bias": True})),
        "Deconvolution": (lambda: ([arr(B, C, 28, 28), arr(C, 64, 2, 2)],
                                   {"kernel": (2, 2), "stride": (2, 2),
                                    "num_filter": 64})),
        "Pooling": (lambda: ([arr(B, C, H, W)],
                             {"kernel": (2, 2), "stride": (2, 2),
                              "pool_type": "max"})),
        "BatchNorm": (lambda: ([arr(B, C, H, W), arr(C), arr(C), arr(C),
                                arr(C)], {})),
        "LayerNorm": (lambda: ([arr(B, 128, 768), arr(768), arr(768)], {})),
        "RMSNorm": (lambda: ([arr(B, 128, 768), arr(768)], {})),
        "GroupNorm": (lambda: ([arr(B, C, 28, 28), arr(C), arr(C)],
                               {"num_groups": 8})),
        "InstanceNorm": (lambda: ([arr(B, C, 28, 28), arr(C), arr(C)], {})),
        "softmax": (lambda: ([arr(B, 1000)], {})),
        "log_softmax": (lambda: ([arr(B, 1000)], {})),
        "dot": (lambda: ([arr(n, n), arr(n, n)], {})),
        "batch_dot": (lambda: ([arr(B, 128, 128), arr(B, 128, 128)], {})),
        "Embedding": (lambda: ([jnp.asarray(rng.randint(0, 1000, (B, 64)),
                                            jnp.int32), arr(1000, 512)],
                               {"input_dim": 1000, "output_dim": 512})),
        "dot_product_attention": (lambda: (
            [arr(B, 8, 128, 64), arr(B, 8, 128, 64), arr(B, 8, 128, 64)],
            {})),
        "take": (lambda: ([arr(1000, 512),
                           jnp.asarray(rng.randint(0, 1000, (B, 64)),
                                       jnp.int32)], {})),
        "concat": (lambda: ([arr(B, 512), arr(B, 512)], {"dim": 1})),
        "topk": (lambda: ([arr(B, 1000)], {"k": 5})),
        "sort": (lambda: ([arr(B, 1000)], {})),
        "argsort": (lambda: ([arr(B, 1000)], {})),
        "RNN": None,  # exercised via gluon rnn tests; stateful signature
        "_contrib_interleaved_matmul_selfatt_qk": (
            lambda: ([arr(128, B, 8 * 64 * 3)], {"heads": 8})),
    }
    # generic elementwise/reduction fallbacks
    unary = ["relu", "sigmoid", "tanh", "exp", "log", "sqrt", "square",
             "abs", "negative", "erf", "gelu", "softsign", "softrelu",
             "mean", "sum", "max", "min", "norm", "argmax", "argmin",
             "floor", "ceil", "round", "rsqrt", "cbrt", "sin", "cos",
             "tan", "arcsin", "arccos", "arctan", "sinh", "cosh", "tanh",
             "log1p", "expm1", "logical_not", "sign", "reciprocal",
             "flatten", "transpose", "reverse", "cumsum", "clip",
             "L2Normalization", "softmax_cross_entropy"]
    binary = ["add", "subtract", "multiply", "divide", "maximum", "minimum",
              "power", "mod", "hypot", "broadcast_add", "broadcast_sub",
              "broadcast_mul", "broadcast_div", "elemwise_add",
              "elemwise_sub", "elemwise_mul", "elemwise_div"]
    for name in unary:
        specs.setdefault(name, (lambda: ([arr(n, n)], {})))
    for name in binary:
        specs.setdefault(name, (lambda: ([arr(n, n), arr(n, n)], {})))

    # optimizer-update family (ops/optimizer_ops.py): weight-sized
    # tensors, pure update returns new (weight, *state)
    P = (4096, 1024)
    specs.update({
        "sgd_update": (lambda: ([arr(*P), arr(*P)], {"lr": 0.1})),
        "sgd_mom_update": (lambda: ([arr(*P), arr(*P), arr(*P)],
                                    {"lr": 0.1, "momentum": 0.9})),
        "nag_mom_update": (lambda: ([arr(*P), arr(*P), arr(*P)],
                                    {"lr": 0.1, "momentum": 0.9})),
        "adam_update": (lambda: ([arr(*P), arr(*P), arr(*P), arr(*P)],
                                 {"lr": 0.001})),
        "adamw_update": (lambda: ([arr(*P), arr(*P), arr(*P), arr(*P)],
                                  {"lr": 0.001, "wd": 0.01})),
        "ftrl_update": (lambda: ([arr(*P), arr(*P), arr(*P), arr(*P)],
                                 {"lr": 0.1})),
        "rmsprop_update": (lambda: ([arr(*P), arr(*P), arr(*P)],
                                    {"lr": 0.01})),
        "signum_update": (lambda: ([arr(*P), arr(*P), arr(*P)],
                                   {"lr": 0.01, "momentum": 0.9})),
        "lamb_update_phase1": (lambda: ([arr(*P), arr(*P), arr(*P),
                                         arr(*P)], {"t": 1})),
        "group_adagrad_update": (lambda: ([arr(*P), arr(*P),
                                           arr(P[0])], {"lr": 0.1})),
        "multi_all_finite": (lambda: ([arr(*P), arr(*P)],
                                      {"num_arrays": 2})),
        # image family
        "image_resize": (lambda: ([arr(B, 256, 256, 3)],
                                  {"size": (224, 224)})),
        "image_to_tensor": (lambda: ([arr(B, 224, 224, 3)], {})),
        "image_normalize": (lambda: ([arr(B, 3, 224, 224)],
                                     {"mean": (0.485, 0.456, 0.406),
                                      "std": (0.229, 0.224, 0.225)})),
        "BilinearResize2D": (lambda: ([arr(B, C, 28, 28)],
                                      {"height": 56, "width": 56})),
        "box_decode": (lambda: ([arr(B, 8732, 4), arr(1, 8732, 4)], {})),
        # linalg tail (square SPD-ish inputs for the factorizations)
        "linalg_trmm": (lambda: ([jnp.asarray(
            onp.tril(rng.rand(512, 512)) + onp.eye(512), f),
            arr(512, 512)], {})),
        "linalg_potri": (lambda: ([jnp.asarray(
            onp.tril(rng.rand(256, 256)) + 2 * onp.eye(256), f)], {})),
        "linalg_syevd": (lambda: ([jnp.asarray(
            (lambda m: (m + m.T) / 2)(rng.rand(256, 256)), f)], {})),
        "linalg_gelqf": (lambda: ([arr(256, 512)], {})),
        "interleaved_matmul_encdec_qk": (
            lambda: ([arr(128, B, 8 * 64), arr(128, B, 8 * 2 * 64)],
                     {"heads": 8})),
        "hawkesll": (lambda: ([arr(B, 8), arr(8), arr(8), arr(B, 8),
                               arr(B, 100),
                               jnp.asarray(rng.randint(0, 8, (B, 100)),
                                           jnp.int32),
                               jnp.full((B,), 100.0, f),
                               jnp.full((B,), 60.0, f)], {})),
        "arange": (lambda: ([], {"start": 0.0, "stop": float(n * n)})),
        "eye": (lambda: ([], {"N": n})),
        "histogram": (lambda: ([arr(n, n)],
                               {"bins": 64, "range": (0.0, 1.0)})),
    })
    return specs


def bench_op(op, args, kwargs, steps, warmup, grad):
    """Time one op's forward (and backward) with host-readback sync."""
    fwd = op.jitted(tuple(sorted(kwargs)))

    out = fwd(*args, **kwargs)
    _sync(out)
    for _ in range(warmup):
        out = fwd(*args, **kwargs)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd(*args, **kwargs)
    _sync(out)
    fwd_ms = (time.perf_counter() - t0) / steps * 1e3

    bwd_ms = None
    if grad and op.differentiable:
        float_pos = [i for i, a in enumerate(args)
                     if jnp.issubdtype(a.dtype, jnp.floating)]
        if float_pos:
            def loss(*a):
                o = op.fn(*a, **kwargs)
                leaves = jax.tree_util.tree_leaves(o)
                return sum(jnp.sum(l.astype(jnp.float32)) for l in leaves
                           if jnp.issubdtype(l.dtype, jnp.floating))

            gfn = jax.jit(jax.grad(loss, argnums=tuple(float_pos)))
            g = gfn(*args)
            _sync(g)
            for _ in range(warmup):
                g = gfn(*args)
            _sync(g)
            t0 = time.perf_counter()
            for _ in range(steps):
                g = gfn(*args)
            _sync(g)
            bwd_ms = (time.perf_counter() - t0) / steps * 1e3
    return fwd_ms, bwd_ms


def bench_bulk_chain(steps, warmup, chain_len=50, size=64):
    """Per-op dispatch vs bulked dispatch on an elementwise chain.

    The bulking headline microbenchmark: a chain of ``chain_len`` small
    elementwise ops, run once with per-op jit dispatch and once with
    ``MXNET_EXEC_ENABLE_BULKING`` semantics (deferred segments compiled
    as one XLA program each, ops/bulking.py).  Outputs are compared at
    ULP granularity — fused segments may FMA-contract across op
    boundaries (same float semantics as hybridize), so a few ULPs of
    drift is expected and anything beyond that is a real divergence —
    and the profiler counters prove ops/segment and the trace-cache hit
    rate.
    """
    from incubator_mxnet_tpu import nd, profiler
    from incubator_mxnet_tpu.ops import bulking

    rng = onp.random.RandomState(0)
    x0 = nd.array(rng.rand(size, size).astype("float32"))
    n_rounds = max(1, chain_len // 5)

    def chain():
        x = x0
        for _ in range(n_rounds):  # 5 ops per round
            x = x * 1.0001
            x = x + 0.0001
            x = nd.relu(x)
            x = x - 0.00005
            x = nd.minimum(x, 10.0)
        return x.asnumpy()

    def run(bulk):
        with bulking.bulk_scope(bulk):
            return chain()

    ref, got = run(False), run(True)
    identical = bool(onp.array_equal(ref, got))
    max_abs = 0.0 if identical else float(onp.max(onp.abs(ref - got)))
    max_ulp = 0.0 if identical else float(onp.max(
        onp.abs(ref - got) / onp.spacing(onp.maximum(onp.abs(ref), 1e-30))))

    def time_mode(bulk):
        for _ in range(warmup):
            run(bulk)
        profiler.reset_bulk_stats()  # counters cover only the timed steps
        t0 = time.perf_counter()
        for _ in range(steps):
            run(bulk)
        return (time.perf_counter() - t0) / steps * 1e3

    per_op_ms = time_mode(False)
    off_stats = profiler.bulk_stats(reset=True)
    bulked_ms = time_mode(True)
    on_stats = profiler.bulk_stats(reset=True)
    return {
        "chain_len": n_rounds * 5,
        "size": size,
        "steps": steps,
        "identical": identical,
        "max_abs_diff": max_abs,
        "max_ulp_diff": max_ulp,
        "per_op_ms": round(per_op_ms, 4),
        "bulked_ms": round(bulked_ms, 4),
        "speedup": round(per_op_ms / bulked_ms, 3) if bulked_ms else None,
        "per_op_dispatches_per_run": off_stats["eager_dispatches"] // max(
            1, steps),
        "bulked_launches_per_run": on_stats["segments_flushed"] // max(
            1, steps),
        "ops_per_segment_mean": round(on_stats["ops_per_segment_mean"], 2),
        "ops_per_segment_hist": {str(k): v for k, v in sorted(
            on_stats["ops_per_segment"].items())},
        "trace_cache_hit_rate": round(on_stats["trace_cache_hit_rate"], 4),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--output", default="opperf_results.json")
    ap.add_argument("--ops", default="",
                    help="comma-separated subset (default: all with specs)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--size", type=int, default=1024)
    ap.add_argument("--no-grad", action="store_true")
    ap.add_argument("--bulk-chain", action="store_true",
                    help="run the op-bulking chain microbenchmark "
                    "(per-op vs bulked dispatch) instead of the op sweep")
    ap.add_argument("--chain-len", type=int, default=50)
    ap.add_argument("--chain-size", type=int, default=64,
                    help="square side of the chain tensor; bulking "
                    "targets the small-op dispatch-bound regime, large "
                    "tensors hide dispatch behind async compute")
    ap.add_argument("--check", action="store_true",
                    help="with --bulk-chain: exit nonzero if bulked and "
                    "per-op outputs diverge or no bulking happened")
    ap.add_argument("--resume", action="store_true",
                    help="keep results already in --output and only "
                    "measure the rest (a run cut short by its time limit)")
    ap.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                    help="force a jax platform (sets jax.config directly)")
    args = ap.parse_args()
    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif args.platform == "tpu":
        if jax.devices()[0].platform == "cpu":
            print("--platform tpu: no accelerator available "
                  "(jax.devices() is CPU-only)", file=sys.stderr)
            sys.exit(2)

    from incubator_mxnet_tpu.ops import registry

    if args.bulk_chain:
        chain_size = args.chain_size
        res = bench_bulk_chain(args.steps, args.warmup,
                               chain_len=args.chain_len, size=chain_size)
        platform = jax.devices()[0].platform
        out = {"platform": platform, "bulk_chain": res}
        with open(args.output, "w") as f:
            json.dump(out, f, indent=1)
        print(f"bulk chain ({res['chain_len']} ops, {chain_size}x"
              f"{chain_size}): per-op {res['per_op_ms']:.3f} ms "
              f"({res['per_op_dispatches_per_run']} dispatches)  "
              f"bulked {res['bulked_ms']:.3f} ms "
              f"({res['bulked_launches_per_run']} launches, "
              f"{res['ops_per_segment_mean']} ops/segment, "
              f"cache hit rate {res['trace_cache_hit_rate']:.0%})  "
              f"max diff {res['max_ulp_diff']:.1f} ulp")
        # a fused segment may FMA-contract across op boundaries (same
        # float semantics as hybridize): a few ULPs is expected, more is
        # a real numeric divergence
        if args.check and not (res["max_ulp_diff"] <= 32.0
                               and res["bulked_launches_per_run"] >= 1
                               and res["ops_per_segment_mean"] > 1):
            print("bulk chain smoke FAILED: outputs diverged beyond ULP "
                  "noise or no bulking happened", file=sys.stderr)
            sys.exit(1)
        return

    specs = default_specs(args.size)
    # chip time is budgeted: measure the hot NN/linear-algebra ops
    # first so a run cut short by its time limit still yields the
    # latencies that matter (the resume flag picks up the tail later)
    priority = [
        "Convolution", "FullyConnected", "BatchNorm", "dot", "batch_dot",
        "Pooling", "Activation", "relu", "softmax", "log_softmax",
        "SoftmaxOutput", "softmax_cross_entropy", "LayerNorm", "Dropout",
        "elemwise_add", "elemwise_mul", "broadcast_add", "broadcast_mul",
        "sum", "mean", "max", "transpose", "Reshape", "concat", "take",
        "Embedding", "slice", "sigmoid", "tanh", "exp", "log", "sqrt",
        "where", "gather_nd", "topk", "argmax", "norm", "Deconvolution",
        "RNN", "add_n", "clip", "expand_dims", "one_hot",
    ]
    wanted = [s for s in args.ops.split(",") if s]
    if not wanted:
        rest = sorted(s for s in specs if s not in set(priority))
        wanted = [p for p in priority if p in specs] + rest
    results, skipped = {}, {}
    platform = jax.devices()[0].platform
    if args.resume and os.path.exists(args.output):
        with open(args.output) as f:
            prev = json.load(f)
        if prev.get("platform") == platform:
            results = prev.get("results", {})
            wanted = [n for n in wanted if n not in results]
            print(f"resuming: {len(results)} ops kept, "
                  f"{len(wanted)} to measure", flush=True)
    for name in wanted:
        spec = specs.get(name)
        if spec is None:
            skipped[name] = "no input spec"
            continue
        try:
            op = registry.get_op(name)
        except KeyError:
            skipped[name] = "not registered"
            continue
        try:
            a, kw = spec()
            fwd_ms, bwd_ms = bench_op(op, a, kw, args.steps, args.warmup,
                                      not args.no_grad)
            results[name] = {
                "fwd_ms": round(fwd_ms, 4),
                "bwd_ms": round(bwd_ms, 4) if bwd_ms is not None else None,
                "inputs": [list(x.shape) for x in a],
            }
            print(f"{name:48s} fwd {fwd_ms:9.4f} ms"
                  + (f"  bwd {bwd_ms:9.4f} ms" if bwd_ms else ""),
                  flush=True)
        except Exception as e:  # mxlint: allow-broad-except(sweep harness: the failure is recorded in the skipped table and the sweep continues)
            skipped[name] = f"{type(e).__name__}: {e}"[:200]
        # flush INCREMENTALLY: an op can hang mid-sweep, and the ops
        # already measured must survive the parent's kill
        out = {"platform": platform, "n_ops": len(results),
               "steps": args.steps, "results": results, "skipped": skipped}
        tmp = args.output + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1)
        os.replace(tmp, args.output)
    print(f"\n{len(results)} ops benchmarked, {len(skipped)} skipped "
          f"-> {args.output}")


if __name__ == "__main__":
    main()
