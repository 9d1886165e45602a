"""CPU-vs-TPU cross-backend oracle battery.

The reference's flagship correctness tool is check_consistency
(test_utils.py:1428): run the same op on every backend and cross-check.
This script does that for the real TPU at registry scale: every op
benchmark/opperf.py has an input spec for is run on the CPU backend and
the chip — forward in fp32 AND bf16, gradient in fp32 — and
cross-checked (VERDICT r3 Next #3).

Ops run in CHUNKED SUBPROCESSES under timeouts, one at a time (a chip
belongs to one process, and this parent never initializes a JAX
backend), results append to the artifact after every chunk, and
already-recorded ops are skipped on re-run — the battery is resumable
and an op that hangs costs one chunk.  Each child holds both sides of
the comparison: JAX's TPU backend and the host's CPU backend
(``jax.devices("cpu")``) live in one process.

Usage (on the chip, e.g. through the chip tool):
  python scripts/tpu_consistency.py [--out chiprun_out/consistency.json]
      [--deadline 1200] [--chunk 8] [--ops name1,name2]
Exit 0 iff every attempted op passed; 2 when JAX finds no TPU
(``CONSIST_SELF_TEST=1`` lets the harness compare the CPU with itself).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# ops whose outputs are legitimately backend-dependent
SKIP = {
    "arange", "eye",              # no tensor inputs; trivial + shape-only
    "RNN",                        # stateful signature, exercised in gluon
    "linalg_syevd", "linalg_gelqf",  # unique only up to column/row sign;
    # element-wise cross-backend compare is meaningless.  Correctness is
    # covered by reconstruction tests (tests/test_op_tail.py linalg).
}
# reductions/factorizations where fp32 associativity differs across
# backends more than the default tolerance
LOOSE = {"linalg_potri", "hawkesll", "softmax_cross_entropy", "norm"}

FP32_TOL = 2e-3
LOOSE_TOL = 2e-2
BF16_TOL = 4e-2


def op_list():
    """Curated opperf specs plus a generic fallback for every other
    registry op (dedup by canonical name).  Generic cases that the CPU
    oracle itself cannot run are recorded as 'skip', not 'fail' — the
    battery measures CPU↔TPU parity, not spec completeness."""
    from benchmark.opperf import default_specs
    from incubator_mxnet_tpu.ops import registry
    specs = default_specs(n=256)

    import numpy as onp
    rng = onp.random.RandomState(7)

    def generic(nin):
        def gen():
            import jax.numpy as jnp
            return ([jnp.asarray(rng.rand(8, 8) + 0.5, jnp.float32)
                     for _ in range(nin)], {})
        return gen

    # domain-constrained inputs the generic fallback can't guess
    import jax.numpy as _jnp
    specs["arccosh"] = lambda: (
        [_jnp.asarray(rng.rand(8, 8) + 1.1, _jnp.float32)], {})
    specs["arctanh"] = lambda: (
        [_jnp.asarray(rng.rand(8, 8) * 1.6 - 0.8, _jnp.float32)], {})
    specs["erfinv"] = lambda: (
        [_jnp.asarray(rng.rand(8, 8) * 1.6 - 0.8, _jnp.float32)], {})
    _m = rng.rand(8, 8)
    specs["linalg_potrf"] = lambda: (
        [_jnp.asarray(_m @ _m.T + 8 * onp.eye(8), _jnp.float32)], {})
    # index/kwarg-constrained ops the generic 8x8-floats fallback skips
    specs["gather_nd"] = lambda: (
        [_jnp.asarray(rng.rand(6, 7), _jnp.float32),
         _jnp.asarray(rng.randint(0, 1000, (2, 5)) % onp.array([[6], [7]]),
                      _jnp.int32)], {})
    # scatter sites must be UNIQUE: with duplicates, .set() ordering is
    # backend-unspecified and .add() rounding is order-dependent — either
    # would make the cross-backend compare a flake
    specs["index_add_nd"] = lambda: (
        [_jnp.asarray(rng.rand(6, 7), _jnp.float32),
         _jnp.asarray(rng.permutation(6)[:5].reshape(1, 5), _jnp.int32),
         _jnp.asarray(rng.rand(5, 7), _jnp.float32)], {})
    specs["index_update_nd"] = lambda: (
        [_jnp.asarray(rng.rand(6, 7), _jnp.float32),
         _jnp.asarray(rng.permutation(6)[:5].reshape(1, 5), _jnp.int32),
         _jnp.asarray(rng.rand(5, 7), _jnp.float32)], {})
    specs["im2col"] = lambda: (
        [_jnp.asarray(rng.rand(2, 3, 10, 10), _jnp.float32)],
        {"kernel": (3, 3), "stride": (1, 1), "pad": (1, 1)})
    specs["image_crop"] = lambda: (
        [_jnp.asarray(rng.rand(10, 12, 3), _jnp.float32)],
        {"x_start": 2, "y_start": 1, "width": 6, "height": 5})
    specs["_contrib_RROIAlign"] = lambda: (
        [_jnp.asarray(rng.rand(2, 3, 16, 16), _jnp.float32),
         _jnp.asarray([[0, 8.0, 8.0, 6.0, 4.0, 30.0],
                       [1, 5.0, 7.0, 4.0, 4.0, -15.0]], _jnp.float32)],
        {"pooled_size": (3, 3), "spatial_scale": 1.0})

    seen_canonical = set()
    for name in registry.list_ops():
        op = registry.get_op(name)
        if op.name in seen_canonical:
            continue
        seen_canonical.add(op.name)
        if op.name in specs or op.name in SKIP:
            continue
        if any(tok in op.name.lower() for tok in
               ("random", "sample", "shuffle", "dropout", "rand")):
            continue  # stochastic: parity is a seeding contract, not
            # bitwise (docs/migration.md RNG note)
        info = registry.describe_op(op)
        nin = len([i for i in info["inputs"] if i != "*args"])
        if not (1 <= nin <= 3):
            continue
        specs[op.name] = generic(nin)
    return specs, [k for k, v in sorted(specs.items())
                   if v is not None and k not in SKIP]


def _child(names):
    import jax
    import numpy as onp
    import jax.numpy as jnp

    cpu0 = jax.local_devices(backend="cpu")[0]
    accel = jax.devices()[0]
    if accel.platform != "tpu" and os.environ.get(
            "CONSIST_SELF_TEST") != "1":
        print("NO_ACCELERATOR", flush=True)
        return
    from incubator_mxnet_tpu.ops import registry
    specs, _ = op_list()

    def to_np(t):
        return onp.asarray(jax.device_get(t))

    def run_on(dev, op, args_np, kwargs, dtype):
        args = []
        for a in args_np:
            t = jnp.asarray(a)
            if dtype == "bfloat16" and jnp.issubdtype(t.dtype, jnp.floating):
                t = t.astype(jnp.bfloat16)
            args.append(jax.device_put(t, dev))
        fwd = jax.jit(lambda *a: op.fn(*a, **kwargs))
        out = fwd(*args)
        outs = [to_np(t).astype("float32")
                for t in jax.tree_util.tree_leaves(out)]
        grads = []
        if dtype == "float32" and op.differentiable:
            fpos = tuple(i for i, a in enumerate(args)
                         if jnp.issubdtype(a.dtype, jnp.floating))
            if fpos:
                def loss(*a):
                    o = op.fn(*a, **kwargs)
                    return sum(jnp.sum(l.astype(jnp.float32))
                               for l in jax.tree_util.tree_leaves(o)
                               if jnp.issubdtype(l.dtype, jnp.floating))
                g = jax.jit(jax.grad(loss, argnums=fpos))(*args)
                grads = [to_np(t).astype("float32")
                         for t in jax.tree_util.tree_leaves(g)]
        return outs, grads

    for name in names:
        t0 = time.monotonic()
        try:
            op = registry.get_op(name)
            gen = specs[name]
            args, kwargs = gen()
            args_np = [to_np(a) for a in args]
            tol = LOOSE_TOL if name in LOOSE else FP32_TOL
            worst = 0.0
            passed_dtypes = []
            for dtype, dtol in (("float32", tol), ("bfloat16", BF16_TOL)):
                try:
                    ref_o, ref_g = run_on(cpu0, op, args_np, kwargs, dtype)
                except Exception as e:  # mxlint: allow-broad-except(the CPU oracle cannot run this leg - a spec gap, not a TPU parity failure)
                    # can't run this leg: a spec/kernel gap, not a TPU
                    # parity failure.  A completed fp32 verdict is kept
                    # (LAPACK-backed ops often have no bf16 CPU kernel).
                    msg = f"{type(e).__name__}"[:80]
                    if passed_dtypes:
                        print(f"RESULT {name} ok {worst:.3e} "
                              f"{'+'.join(passed_dtypes)}-only "
                              f"(cpu-oracle {msg} on {dtype})", flush=True)
                    else:
                        print(f"RESULT {name} skip cpu-oracle {msg}",
                              flush=True)
                    break
                got_o, got_g = run_on(accel, op, args_np, kwargs, dtype)
                for r, g in zip(ref_o + ref_g, got_o + got_g):
                    finite = onp.isfinite(r) & onp.isfinite(g)
                    denom = onp.maximum(onp.abs(r), 1.0)
                    diff = onp.where(finite, onp.abs(r - g) / denom, 0.0)
                    err = float(onp.max(diff)) if r.size else 0.0
                    worst = max(worst, err)
                    # equal_nan: agreeing on the invalid domain IS
                    # consistency; disagreeing (one finite, one not)
                    # fails via the isfinite mask below
                    if not onp.allclose(r, g, rtol=dtol, atol=dtol,
                                        equal_nan=True):
                        raise AssertionError(
                            f"{dtype} mismatch rel-err {err:.3e} > {dtol}")
                    if not bool(onp.all(onp.isfinite(r) ==
                                        onp.isfinite(g))):
                        raise AssertionError(
                            f"{dtype} finiteness mismatch")
                passed_dtypes.append(dtype)
            else:
                print(f"RESULT {name} ok {worst:.3e} "
                      f"{time.monotonic() - t0:.1f}s", flush=True)
        except Exception as e:  # mxlint: allow-broad-except(parity sweep: the op is recorded as FAIL and the sweep continues)
            msg = f"{type(e).__name__}: {e}"[:160].replace("\n", " ")
            print(f"RESULT {name} FAIL {msg}", flush=True)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="chiprun_out/consistency.json")
    p.add_argument("--deadline", type=float, default=1200.0)
    p.add_argument("--chunk", type=int, default=8)
    p.add_argument("--ops", default=None)
    p.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.child is not None:
        _child(args.child.split(","))
        return 0

    t_start = time.monotonic()
    remaining = lambda: args.deadline - (time.monotonic() - t_start)  # noqa

    _, names = op_list()
    if args.ops:
        names = args.ops.split(",")

    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f).get("ops", {})
        if not args.ops:
            # resume: FAILed ops get a retry; passes and skips are kept.
            # An explicit --ops list always re-runs what it names.
            names = [n for n in names
                     if results.get(n, {}).get("status")
                     not in ("ok", "skip")]
    print(f"{len(names)} ops to run", flush=True)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    def flush():
        ok = sum(1 for r in results.values() if r["status"] == "ok")
        skip = sum(1 for r in results.values() if r["status"] == "skip")
        doc = {"format": "tpu_consistency_v1", "passed": ok,
               "skipped": skip, "failed": len(results) - ok - skip,
               "total": len(results), "ops": results}
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, args.out)

    i = 0
    while i < len(names) and remaining() > 90:
        chunk = names[i:i + args.chunk]
        i += args.chunk
        # generous first-compile allowance, then ~20s/op
        budget = min(120 + 25 * len(chunk), remaining() - 10)
        timed_out, stderr_tail = False, ""
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--child", ",".join(chunk)],
                capture_output=True, text=True, timeout=budget)
            out = proc.stdout
            stderr_tail = (proc.stderr or "")[-300:].replace("\n", " | ")
        except subprocess.TimeoutExpired as e:
            timed_out = True
            out = (e.stdout or b"").decode() if isinstance(
                e.stdout, bytes) else (e.stdout or "")
            print(f"chunk timed out after {budget:.0f}s", flush=True)
        if "NO_ACCELERATOR" in out:
            print("no accelerator visible — aborting", flush=True)
            return 2
        seen = set()
        for line in out.splitlines():
            if not line.startswith("RESULT "):
                continue
            _, name, status, *rest = line.split(" ", 3)
            seen.add(name)
            results[name] = {
                "status": status if status in ("ok", "skip") else "fail",
                "detail": " ".join(rest)}
            print(line, flush=True)
        # crash vs hang: a chunk that FINISHED without emitting results
        # is a harness crash (import error, registry break) and must
        # read as one — a silent skip would let the battery rot green;
        # a chunk that TIMED OUT leaves its unfinished ops failed
        missing_why = ("no result (hang/timeout)" if timed_out else
                       f"child crashed: {stderr_tail or 'no stderr'}")
        for name in chunk:
            if name not in seen and name not in results:
                results[name] = {"status": "fail", "detail": missing_why}
                print(f"RESULT {name} FAIL {missing_why}", flush=True)
        flush()

    ok = sum(1 for r in results.values() if r["status"] == "ok")
    skip = sum(1 for r in results.values() if r["status"] == "skip")
    fail = len(results) - ok - skip
    print(f"DONE {ok} ok / {skip} skip / {fail} fail "
          f"({len(names) - min(i, len(names))} not attempted)", flush=True)
    return 0 if fail == 0 and i >= len(names) else 1


if __name__ == "__main__":
    sys.exit(main())
