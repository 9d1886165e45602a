#!/usr/bin/env python
"""graphlint CLI — IR-level static analysis of traced graphs.

Usage:
    python tools/graphlint.py --zoo resnet18_v1 --batch 8   # a model zoo net
    python tools/graphlint.py --ops-smoke                   # curated op sweep
    python tools/graphlint.py --op FullyConnected \
        --spec 8x256:float32 --spec 64x256:float32          # one op
    python tools/graphlint.py --selftest     # seeded violations per rule
    python tools/graphlint.py --json --ignore GL-TILE001 ...

Exit status: 0 when no error-severity findings beyond the baseline
(advisories are reported but never gate), 1 otherwise.  Rule catalog:
docs/graph_analysis.md.  Unlike mxlint this tool traces — it imports
the framework (and jax) and runs on the CPU backend.

``--zoo`` lints the block's forward in BOTH inference and training
mode (training exercises the BatchNorm stats path and dropout masks).
``--ops-smoke`` sweeps a curated set of central operators at canonical
shapes in f32 and bf16 — the compiled surface almost every model
shares.  ``--selftest`` seeds one violation per rule (plus a shape-leak
recompile storm and a strict-mode ``check_traced``) and requires each
expected rule id / typed error to surface — proving the CI stage would
catch the real thing.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "ci", "graphlint_baseline.json")

# (op, specs as (shape, dtype), static kwargs) — central ops most graphs
# share; bf16 entries prove the low-precision paths accumulate wide
_OPS_SMOKE = [
    ("FullyConnected", [((8, 256), "float32"), ((64, 256), "float32"),
                        ((64,), "float32")], {}),
    ("FullyConnected", [((8, 256), "bfloat16"), ((64, 256), "bfloat16"),
                        ((64,), "bfloat16")], {}),
    ("Convolution", [((2, 8, 16, 16), "float32"), ((16, 8, 3, 3),
                     "float32")], {"kernel": (3, 3), "num_filter": 16,
                                   "pad": (1, 1)}),
    ("BatchNorm", [((8, 16, 8, 8), "bfloat16")] + [((16,), "float32")] * 4,
     {"training": True}),
    ("BatchNorm", [((8, 16, 8, 8), "float32")] + [((16,), "float32")] * 4,
     {}),
    ("Pooling", [((4, 16, 16, 16), "bfloat16")],
     {"kernel": (2, 2), "pool_type": "avg"}),
    ("Pooling", [((4, 16, 16, 16), "bfloat16")],
     {"global_pool": True, "pool_type": "avg"}),
    ("LayerNorm", [((16, 128), "bfloat16"), ((128,), "float32"),
                   ((128,), "float32")], {}),
    ("softmax", [((32, 128), "bfloat16")], {}),
    ("softmax_xent", [((32, 128), "float32"), ((32,), "float32")], {}),
    ("sum", [((64, 1024), "bfloat16")], {"axis": 1}),
    ("mean", [((64, 1024), "bfloat16")], {"axis": 1}),
]


def selftest():
    """Seed one violation per rule and require the expected rule id —
    plus the sentinel's storm error and strict-mode check_traced."""
    import warnings

    import numpy as onp
    import jax
    import jax.numpy as jnp
    from jax import lax

    from incubator_mxnet_tpu import error
    from incubator_mxnet_tpu.analysis import graphlint as gl
    from incubator_mxnet_tpu.analysis import recompile as rc

    failures = []

    def expect(tag, rules, fn, *args, **kw):
        got = {f.rule for f in gl.lint_fn(fn, *args, **kw)}
        if not set(rules) <= got:
            failures.append(f"{tag}: wanted {rules}, got {sorted(got)}")
        else:
            print(f"[selftest] {tag}: {sorted(rules)} OK")

    with jax.enable_x64(True):
        expect("f64-upcast", ["GL-DTYPE001"],
               lambda x: (x.astype(jnp.float64) * 2.0).sum(),
               jnp.ones((4,), jnp.float32))
    baked = onp.ones((600, 600), onp.float32)
    expect("baked-const", ["GL-CONST001"], lambda x: x @ baked,
           jnp.ones((2, 600)))
    expect("host-callback", ["GL-HOST001"],
           lambda x: jax.pure_callback(
               lambda a: onp.asarray(a) * 2,
               jax.ShapeDtypeStruct(x.shape, x.dtype), x),
           jnp.ones((4,)))
    expect("dead-code", ["GL-DEAD001"],
           lambda x: (jnp.sin(x), (x * 2).sum())[1], jnp.ones((4,)))
    expect("promotion", ["GL-DTYPE002"],
           lambda x, w: x * w, jnp.ones((8,), jnp.bfloat16),
           jnp.ones((8,), jnp.float32))
    expect("bf16-accum", ["GL-PREC001"],
           lambda x: lax.reduce_window(x, 0.0, lax.add, (1024,), (1,),
                                       "VALID"),
           jnp.ones((2048,), jnp.bfloat16))
    expect("tile-layout", ["GL-TILE001"],
           lambda x: x.reshape(65536, 4) * 2, jnp.ones((4 * 65536,)))
    expect("donate-advisory", ["GL-DONATE001"],
           lambda p, g: p - 0.1 * g, jnp.ones((1024,)),
           jnp.ones((1024,)), check_donation=True)

    # shape-leak recompile storm -> typed error with the diagnosis
    rc.reset()
    try:
        with rc.sentinel_scope("raise", 3):
            for n in range(1, 10):
                rc.record_compile(
                    "selftest:leak", (("arr", (n, 8), "float32"),))
        failures.append("recompile-storm: RecompileStormError not raised")
    except error.RecompileStormError as e:
        if "varying leading/batch" not in str(e):
            failures.append(f"recompile-storm: diagnosis missing: {e}")
        else:
            print("[selftest] recompile-storm: RecompileStormError OK")
    finally:
        rc.reset()

    # strict check_traced -> GraphLintError (and warn mode only warns)
    prev = gl.set_lint_mode("strict")
    try:
        gl.check_traced(lambda x: (jnp.sin(x), x.sum())[1],
                        (jnp.ones((4,)),), name="selftest:strict")
        failures.append("strict-mode: GraphLintError not raised")
    except error.GraphLintError:
        print("[selftest] strict-mode: GraphLintError OK")
    finally:
        gl.set_lint_mode(prev)
    gl.set_lint_mode("warn")
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            gl.check_traced(lambda x: (jnp.sin(x), x.sum())[1],
                            (jnp.ones((4,)),), name="selftest:warn")
        if not any("GL-DEAD001" in str(x.message) for x in w):
            failures.append("warn-mode: no GL-DEAD001 warning emitted")
        else:
            print("[selftest] warn-mode: warning OK")
    finally:
        gl.set_lint_mode(prev)

    for f in failures:
        print(f"[selftest] FAIL {f}")
    print("[selftest] " + ("FAILED" if failures
                           else "all seeded violations caught"))
    return 1 if failures else 0


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="graphlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--zoo", action="append", default=[],
                   help="model_zoo.vision factory name (repeatable)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--op", default=None, help="registered op name")
    p.add_argument("--spec", action="append", default=[],
                   help="input spec for --op as NxM...:dtype (repeatable, "
                        "in positional order)")
    p.add_argument("--kw", action="append", default=[],
                   help="static kwarg for --op as name=value (python "
                        "literal), repeatable")
    p.add_argument("--ops-smoke", action="store_true",
                   help="lint the curated central-operator sweep")
    p.add_argument("--selftest", action="store_true",
                   help="seed one violation per rule; each must surface")
    p.add_argument("--ignore", action="append", default=[],
                   help="rule id to silence (repeatable)")
    p.add_argument("--baseline", default=None,
                   help=f"baseline JSON (default: {DEFAULT_BASELINE} "
                        "when it exists; same contract as mxlint)")
    p.add_argument("--prune-stale", action="store_true",
                   help="rewrite the baseline file with its stale "
                        "entries removed, then report as usual")
    p.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)

    if not (args.zoo or args.op or args.ops_smoke or args.selftest):
        p.error("nothing to lint: pass --zoo, --op, --ops-smoke "
                "and/or --selftest")

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import incubator_mxnet_tpu as mx   # noqa: F401  (registers ops)
    from incubator_mxnet_tpu.analysis import findings as flib
    from incubator_mxnet_tpu.analysis import graphlint as gl

    if args.selftest:
        rc = selftest()
        if rc or not (args.zoo or args.op or args.ops_smoke):
            return rc

    config = gl.Config(ignore=args.ignore)
    findings = []
    wheres = []   # entry labels this run analyzed (prune-stale scope)

    for name in args.zoo:
        from incubator_mxnet_tpu import nd
        from incubator_mxnet_tpu.gluon.model_zoo import vision
        net = vision.get_model(name, classes=10)
        net.initialize()
        x = nd.random.uniform(
            shape=(args.batch, 3, args.image_size, args.image_size))
        net(x)   # materialize deferred-shape parameters
        for training in (False, True):
            mode = "train" if training else "infer"
            wheres.append(f"zoo:{name}:{mode}")
            findings += gl.lint_block(net, x, training=training,
                                      where=f"zoo:{name}:{mode}",
                                      config=config)

    def parse_spec(s):
        dims, _, dtype = s.partition(":")
        shape = tuple(int(d) for d in dims.split("x") if d)
        return (shape, dtype or "float32")

    if args.op:
        import ast
        kwargs = {}
        for kv in args.kw:
            k, _, v = kv.partition("=")
            try:
                kwargs[k] = ast.literal_eval(v)
            except (ValueError, SyntaxError):
                kwargs[k] = v
        from incubator_mxnet_tpu.ops.registry import get_op
        # canonical name: findings are labeled op:<op.name>, so an
        # alias spelling (--op Reshape) must scope the same entries
        wheres.append(f"op:{get_op(args.op).name}")
        findings += gl.lint_op(args.op,
                               *[parse_spec(s) for s in args.spec],
                               config=config, **kwargs)

    if args.ops_smoke:
        from incubator_mxnet_tpu.ops.registry import get_op
        for op, specs, kwargs in _OPS_SMOKE:
            wheres.append(f"op:{get_op(op).name}")
            findings += gl.lint_op(op, *specs, config=config, **kwargs)

    baseline_path = args.baseline or (
        DEFAULT_BASELINE if os.path.exists(DEFAULT_BASELINE) else None)
    baseline = (flib.load_baseline(baseline_path) if baseline_path
                else {})
    errors = [f for f in findings if f.severity == "error"]
    advisories = [f for f in findings if f.severity != "error"]
    regressions, suppressed, stale = flib.apply_baseline(errors, baseline)

    if args.prune_stale and stale and baseline_path:
        # only entries whose analyzed surface ran this invocation are
        # prunable — a --zoo/--op subset must not delete the rest of
        # the surfaces' justified entries.  Baseline "file" is the
        # finding's where+path (path always begins with "/"), so the
        # "/" boundary keeps op:relu from claiming op:relu6's entries
        def in_scope(key):
            return any(key[1] == w or key[1].startswith(w + "/")
                       for w in wheres)

        pruned = [k for k in stale if in_scope(k)]
        flib.prune_stale_baseline(baseline_path, stale,
                                  in_scope=in_scope)
        print(f"[graphlint] pruned {len(pruned)} stale entr"
              f"{'y' if len(pruned) == 1 else 'ies'} from {baseline_path}"
              + (f" ({len(stale) - len(pruned)} out-of-scope kept)"
                 if len(pruned) != len(stale) else ""))
        stale = [k for k in stale if not in_scope(k)]

    if args.as_json:
        print(json.dumps({
            "regressions": [f.as_dict() for f in regressions],
            "suppressed": [f.as_dict() for f in suppressed],
            "advisories": [f.as_dict() for f in advisories],
            "stale_baseline": [list(k) for k in stale],
        }, indent=2))
    else:
        if regressions:
            print(gl.render(regressions))
        if advisories:
            print(gl.render(advisories))
        for key in stale:
            print(f"[graphlint] note: stale baseline entry {key} — the "
                  "finding is gone, drop it from the baseline")
        print(f"[graphlint] {len(regressions)} finding(s), "
              f"{len(advisories)} advisor{'y' if len(advisories) == 1 else 'ies'}, "
              f"{len(suppressed)} baselined, {len(stale)} stale")
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
