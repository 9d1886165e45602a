"""graphlint (IR jaxpr passes) + recompilation sentinel
(docs/graph_analysis.md).

Each rule gets a must-flag and a must-pass fixture; the framework's own
graphs (model zoo forward, Symbol executor, curated op sweep) are
pinned at ZERO findings; the sentinel batteries prove storm detection,
churn diagnosis, bucketed-replay silence and flag-off inertness.
"""
import warnings

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import error, profiler
from incubator_mxnet_tpu.analysis import graphlint as gl
from incubator_mxnet_tpu.analysis import recompile as rc
from incubator_mxnet_tpu.ops import registry


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# per-rule fixtures
# ---------------------------------------------------------------------------

class TestConstRule:
    def test_baked_constant_flags(self):
        big = onp.ones((600, 600), onp.float32)   # 1.44 MB > 1 MiB

        def f(x):
            return x @ big

        fs = gl.lint_fn(f, jnp.ones((2, 600)))
        assert rules_of(fs) == ["GL-CONST001"]
        assert "600, 600" in fs[0].message

    def test_passed_as_argument_clean(self):
        fs = gl.lint_fn(lambda x, w: x @ w, jnp.ones((2, 600)),
                        jnp.ones((600, 600)))
        assert fs == []

    def test_threshold_configurable(self):
        small = onp.ones((64, 64), onp.float32)   # 16 KB

        def f(x):
            return x @ small

        assert gl.lint_fn(f, jnp.ones((2, 64))) == []
        fs = gl.lint_fn(f, jnp.ones((2, 64)),
                        config=gl.Config(const_bytes=1024))
        assert rules_of(fs) == ["GL-CONST001"]


class TestDeadRule:
    def test_dead_eqn_flags(self):
        def f(x):
            _dead = jnp.sin(x)
            return (x * 2).sum()

        fs = gl.lint_fn(f, jnp.ones((4,)))
        assert rules_of(fs) == ["GL-DEAD001"]
        assert "sin" in fs[0].message

    def test_all_used_clean(self):
        assert gl.lint_fn(lambda x: (jnp.sin(x) + x * 2).sum(),
                          jnp.ones((4,))) == []

    def test_dead_inside_scan_body_located(self):
        def f(x):
            def body(c, t):
                _dead = jnp.cos(t) * 3.0
                return c + t, c

            return lax.scan(body, jnp.zeros_like(x[0]), x)[0]

        fs = gl.lint_fn(f, jnp.ones((4, 4)))
        assert any(f_.rule == "GL-DEAD001" and "/scan" in f_.path
                   for f_ in fs)

    def test_multi_output_partially_used_clean(self):
        """One consumed output keeps a multi-output eqn alive: scan's
        stacked ys go unused, but the carry is — the scan eqn itself
        must not be reported dead."""
        def f(x):
            carry, _ys = lax.scan(lambda c, t: (c + t, c * 2),
                                  jnp.zeros_like(x[0]), x)
            return carry.sum()

        fs = gl.lint_fn(f, jnp.ones((3, 4)))
        assert not any(f_.primitive == "scan" for f_ in fs)


class TestPromotionRule:
    def test_f32_array_promotes_bf16_flags(self):
        def f(x):
            c = jnp.ones((4,), jnp.float32) * 2.0
            return x + c

        fs = gl.lint_fn(f, jnp.ones((4,), jnp.bfloat16))
        assert rules_of(fs) == ["GL-DTYPE002"]

    def test_f32_param_promotes_bf16_flags(self):
        fs = gl.lint_fn(lambda x, w: x * w,
                        jnp.ones((8,), jnp.bfloat16),
                        jnp.ones((8,), jnp.float32))
        assert rules_of(fs) == ["GL-DTYPE002"]

    def test_deliberate_upcast_region_clean(self):
        """A layer_norm-style f32 compute region: the widened value only
        ever meets values derived from itself (taint exemption)."""
        def f(x):
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, keepdims=True)
            return ((xf - mean) ** 2).astype(x.dtype)

        assert gl.lint_fn(f, jnp.ones((64,), jnp.bfloat16)) == []

    def test_weak_python_scalar_clean(self):
        assert gl.lint_fn(lambda x: x * 2.0 + 1.0,
                          jnp.ones((4,), jnp.bfloat16)) == []

    def test_framework_layer_norm_clean(self):
        fs = gl.lint_op("LayerNorm", ((16, 128), "bfloat16"),
                        ((128,), "float32"), ((128,), "float32"))
        assert fs == []


class TestAccumRule:
    def test_bf16_reduce_window_flags(self):
        def f(x):
            return lax.reduce_window(x, 0.0, lax.add, (1024,), (1,),
                                     "VALID")

        fs = gl.lint_fn(f, jnp.ones((2048,), jnp.bfloat16))
        assert rules_of(fs) == ["GL-PREC001"]
        assert "1024" in fs[0].message

    def test_jnp_sum_bf16_clean(self):
        """jnp.sum upcasts bf16 to f32 internally — must not flag."""
        assert gl.lint_fn(lambda x: jnp.sum(x),
                          jnp.ones((4096,), jnp.bfloat16)) == []

    def test_small_window_clean(self):
        """A 3x3 pool window accumulates 9 elements — under threshold."""
        fs = gl.lint_op("Pooling", ((2, 8, 16, 16), "bfloat16"),
                        kernel=(3, 3), pool_type="avg")
        assert fs == []

    def test_f32_reduce_clean(self):
        def f(x):
            return lax.reduce_window(x, 0.0, lax.add, (1024,), (1,),
                                     "VALID")

        assert gl.lint_fn(f, jnp.ones((2048,), jnp.float32)) == []

    def test_pooling_bf16_big_window_fixed(self):
        """The finding this rule surfaced in the framework: avg pooling
        with a big window now accumulates in f32 (lint clean) and its
        value tracks the f32 reference instead of drifting."""
        fs = gl.lint_op("Pooling", ((1, 4, 64, 64), "bfloat16"),
                        kernel=(64, 64), pool_type="avg")
        assert fs == []
        op = registry.get_op("Pooling")
        x32 = jax.random.uniform(jax.random.PRNGKey(7), (1, 2, 64, 64),
                                 jnp.float32)
        ref = op.fn(x32, kernel=(64, 64), pool_type="avg")
        got = op.fn(x32.astype(jnp.bfloat16), kernel=(64, 64),
                    pool_type="avg")
        # a bf16-accumulated 4096-element sum saturates (~88% relative
        # error); f32 accumulation lands within one bf16 ulp of the ref
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))) \
            < 8e-3
        assert got.dtype == jnp.bfloat16


class TestHostRule:
    def test_pure_callback_flags(self):
        def f(x):
            return jax.pure_callback(
                lambda a: onp.asarray(a) * 2,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        fs = gl.lint_fn(f, jnp.ones((4,)))
        assert "GL-HOST001" in rules_of(fs)


class TestTileRule:
    def test_long_skinny_flags(self):
        fs = gl.lint_fn(lambda x: x.reshape(65536, 4) * 2,
                        jnp.ones((4 * 65536,)))
        assert rules_of(fs) == ["GL-TILE001"]
        assert "(65536, 4)" in fs[0].message

    def test_lane_aligned_clean(self):
        assert gl.lint_fn(lambda x: x.reshape(2048, 128) * 2,
                          jnp.ones((2048 * 128,))) == []

    def test_small_array_clean(self):
        assert gl.lint_fn(lambda x: x.reshape(256, 4) * 2,
                          jnp.ones((1024,))) == []


class TestF64Rule:
    def test_f64_flags_under_x64(self):
        with jax.enable_x64(True):
            def f(x):
                return (x.astype(jnp.float64) * 2.0).sum()

            fs = gl.lint_fn(f, jnp.ones((4,), jnp.float32))
        assert "GL-DTYPE001" in rules_of(fs)

    def test_f32_clean(self):
        assert gl.lint_fn(lambda x: (x * 2.0).sum(),
                          jnp.ones((4,), jnp.float32)) == []


# ---------------------------------------------------------------------------
# framework surfaces + config plumbing
# ---------------------------------------------------------------------------

class TestEntryPoints:
    def test_ignore_silences(self):
        def f(x):
            _dead = jnp.sin(x)
            return x.sum()

        assert gl.lint_fn(f, jnp.ones((4,)),
                          config=gl.Config(ignore={"GL-DEAD001"})) == []

    def test_render_and_dicts(self):
        def f(x):
            _dead = jnp.sin(x)
            return x.sum()

        fs = gl.lint_fn(f, jnp.ones((4,)), where="toy")
        text = gl.render(fs)
        assert "GL-DEAD001" in text and "toy" in text
        d = fs[0].as_dict()
        assert d["rule"] == "GL-DEAD001" and d["where"] == "toy"

    def test_lint_op_accepts_shape_dtype_specs(self):
        assert gl.lint_op("FullyConnected", ((8, 32), "float32"),
                          ((16, 32), "float32"), ((16,), "float32")) == []

    def test_zoo_block_clean_both_modes(self):
        from incubator_mxnet_tpu.gluon.model_zoo import vision
        net = vision.get_model("resnet18_v1", classes=10)
        net.initialize()
        x = mx.nd.random.uniform(shape=(2, 3, 32, 32))
        net(x)
        assert gl.lint_block(net, x) == []
        assert gl.lint_block(net, x, training=True) == []

    def test_symbol_clean_and_missing_shape_raises(self):
        from incubator_mxnet_tpu import sym
        data = sym.var("data")
        net = sym.FullyConnected(data, num_hidden=16, name="fc1")
        net = sym.Activation(net, act_type="relu")
        shapes = {"data": (4, 8), "fc1_weight": (16, 8),
                  "fc1_bias": (16,)}
        assert gl.lint_symbol(net, shapes) == []
        with pytest.raises(ValueError, match="fc1_weight"):
            gl.lint_symbol(net, {"data": (4, 8)})

    def test_ops_smoke_sweep_clean(self):
        """The CI stage's curated central-op sweep is pinned clean."""
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "_glcli", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "graphlint.py"))
        cli = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cli)
        for op, specs, kwargs in cli._OPS_SMOKE:
            assert gl.lint_op(op, *specs, **kwargs) == [], \
                f"{op} {kwargs} not clean"

    def test_seeded_violation_fails_cli_path(self):
        """A deliberately dirty graph exits 1 through lint_op, the same
        path the CI graphlint stage uses."""
        from incubator_mxnet_tpu.ops.registry import register, _OPS
        name = "_test_graphlint_dirty"

        @register(name)
        def dirty(x):
            _dead = jnp.sin(x)
            return x * 2

        try:
            fs = gl.lint_op(name, ((8,), "float32"))
            assert rules_of(fs) == ["GL-DEAD001"]
        finally:
            _OPS.pop(name, None)


class TestCallingConvention:
    def test_unused_argument_advisory(self):
        fs = gl.lint_fn(lambda x, unused: x * 2, jnp.ones((4,)),
                        jnp.ones((8,)))
        adv = [f for f in fs if f.rule == "GL-DEAD001"]
        assert adv and adv[0].severity == "advisory"
        assert "argument 1" in adv[0].message

    def test_allow_unused_args_silences(self):
        fs = gl.lint_fn(lambda x, unused: x * 2, jnp.ones((4,)),
                        jnp.ones((8,)), allow_unused_args=(1,))
        assert fs == []

    def test_donation_advisory_and_donated_clean(self):
        def sgd(p, g):
            return p - 0.1 * g

        args = (jnp.ones((1024,)), jnp.ones((1024,)))
        fs = gl.lint_fn(sgd, *args, check_donation=True)
        assert [f.rule for f in fs] == ["GL-DONATE001"]
        assert fs[0].severity == "advisory"
        assert gl.lint_fn(sgd, *args, check_donation=True,
                          donate_argnums=(0,)) == []

    def test_donation_off_by_default(self):
        assert gl.lint_fn(lambda p, g: p - 0.1 * g,
                          jnp.ones((1024,)), jnp.ones((1024,))) == []

    def test_small_buffers_not_advised(self):
        assert gl.lint_fn(lambda p, g: p - 0.1 * g, jnp.ones((8,)),
                          jnp.ones((8,)), check_donation=True) == []


@pytest.fixture()
def lint_off():
    prev = gl.set_lint_mode(None)
    yield
    gl.set_lint_mode(prev)


class TestCheckTraced:
    def test_inert_by_default(self, lint_off):
        assert gl.lint_mode() is None
        assert gl.check_traced(lambda x: (jnp.sin(x), x)[1],
                               (jnp.ones((4,)),)) is None

    def test_warn_mode_warns_and_returns(self, lint_off):
        gl.set_lint_mode("warn")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            fs = gl.check_traced(lambda x: (jnp.sin(x), x.sum())[1],
                                 (jnp.ones((4,)),), name="toy")
        assert [f.rule for f in fs] == ["GL-DEAD001"]
        assert any("GL-DEAD001" in str(x.message) for x in w)

    def test_strict_mode_raises_on_error_severity(self, lint_off):
        gl.set_lint_mode("strict")
        with pytest.raises(error.GraphLintError, match="GL-DEAD001"):
            gl.check_traced(lambda x: (jnp.sin(x), x.sum())[1],
                            (jnp.ones((4,)),), name="toy")

    def test_strict_mode_advisory_only_warns(self, lint_off):
        gl.set_lint_mode("strict")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            fs = gl.check_traced(lambda p, g: p - 0.1 * g,
                                 (jnp.ones((1024,)), jnp.ones((1024,))),
                                 name="toy", check_donation=True)
        assert [f.rule for f in fs] == ["GL-DONATE001"]
        assert any("GL-DONATE001" in str(x.message) for x in w)

    def test_untraceable_fn_warns_never_raises(self, lint_off):
        gl.set_lint_mode("strict")

        def bad(x):
            raise RuntimeError("cannot trace me")

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out = gl.check_traced(bad, (jnp.ones((4,)),), name="toy")
        assert out is None
        assert any("could not analyze" in str(x.message) for x in w)

    def test_cachedop_choke_strict_catches_seeded_dirty_block(
            self, lint_off):
        from incubator_mxnet_tpu.gluon import nn

        class Dirty(nn.HybridSequential):
            def forward(self, x):
                _dead = (x * 3).sum()   # seeded dead compute
                return super().forward(x)

        net = Dirty()
        net.add(nn.Dense(4))
        net.initialize()
        net.hybridize()
        x = mx.nd.ones((2, 8))
        net(x)   # first pass (deferred init) runs eagerly, no lint
        gl.set_lint_mode("strict")
        net.hybridize()   # drop the cached op so the build re-lints
        with pytest.raises(error.GraphLintError, match="GL-DEAD001"):
            net(x)
        gl.set_lint_mode(None)

    def test_cachedop_choke_clean_block_quiet(self, lint_off):
        from incubator_mxnet_tpu.gluon import nn
        gl.set_lint_mode("strict")
        net = nn.Dense(4)
        net.initialize()
        net.hybridize()
        out = net(mx.nd.ones((2, 8)))   # deferred-init eager pass
        out = net(mx.nd.ones((2, 8)))   # compiled + linted
        assert out.shape == (2, 4)

    def test_bulking_choke_strict_poisons_segment(self, lint_off):
        from incubator_mxnet_tpu.ops import bulking
        from incubator_mxnet_tpu.ops.registry import register, _OPS
        name = "_test_bulk_dirty"

        @register(name)
        def dirty(x):
            _dead = jnp.sin(x)
            return x * 2

        gl.set_lint_mode("strict")
        try:
            with pytest.raises(error.GraphLintError, match="GL-DEAD001"):
                with bulking.bulk_scope(True):
                    y = registry.invoke(name, mx.nd.ones((4,)))
                    y.asnumpy()
        finally:
            gl.set_lint_mode(None)
            _OPS.pop(name, None)
            bulking.clear_trace_cache()

    def test_fused_step_choke_clean(self, lint_off):
        from incubator_mxnet_tpu import fuse, gluon
        from incubator_mxnet_tpu.gluon import nn
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
        net.initialize()
        x = mx.nd.random.uniform(shape=(4, 6))
        net(x)
        gl.set_lint_mode("strict")
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        step = fuse.make_fused_train_step(net, loss, "sgd",
                                          {"learning_rate": 0.1})
        val = step(x, mx.nd.array(onp.zeros((4,), onp.float32)))
        assert float(val) > 0


class TestExportIntegration:
    def _export(self, tmp_path, fn, params, example, monkeypatch, mode):
        from incubator_mxnet_tpu import deploy
        monkeypatch.setenv("MXNET_EXPORT_GRAPHLINT", mode)
        prefix = str(tmp_path / "m")
        return deploy.export_model(fn, example, prefix, params=params), \
            prefix

    def test_clean_export_records_zero(self, tmp_path, monkeypatch):
        def fwd(params, x):
            return x @ params["w"]

        meta, _ = self._export(
            tmp_path, fwd, {"w": jnp.ones((8, 4))}, (jnp.ones((2, 8)),),
            monkeypatch, "warn")
        assert meta["graphlint"]["findings"] == 0

    def test_dirty_export_warns_and_records(self, tmp_path, monkeypatch):
        baked = onp.ones((600, 600), onp.float32)

        def fwd(params, x):
            return (x @ baked) * params["s"]

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            meta, _ = self._export(
                tmp_path, fwd, {"s": jnp.ones(())},
                (jnp.ones((2, 600)),), monkeypatch, "warn")
        assert meta["graphlint"]["findings"] >= 1
        assert meta["graphlint"]["by_rule"].get("GL-CONST001", 0) >= 1
        assert any("GL-CONST001" in str(x.message) for x in w)

    def test_raise_mode_fails_export(self, tmp_path, monkeypatch):
        baked = onp.ones((600, 600), onp.float32)

        def fwd(params, x):
            return (x @ baked) * params["s"]

        from incubator_mxnet_tpu import deploy
        monkeypatch.setenv("MXNET_EXPORT_GRAPHLINT", "raise")
        with pytest.raises(error.GraphLintError, match="GL-CONST001"):
            deploy.export_model(fwd, (jnp.ones((2, 600)),),
                                str(tmp_path / "m"),
                                params={"s": jnp.ones(())})

    def test_advisory_only_export_does_not_gate(self, tmp_path,
                                                monkeypatch):
        """Advisories never gate: an unused example input (GL-DEAD001
        advisory) must survive raise-mode and record findings=0."""
        def fwd(params, x, unused):
            return x @ params["w"]

        from incubator_mxnet_tpu import deploy
        monkeypatch.setenv("MXNET_EXPORT_GRAPHLINT", "raise")
        meta = deploy.export_model(
            fwd, (jnp.ones((2, 8)), jnp.ones((3,))),
            str(tmp_path / "m"), params={"w": jnp.ones((8, 4))})
        assert meta["graphlint"]["findings"] == 0
        assert meta["graphlint"]["advisories"] >= 1

    def test_off_mode_skips(self, tmp_path, monkeypatch):
        def fwd(params, x):
            return x @ params["w"]

        meta, _ = self._export(
            tmp_path, fwd, {"w": jnp.ones((8, 4))}, (jnp.ones((2, 8)),),
            monkeypatch, "0")
        assert "graphlint" not in meta


# ---------------------------------------------------------------------------
# recompilation sentinel
# ---------------------------------------------------------------------------

@pytest.fixture()
def clean_sentinel():
    rc.reset()
    registry.clear_caches()
    yield
    rc.reset()
    registry.clear_caches()


class TestSentinel:
    def test_off_instrument_is_identity(self, clean_sentinel):
        def f(x):
            return x

        assert rc.enabled() is None
        assert rc.instrument(f, "site") is f

    def test_varying_batch_storms_and_raises(self, clean_sentinel):
        with rc.sentinel_scope("raise", 3):
            with pytest.raises(error.RecompileStormError,
                               match="varying leading/batch"):
                for n in range(1, 10):
                    mx.nd.ones((n, 4)).sum().asscalar()
        st = rc.stats()
        assert "op:sum" in st["storming_sites"]

    def test_warn_mode_throttled(self, clean_sentinel):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with rc.sentinel_scope("warn", 2):
                for n in range(1, 7):
                    mx.nd.ones((n, 3)).max().asscalar()
        storm = [x for x in w
                 if "recompile storm" in str(x.message)]
        assert 1 <= len(storm) < 4   # crossing + power-of-two throttle
        assert "op:max" in str(storm[0].message)

    def test_bucketed_replay_stays_quiet(self, clean_sentinel):
        buckets = [1, 2, 4, 8]
        with rc.sentinel_scope("raise", len(buckets) + 1):
            for _ in range(3):
                for b in buckets:
                    mx.nd.ones((b, 8)).sum().asscalar()
        st = rc.stats()
        assert st["storming_sites"] == []
        site = st["per_site"]["op:sum"]
        assert site["compiles"] == len(buckets)
        assert site["distinct_signatures"] == len(buckets)
        assert site["retraces"] == 0

    def test_static_arg_churn_diagnosed(self, clean_sentinel):
        with rc.sentinel_scope("warn", 100):
            rc.record_compile("s", (("arr", (4,), "float32"),
                                    ("static", "1")))
            rc.record_compile("s", (("arr", (4,), "float32"),
                                    ("static", "2")))
        assert "static arg" in rc.stats()["per_site"]["s"]["last_change"]

    def test_retrace_of_same_signature_diagnosed(self, clean_sentinel):
        sig = (("arr", (4,), "float32"),)
        with rc.sentinel_scope("warn", 100):
            rc.record_compile("s", sig)
            rc.record_compile("s", sig)
        site = rc.stats()["per_site"]["s"]
        assert site["retraces"] == 1
        assert "re-traced" in site["last_change"]

    def test_varying_static_kwarg_diagnosed(self, clean_sentinel):
        """The flagship churn case: a per-call static kwarg.  The
        signature keeps the kw name AND the inner kind, so the
        diagnosis names the kwarg and the hoist-it remedy."""
        with rc.sentinel_scope("warn", 100):
            rc.record_compile("s", rc.signature_of(
                (jax.ShapeDtypeStruct((4,), jnp.float32),), {"axis": 0}))
            rc.record_compile("s", rc.signature_of(
                (jax.ShapeDtypeStruct((4,), jnp.float32),), {"axis": 1}))
        change = rc.stats()["per_site"]["s"]["last_change"]
        assert "kwarg axis" in change and "static" in change

    def test_kwarg_array_shape_churn_diagnosed(self, clean_sentinel):
        with rc.sentinel_scope("warn", 100):
            rc.record_compile("s", rc.signature_of(
                (), {"x": jax.ShapeDtypeStruct((2, 8), jnp.float32)}))
            rc.record_compile("s", rc.signature_of(
                (), {"x": jax.ShapeDtypeStruct((3, 8), jnp.float32)}))
        change = rc.stats()["per_site"]["s"]["last_change"]
        assert "kwarg x" in change and "varying leading/batch" in change

    def test_bulk_kwarg_variants_are_distinct_sites(self, clean_sentinel):
        """Same op chain + shapes, different static kwargs = genuinely
        different PROGRAMS: each segment structure gets its own site
        (its own storm budget, like op:{name}) — the sentinel must not
        call them a re-traced signature nor pool them into one budget."""
        from incubator_mxnet_tpu.ops import bulking
        with rc.sentinel_scope("warn", 100):
            for axis in (0, 1):
                with bulking.bulk_scope(True):
                    x = mx.nd.ones((4, 6))
                    (x * 2).sum(axis=axis).asnumpy()
            st = rc.stats()["per_site"]
            sites = [k for k in st if k.startswith("bulk:segment:")]
            assert len(sites) == 2
            for s in sites:
                assert st[s]["compiles"] == 1
                assert st[s]["retraces"] == 0

    def test_dtype_flip_diagnosed(self, clean_sentinel):
        with rc.sentinel_scope("warn", 100):
            rc.record_compile("s", (("arr", (4,), "float32"),))
            rc.record_compile("s", (("arr", (4,), "bfloat16"),))
        assert "dtype" in rc.stats()["per_site"]["s"]["last_change"]

    def test_profiler_provider_registered_while_on(self, clean_sentinel):
        with rc.sentinel_scope("warn", 100):
            rc.record_compile("s", (("arr", (4,), "float32"),))
            stats = profiler.provider_stats()
            assert stats["recompile"]["compiles_total"] == 1
        assert "recompile" not in profiler.provider_stats()

    def test_cachedop_site_observed(self, clean_sentinel):
        from incubator_mxnet_tpu.gluon import nn
        with rc.sentinel_scope("warn", 100):
            net = nn.Dense(4)
            net.initialize()
            net.hybridize()
            net(mx.nd.ones((2, 8)))
            net(mx.nd.ones((2, 8)))   # warm replay: no second compile
            st = rc.stats()["per_site"]
            (site,) = [k for k in st if k.startswith("cachedop:")]
            assert st[site]["compiles"] == 1

    def test_bulk_segment_site_observed(self, clean_sentinel):
        from incubator_mxnet_tpu.ops import bulking
        with rc.sentinel_scope("warn", 100):
            for _ in range(2):   # second pass replays the trace cache
                with bulking.bulk_scope(True):
                    x = mx.nd.ones((4, 4))
                    y = ((x * 2) + 1).sum()
                    y.asscalar()
            st = rc.stats()["per_site"]
            sites = [k for k in st if k.startswith("bulk:segment:")]
            assert len(sites) == 1
            assert st[sites[0]]["compiles"] == 1

    def test_scope_restores_mode_and_limit(self, clean_sentinel):
        prev_mode = rc.enabled()
        with rc.sentinel_scope("raise", 2):
            assert rc.enabled() == "raise"
            assert rc.limit() == 2
        assert rc.enabled() == prev_mode

    def test_instrument_preserves_signature(self, clean_sentinel):
        """static_argnames must keep resolving through the wrapper."""
        with rc.sentinel_scope("warn", 100):
            def f(x, k=2):
                return x * k

            traced = rc.instrument(f, "sig-site")
            assert traced is not f
            jfn = jax.jit(traced, static_argnames=("k",))
            out = jfn(jnp.ones((2,)), k=3)
            assert float(out.sum()) == 6.0
            assert rc.stats()["per_site"]["sig-site"]["compiles"] == 1


class TestFusedStepLint:
    def test_fused_step_lints_with_dead_ignored(self):
        """Gradient graphs carry AD-transposition dead primals
        (documented scope limit) — with GL-DEAD001 ignored the whole
        resnet fused train step is clean."""
        from incubator_mxnet_tpu import fuse, gluon
        from incubator_mxnet_tpu.gluon.model_zoo import vision
        net = vision.get_model("resnet18_v1", classes=10)
        net.initialize()
        x = mx.nd.random.uniform(shape=(2, 3, 32, 32))
        net(x)
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        step = fuse.make_fused_train_step(net, loss, "sgd",
                                          {"learning_rate": 0.1})
        fs = gl.lint_fn(step._step_fn, step.params, step.aux,
                        step.opt_state, x.data,
                        jnp.zeros((2,), jnp.float32),
                        jax.random.PRNGKey(0), where="fused",
                        config=gl.Config(ignore={"GL-DEAD001"}))
        assert fs == []
