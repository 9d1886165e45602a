"""Unified Executor / trace-cache / cold-start caches (ISSUE 10).

Four batteries:

* **TraceCache + Executor keying** — shape/dtype/static/donation
  changes miss (a fresh executable), re-entry hits (no retrace), and
  the compile_count probe tracks exactly that.
* **Persistent compilation cache** — one rule at the shared init
  point: ``JAX_COMPILATION_CACHE_DIR`` set means no directory is set
  in code, unset means ``<checkout>/.jax_cache``; compiling through
  any Executor populates the directory in effect.
* **AOT executables** — envelope round-trip is bitwise-identical to
  the traced path; a version/platform mismatch or corrupted blob is a
  typed :class:`AOTCompatError` and the Predictor falls back to
  recompilation (loudly) instead of crashing; an intact AOT artifact
  serves with ``compile_count == 0`` from process start.
* **Choke-point pinning** — a seeded graphlint finding surfaces from
  each of the four compile frontends (CachedOp, bulked segment, fused
  step, export), and the three build-time surfaces all flow through
  ``executor_cache.run_analyses`` (no per-surface wiring left to rot).
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import deploy, error, executor_cache as xc, profiler
from incubator_mxnet_tpu.analysis import graphlint as gl
from incubator_mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def lint_off():
    yield
    gl.set_lint_mode(None)


def _mlp_artifact(tmp_path, aot_buckets=None, name="m"):
    def fwd(params, x):
        return jnp.tanh(x @ params["w"]) @ params["w2"]

    rng = onp.random.RandomState(0)
    params = {"w": rng.randn(16, 16).astype(onp.float32),
              "w2": rng.randn(16, 4).astype(onp.float32)}
    x = rng.randn(1, 16).astype(onp.float32)
    prefix = str(tmp_path / name)
    meta = deploy.export_model(fwd, (x,), prefix, params=params,
                               aot_buckets=aot_buckets)
    return prefix, meta


# ---------------------------------------------------------------------------
# TraceCache + Executor keying
# ---------------------------------------------------------------------------

class TestTraceCache:
    def test_hit_miss_accounting(self):
        c = xc.TraceCache("t")
        assert c.get("k") is None
        c.put("k", 1)
        assert c.get("k") == 1
        assert c.stats() == {"entries": 1, "hits": 1, "misses": 1}
        assert c.peek("nope") is None           # no counter churn
        assert c.stats()["misses"] == 1
        assert c.clear() == 1 and len(c) == 0

    def test_executor_compile_count_tracks_signatures(self):
        ex = xc.Executor(lambda a: a * 2, "test:sig")
        ex(jnp.ones((2, 2)))
        ex(jnp.ones((2, 2)))                    # replay: no new compile
        assert ex.compile_count == 1
        ex(jnp.ones((4, 2)))                    # shape change: compiles
        assert ex.compile_count == 2
        ex(jnp.ones((2, 2), jnp.bfloat16))      # dtype change: compiles
        assert ex.compile_count == 3

    def test_cachedop_reentry_hits_and_signature_misses(self):
        net = nn.Dense(4)
        net.initialize()
        net.hybridize()
        net(mx.nd.ones((2, 8)))                 # deferred-init eager pass
        net(mx.nd.ones((2, 8)))                 # build
        op = net._cached_op
        assert len(op._cache) == 1
        net(mx.nd.ones((2, 8)))                 # re-entry: hit
        assert len(op._cache) == 1 and op._cache.hits >= 1
        net(mx.nd.ones((3, 8)))                 # batch change: miss
        assert len(op._cache) == 2
        net(mx.nd.ones((2, 8)).astype("float16"))   # dtype change: miss
        assert len(op._cache) == 3

    def test_donation_contract_lands_on_the_jit(self):
        # static_alloc -> the executor donates the input slot; without
        # it nothing is donated (the caller still owns its buffers)
        net = nn.Dense(4, in_units=8)
        net.initialize()
        net.hybridize(static_alloc=True)
        net(mx.nd.ones((2, 8)))
        entry = next(iter(net._cached_op._cache._d.values()))
        assert entry["executor"].donate_argnums == (1,)
        net.hybridize()          # plain: fresh CachedOp, no donation
        net(mx.nd.ones((2, 8)))
        entry = next(iter(net._cached_op._cache._d.values()))
        assert entry["executor"].donate_argnums == ()


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

class TestPersistentCache:
    """The one rule of ``ensure_compile_cache``: JAX's own variable
    wins and then no directory is set in code; otherwise the fixed
    ``<checkout>/.jax_cache``."""

    @pytest.fixture
    def fresh_rule(self):
        was = jax.config.jax_compilation_cache_dir
        xc._reset_compile_cache_for_tests()
        yield
        jax.config.update("jax_compilation_cache_dir", was)
        # drop the in-memory cache object too: a stale initialized
        # cache with the config off makes later identical compiles
        # return shared executables whose re-serialization is
        # incomplete (AOT blobs that fail to load)
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
        xc._reset_compile_cache_for_tests()

    def test_jax_variable_set_no_directory_set_in_code(
            self, tmp_path, monkeypatch, fresh_rule):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "j"))
        # the package's old variable must not override JAX's
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path / "m"))
        dirs_set = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (dirs_set.append(v)
                          if k == "jax_compilation_cache_dir" else None,
                          real_update(k, v))[1])
        assert xc.ensure_compile_cache() == str(tmp_path / "j")
        assert dirs_set == []
        assert xc.ensure_compile_cache() == str(tmp_path / "j")

    def test_unset_goes_to_fixed_checkout_path(self, tmp_path,
                                               monkeypatch, fresh_rule):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path / "m"))
        want = os.path.join(REPO, ".jax_cache")
        assert xc.ensure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # idempotent: second call is a cached read, same answer
        assert xc.ensure_compile_cache() == want

    def test_executor_compile_lands_in_jax_variable_dir(self, tmp_path):
        """End to end in a fresh process: with JAX's variable set, an
        Executor compile populates THAT directory."""
        d = tmp_path / "xla_cache"
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(d),
                   JAX_ENABLE_COMPILATION_CACHE="true",
                   MXNET_COMPILE_CACHE_DIR=str(tmp_path / "m"),
                   PYTHONPATH=REPO)
        code = ("import jax.numpy as jnp\n"
                "from incubator_mxnet_tpu import executor_cache as xc\n"
                "ex = xc.Executor(lambda a: jnp.tanh(a @ a) * 3, 't:p')\n"
                "ex(jnp.ones((64, 64))).block_until_ready()\n"
                "print(xc.stats()['persistent_cache_dir'])\n")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        assert proc.stdout.strip().splitlines()[-1] == str(d)
        assert len(os.listdir(d)) > 0
        assert not (tmp_path / "m").exists()

    def test_cold_start_provider_registered(self):
        ex = xc.Executor(lambda a: a + 1, "test:provider")
        ex(jnp.ones((3,))).block_until_ready()
        stats = profiler.provider_stats()["cold_start"]
        # when set-up's phases began and ended is the process trace's,
        # folded by span name under "setup": this compile is in it
        compiled = stats["setup"]["spans"]["jit.compile"]
        assert compiled["count"] >= 1 and compiled["total_s"] > 0
        assert 0 < compiled["first_start_s"] < compiled["last_end_s"]
        assert stats["setup"]["dropped"] == 0
        assert "test:provider" in stats["per_site"]
        assert stats["since_import_ms"] > 0


# ---------------------------------------------------------------------------
# AOT executables
# ---------------------------------------------------------------------------

class TestAOT:
    def test_roundtrip_bitwise_parity(self):
        def f(a, b):
            return jnp.tanh(a @ b) * 2.0

        a = onp.random.RandomState(1).randn(8, 16).astype(onp.float32)
        b = onp.random.RandomState(2).randn(16, 4).astype(onp.float32)
        jitted = jax.jit(f)  # mxlint: disable=MX-DONATE001(test fixture: parity check needs both buffers after the call)
        compiled = jitted.lower(a, b).compile()
        blob = xc.serialize_executable(compiled)
        loaded = xc.deserialize_executable(blob)
        onp.testing.assert_array_equal(onp.asarray(loaded(a, b)),
                                       onp.asarray(jitted(a, b)))

    @pytest.mark.parametrize("n_dev", [1, 4])
    def test_roundtrip_loads_onto_the_compiled_devices(self, n_dev):
        """A program compiled for 1 (or 4) of the harness's 8 devices
        loads onto exactly those: without ``execution_devices=`` jax
        loads it onto all 8 and the first call dies with "Expected args
        to execute_sharded_on_local_devices to have 8 shards"."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        assert len(jax.devices()) == 8
        mesh = Mesh(onp.array(jax.devices()[:n_dev]), ("dp",))
        w = jax.device_put(jnp.ones((16, 4)), NamedSharding(mesh, P()))
        x = jax.device_put(jnp.ones((8, 16)),
                           NamedSharding(mesh, P("dp")))
        jitted = jax.jit(lambda w, x: jnp.tanh(x @ w))  # mxlint: disable=MX-DONATE001(test fixture: both buffers are reused for the parity call)
        compiled = jitted.lower(w, x).compile()
        loaded = xc.deserialize_executable(
            xc.serialize_executable(compiled))
        assert (loaded.runtime_executable().local_devices()
                == list(jax.devices()[:n_dev]))
        out = loaded(w, x)
        assert len(out.sharding.device_set) == n_dev
        onp.testing.assert_array_equal(onp.asarray(out),
                                       onp.asarray(jitted(w, x)))

    def test_devices_absent_here_is_typed(self):
        compiled = jax.jit(lambda a: a + 1).lower(jnp.ones(3)).compile()  # mxlint: disable=MX-DONATE001(test fixture: one-shot compile for envelope surgery)
        blob = xc.serialize_executable(compiled)
        hlen = int.from_bytes(blob[8:16], "little")
        header = json.loads(blob[16:16 + hlen].decode())
        assert header["devices"] == [0]
        header["devices"] = [0, 99]
        new_header = json.dumps(header, sort_keys=True).encode()
        tampered = (blob[:8] + len(new_header).to_bytes(8, "little")
                    + new_header + blob[16 + hlen:])
        with pytest.raises(xc.AOTCompatError, match=r"devices \[0, 99\]"):
            xc.deserialize_executable(tampered)

    def test_version_mismatch_is_typed_and_named(self):
        compiled = jax.jit(lambda a: a + 1).lower(jnp.ones(3)).compile()  # mxlint: disable=MX-DONATE001(test fixture: one-shot compile for envelope surgery)
        blob = xc.serialize_executable(compiled)
        # rewrite the envelope header with a foreign jaxlib version
        hlen = int.from_bytes(blob[8:16], "little")
        header = json.loads(blob[16:16 + hlen].decode())
        header["jaxlib"] = "0.0.1-somebody-elses"
        new_header = json.dumps(header, sort_keys=True).encode()
        tampered = (blob[:8] + len(new_header).to_bytes(8, "little")
                    + new_header + blob[16 + hlen:])
        with pytest.raises(xc.AOTCompatError, match="0.0.1-somebody"):
            xc.deserialize_executable(tampered)

    def test_corrupt_blob_is_typed_not_a_crash(self):
        with pytest.raises(xc.AOTCompatError, match="corrupt|magic"):
            xc.deserialize_executable(b"not an aot blob at all")
        with pytest.raises(xc.AOTCompatError, match="truncated"):
            xc.deserialize_executable(b"MXTAOT1\n\x00\x01")

    def test_predictor_aot_parity_and_zero_compiles(self, tmp_path):
        prefix, meta = _mlp_artifact(tmp_path, aot_buckets=[1, 2, 4])
        assert meta["aot"]["buckets"] == [1, 2, 4]
        pred = deploy.load_predictor(prefix)
        assert pred.aot_buckets == [1, 2, 4]
        x = onp.random.RandomState(3).randn(4, 16).astype(onp.float32)
        out_aot = pred(x)
        assert pred.compile_count == 0      # AOT executed, nothing compiled
        saved, pred._aot = pred._aot, {}    # force the traced path
        out_jit = pred(x)
        pred._aot = saved
        onp.testing.assert_array_equal(out_aot, out_jit)
        assert pred.compile_count > 0       # the traced path DID compile

    def test_chunk_fallback_reuses_aot_executable(self, tmp_path):
        # no polymorphic twin + a non-bucket batch size: the chunk loop
        # runs at the traced size b0, and when the artifact ships an
        # AOT executable for b0 it must execute that, not compile one
        prefix, _ = _mlp_artifact(tmp_path, aot_buckets=[1, 2])
        pred = deploy.load_predictor(prefix)
        pred._batch_call = None      # simulate missing .batch.jaxport
        out = pred(onp.zeros((3, 16), onp.float32))   # 3 not a bucket
        assert out.shape == (3, 4)
        assert pred.compile_count == 0

    def test_predictor_falls_back_on_tampered_blob(self, tmp_path):
        prefix, _ = _mlp_artifact(tmp_path, aot_buckets=[1, 2])
        with open(prefix + ".aot.b2", "wb") as f:
            f.write(b"MXTAOT1\ngarbage")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            pred = deploy.load_predictor(prefix)
        assert pred.aot_buckets == [1]
        assert pred.aot_load_failures == 1
        assert any("recompiles at warmup" in str(x.message) for x in w)
        # the affected bucket still serves (recompiled)
        out = pred(onp.zeros((2, 16), onp.float32))
        assert out.shape == (2, 4)
        assert pred.compile_count > 0

    def test_repository_load_is_deserialization_not_compilation(
            self, tmp_path, monkeypatch):
        from incubator_mxnet_tpu.serving import ModelRepository
        from incubator_mxnet_tpu.serving.metrics import ServingMetrics
        monkeypatch.setenv("MXNET_SERVING_BATCH_BUCKETS", "1,2,4")
        monkeypatch.setenv("MXNET_SERVING_MAX_BATCH", "4")
        prefix, _ = _mlp_artifact(tmp_path, aot_buckets=[1, 2, 4])
        metrics = ServingMetrics()
        repo = ModelRepository(metrics=metrics)
        desc = repo.load("m", prefix)       # load + full bucket warmup
        assert desc["aot_buckets"] == [1, 2, 4]
        assert desc["compile_count"] == 0
        assert desc["cold_start_ms"] is not None
        out = repo.predict(
            "m", (onp.zeros((16,), onp.float32),))
        leaves = jax.tree_util.tree_leaves(out)
        assert onp.asarray(leaves[0]).shape[-1] == 4
        snap = metrics.snapshot()
        assert snap["compile_total"] == 0   # flat FROM PROCESS START
        assert snap["m.aot_loads"] == 3
        assert snap["m.cold_start_ms"] > 0
        assert snap["m.time_to_ready_ms"] > 0
        page = metrics.render()
        assert 'mxnet_serving_cold_start_ms{model="m"}' in page
        assert 'mxnet_serving_aot_loads_total{model="m"} 3' in page
        # rolling reload onto an AOT-less artifact: the _total counters
        # must stay monotonic (a drop reads as a Prometheus counter
        # reset), while the load-cost gauges track the live version
        plain, _ = _mlp_artifact(tmp_path, aot_buckets=None,
                                 name="plain")
        repo.reload("m", plain)
        snap2 = metrics.snapshot()
        assert snap2["m.aot_loads"] == 3        # not reset to 0
        assert snap2["compile_total"] > 0       # v2 really compiled
        repo.unload("m")


# ---------------------------------------------------------------------------
# choke-point pinning: every frontend flows through executor_cache
# ---------------------------------------------------------------------------

class TestChokePoint:
    def test_seeded_finding_surfaces_from_cachedop(self, lint_off):
        class Dirty(nn.HybridSequential):
            def forward(self, x):
                _dead = (x * 3).sum()       # seeded dead compute
                return super().forward(x)

        net = Dirty()
        net.add(nn.Dense(4))
        net.initialize()
        net.hybridize()
        x = mx.nd.ones((2, 8))
        net(x)                              # deferred-init eager pass
        gl.set_lint_mode("strict")
        net.hybridize()                     # drop the cached op
        with pytest.raises(error.GraphLintError, match="GL-DEAD001"):
            net(x)

    def test_seeded_finding_surfaces_from_bulking(self, lint_off):
        from incubator_mxnet_tpu.ops import bulking, registry
        from incubator_mxnet_tpu.ops.registry import register, _OPS
        name = "_test_xc_bulk_dirty"

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
            _OPS.pop(name, None)
            bulking.clear_trace_cache()

    def test_seeded_finding_surfaces_from_fused_step(self, lint_off):
        # GL-DEAD001 is ignored at the fused step by documented scope
        # limit (AD leaves dead primal eqns), so seed GL-CONST001: a
        # closure-captured 4 MiB constant baked into the loss
        from incubator_mxnet_tpu import fuse, gluon
        baked = jnp.asarray(
            onp.random.RandomState(0).randn(1024, 1024).astype(onp.float32))

        class BakedLoss(gluon.loss.Loss):
            def forward(self, pred, label):
                from incubator_mxnet_tpu.ndarray import NDArray
                return NDArray(jnp.square(pred.data - label.data).mean()
                               + (baked * 0).sum())

        net = nn.Dense(2, in_units=6)
        net.initialize()
        net(mx.nd.ones((4, 6)))
        gl.set_lint_mode("strict")
        step = fuse.make_fused_train_step(net, BakedLoss(), "sgd",
                                          {"learning_rate": 0.1})
        with pytest.raises(error.GraphLintError, match="GL-CONST001"):
            step(mx.nd.ones((4, 6)), mx.nd.ones((4, 2)))

    def test_seeded_finding_surfaces_from_export(self, tmp_path,
                                                 monkeypatch):
        monkeypatch.setenv("MXNET_EXPORT_GRAPHLINT", "raise")

        def dirty(params, x):
            _dead = jnp.cos(x).sum()        # seeded dead compute
            return x @ params["w"]

        with pytest.raises(error.GraphLintError, match="GL-DEAD001"):
            deploy.export_model(
                dirty, (onp.ones((2, 4), onp.float32),),
                str(tmp_path / "dirty"),
                params={"w": onp.ones((4, 2), onp.float32)})

    def test_build_surfaces_flow_through_run_analyses(self, lint_off,
                                                      monkeypatch):
        """No per-surface check_traced/check_memory wiring left: the
        three build-time frontends all call executor_cache.run_analyses
        (export's meta.json summary path is covered above)."""
        seen = []
        orig = xc.run_analyses

        def spy(fn, args, name, **kw):
            seen.append(name)
            return orig(fn, args, name, **kw)

        monkeypatch.setattr(xc, "run_analyses", spy)
        gl.set_lint_mode("warn")
        # CachedOp
        net = nn.Dense(3, in_units=5)
        net.initialize()
        net.hybridize()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            net(mx.nd.ones((2, 5)))
            # bulked segment
            from incubator_mxnet_tpu.ops import bulking
            with bulking.bulk_scope(True):
                (mx.nd.ones((4,)) * 2 + 1).asnumpy()
            # fused step
            from incubator_mxnet_tpu import fuse, gluon
            net2 = nn.Dense(2, in_units=6)
            net2.initialize()
            net2(mx.nd.ones((4, 6)))
            step = fuse.make_fused_train_step(
                net2, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1})
            step(mx.nd.ones((4, 6)), mx.nd.ones((4, 2)))
        bulking.clear_trace_cache()
        assert any(n.startswith("cachedop:") for n in seen), seen
        assert "bulk:segment" in seen, seen
        assert any(n.startswith("fused_step:") for n in seen), seen
