"""shardlint (analysis/shardlint.py) — SPMD sharding lint, collective
cost model and per-shard HBM plans (docs/graph_analysis.md).

Five batteries:

* the analyzer itself — spec normalization/shard-factor math, the
  SL-SHARD-PEAK001/SL-RESHARD001/SL-REPL001/SL-SPEC001/SL-DONATE001
  must-flag and must-pass fixtures, check_sharding modes (warn/strict/
  crash-is-best-effort) and the profiler provider;
* the collective cost model — known formulas on hand-built shard_map
  graphs (psum = all-reduce, all_gather, all_to_all, ppermute) and the
  scan-body trip-count multiplication the ring/pipeline surfaces rely
  on;
* the parallel-stack zero-finding pins — one test per module (mesh,
  pipeline, ulysses, ring_attention, moe, gradient_compression): the
  8-device dryrun-mesh sweep stays at zero error findings, so future
  edits can't silently regress sharding discipline;
* the choke point — Executor.analyze / run_analyses carry the
  ``shardlint=`` pass, ``shardlint_active`` gates it, and strict mode
  raises the typed ``ShardLintError`` (a ``GraphLintError``);
* the serving path — export_model(sharding_rule=...) records the
  per-shard plan in meta.json ``"shardlint"`` and
  ``placement.model_footprint_bytes`` charges the PER-SHARD number,
  not the whole-graph one (fallback unchanged).
"""
import json
import warnings

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import error, profiler
from incubator_mxnet_tpu import executor_cache as xc
from incubator_mxnet_tpu.analysis import findings as fnd
from incubator_mxnet_tpu.analysis import shardlint as sl
from incubator_mxnet_tpu.parallel.mesh import make_mesh

F32 = 4


def setup_module():
    assert jax.device_count() >= 8, \
        "shardlint tests need the 8-device CPU dryrun mesh (conftest)"


@pytest.fixture
def mesh():
    return make_mesh(dp=4, tp=2)


# ---------------------------------------------------------------------------
# spec plumbing
# ---------------------------------------------------------------------------

def test_norm_spec_and_factor():
    assert sl._norm_spec(P("dp", None), 2) == (("dp",), ())
    assert sl._norm_spec(P("dp"), 3) == (("dp",), (), ())
    assert sl._norm_spec(P(("dp", "tp"), None), 2) == (("dp", "tp"), ())
    assert sl._norm_spec(None, 2) == ((), ())
    sizes = {"dp": 4, "tp": 2}
    assert sl._shard_factor((("dp",), ()), sizes) == 4
    assert sl._shard_factor((("dp", "tp"), ()), sizes) == 8
    assert sl._shard_factor(((), ()), sizes) == 1
    assert sl._shard_factor(None, sizes) == 1          # untracked = full
    assert sl._shard_factor((("zz",), ()), sizes) == 1  # unknown axis


def test_mesh_axis_sizes_from_mesh_and_dict(mesh):
    sizes = sl._mesh_axis_sizes(mesh)
    assert sizes["dp"] == 4 and sizes["tp"] == 2
    assert sl._mesh_axis_sizes({"dp": 8}) == {"dp": 8}
    assert sl._mesh_axis_sizes(None) == {}


# ---------------------------------------------------------------------------
# the per-shard HBM plan
# ---------------------------------------------------------------------------

def test_per_shard_peak_divides_by_shard_factor(mesh):
    x = jnp.zeros((64, 64), jnp.float32)
    rep = sl.analyze_fn(lambda a: a + 1.0, x, mesh=mesh,
                        in_specs=(P("dp", None),))
    # input + output, both dp-sharded 4-ways: per-shard = whole / 4
    assert rep.peak_hbm_bytes == 2 * 64 * 64 * F32
    assert rep.peak_hbm_bytes_per_shard == rep.peak_hbm_bytes // 4

    # untracked entry: charged full-size to every shard (upper bound)
    rep = sl.analyze_fn(lambda a: a + 1.0, x, mesh=mesh)
    assert rep.peak_hbm_bytes_per_shard == rep.peak_hbm_bytes


def test_replicated_buffer_charged_full_to_every_shard(mesh):
    w = jnp.zeros((64, 64), jnp.float32)   # declared replicated
    x = jnp.zeros((64, 64), jnp.float32)   # dp-sharded
    rep = sl.analyze_fn(lambda w, a: a @ w, w, x, mesh=mesh,
                        in_specs=(P(None, None), P("dp", None)))
    nb = 64 * 64 * F32
    # w full + x/4 + out/4 (out inherits x's spec by shape match)
    assert rep.peak_hbm_bytes_per_shard == nb + nb // 4 + nb // 4
    assert rep.peak_hbm_bytes == 3 * nb


# ---------------------------------------------------------------------------
# rule batteries: each must flag, and the clean twin must pass
# ---------------------------------------------------------------------------

def test_sl_spec001_missing_axis(mesh):
    x = jnp.zeros((64, 64), jnp.float32)
    rep = sl.analyze_fn(lambda a: a + 1.0, x, mesh=mesh,
                        in_specs=(P("zz", None),))
    assert [f.rule for f in rep.findings] == ["SL-SPEC001"]
    assert rep.findings[0].severity == "error"
    # size-1 axes are still IN the mesh (make_mesh always carries all 5)
    rep = sl.analyze_fn(lambda a: a + 1.0, x, mesh=mesh,
                        in_specs=(P("sp", None),))
    assert not rep.findings


def test_sl_repl001_large_replicated_weight(mesh):
    w = jnp.zeros((64, 64), jnp.float32)
    cfg = sl.Config(repl_bytes=1024)
    rep = sl.analyze_fn(lambda a: a + 1.0, w, mesh=mesh,
                        in_specs=(P(None, None),), config=cfg)
    assert [f.rule for f in rep.findings] == ["SL-REPL001"]
    # below the floor: clean
    rep = sl.analyze_fn(lambda a: a + 1.0, w, mesh=mesh,
                        in_specs=(P(None, None),),
                        config=sl.Config(repl_bytes=1 << 20))
    assert not rep.findings
    # sharded on any axis: clean
    rep = sl.analyze_fn(lambda a: a + 1.0, w, mesh=mesh,
                        in_specs=(P(None, "tp"),), config=cfg)
    assert not rep.findings
    # the declared escape hatch: clean
    rep = sl.analyze_fn(lambda a: a + 1.0, w, mesh=mesh,
                        in_specs=(P(None, None),), allow_replicated=(0,),
                        config=cfg)
    assert not rep.findings
    # untracked (no declaration) never draws the rule
    rep = sl.analyze_fn(lambda a: a + 1.0, w, mesh=mesh, config=cfg)
    assert not rep.findings


def test_sl_reshard001_constraint_mismatch(mesh):
    x = jnp.zeros((64, 64), jnp.float32)

    def f(a):
        return jax.lax.with_sharding_constraint(
            a * 2.0, NamedSharding(mesh, P(None, "tp")))

    rep = sl.analyze_fn(f, x, mesh=mesh, in_specs=(P("dp", None),))
    assert [f.rule for f in rep.findings] == ["SL-RESHARD001"]
    # the implied reshard is priced into the collective bill
    assert rep.comm_bytes_per_step == 64 * 64 * F32
    assert any(c["kind"] == "reshard" for c in rep.collectives)

    # agreeing constraint: clean, free
    def g(a):
        return jax.lax.with_sharding_constraint(
            a * 2.0, NamedSharding(mesh, P("dp", None)))

    rep = sl.analyze_fn(g, x, mesh=mesh, in_specs=(P("dp", None),))
    assert not rep.findings
    assert rep.comm_bytes_per_step == 0


def test_sl_donate001_resharded_donation(mesh):
    x = jnp.zeros((64, 64), jnp.float32)

    def f(a):
        return jax.lax.with_sharding_constraint(
            a + 1.0, NamedSharding(mesh, P(None, "tp")))

    rep = sl.analyze_fn(f, x, mesh=mesh, in_specs=(P("dp", None),),
                        donate_argnums=(0,))
    assert "SL-DONATE001" in [f.rule for f in rep.findings]

    # matching output sharding: no donation finding
    def g(a):
        return jax.lax.with_sharding_constraint(
            a + 1.0, NamedSharding(mesh, P("dp", None)))

    rep = sl.analyze_fn(g, x, mesh=mesh, in_specs=(P("dp", None),),
                        donate_argnums=(0,))
    assert "SL-DONATE001" not in [f.rule for f in rep.findings]


def test_sl_shard_peak001_budget(mesh):
    x = jnp.zeros((64, 64), jnp.float32)
    rep = sl.analyze_fn(lambda a: a @ a, x, mesh=mesh,
                        in_specs=(P("dp", None),),
                        config=sl.Config(chip_bytes=100))
    assert "SL-SHARD-PEAK001" in [f.rule for f in rep.findings]
    # a budget the per-shard plan fits (but the whole graph would not)
    budget = rep.peak_hbm_bytes_per_shard + 1
    assert budget < rep.peak_hbm_bytes
    rep = sl.analyze_fn(lambda a: a @ a, x, mesh=mesh,
                        in_specs=(P("dp", None),),
                        config=sl.Config(chip_bytes=budget))
    assert not rep.findings
    # ignore silences the rule (graphlint Config contract)
    rep = sl.analyze_fn(lambda a: a @ a, x, mesh=mesh,
                        in_specs=(P("dp", None),),
                        config=sl.Config(chip_bytes=100,
                                         ignore=("SL-SHARD-PEAK001",)))
    assert not rep.findings


# ---------------------------------------------------------------------------
# the collective cost model
# ---------------------------------------------------------------------------

def _shard_mapped(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def test_collective_costs_psum_and_gather():
    mesh = make_mesh(dp=8)
    x = jnp.zeros((64, 16), jnp.float32)

    def allreduce(a):
        return jax.lax.psum(a, "dp")

    f = _shard_mapped(allreduce, mesh, (P("dp", None),), P("dp", None))
    rep = sl.analyze_fn(f, x, mesh=mesh, in_specs=(P("dp", None),))
    per_shard = (64 // 8) * 16 * F32
    (c,) = [c for c in rep.collectives if c["kind"] == "psum"]
    assert c["axis"] == "dp" and c["axis_size"] == 8
    assert c["payload_bytes"] == per_shard
    assert c["comm_bytes"] == 2 * per_shard * 7 // 8
    assert rep.comm_bytes_per_step == c["comm_bytes"]

    def gather(a):
        return jax.lax.all_gather(a, "dp")

    f = _shard_mapped(gather, mesh, (P("dp", None),), P(None, None, None))
    rep = sl.analyze_fn(f, x, mesh=mesh, in_specs=(P("dp", None),))
    (c,) = [c for c in rep.collectives if c["kind"] == "all_gather"]
    assert c["payload_bytes"] == per_shard
    assert c["comm_bytes"] == per_shard * 7


def test_collectives_in_scan_multiply_by_trip_count():
    mesh = make_mesh(sp=8)
    x = jnp.zeros((64, 16), jnp.float32)
    steps = 5

    def body(a):
        def step(h, _):
            h = jax.lax.ppermute(h, "sp",
                                 [(i, (i + 1) % 8) for i in range(8)])
            return h, None
        h, _ = jax.lax.scan(step, a, None, length=steps)
        return h

    f = _shard_mapped(body, mesh, (P("sp", None),), P("sp", None))
    rep = sl.analyze_fn(f, x, mesh=mesh, in_specs=(P("sp", None),))
    per_shard = (64 // 8) * 16 * F32
    (c,) = [c for c in rep.collectives if c["kind"] == "ppermute"]
    assert c["count"] == steps
    assert c["comm_bytes"] == per_shard * steps
    assert "scan" in c["path"]


# ---------------------------------------------------------------------------
# parallel-stack zero-finding pins (one per module)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep():
    return dict(sl.sweep_parallel())


def _assert_clean(rep):
    errors = [f for f in rep.findings if f.severity == "error"]
    assert not errors, sl.render(errors)


def test_sweep_mesh_clean(sweep):
    _assert_clean(sweep["parallel.mesh"])
    assert sweep["parallel.mesh"].peak_hbm_bytes_per_shard \
        < sweep["parallel.mesh"].peak_hbm_bytes


def test_sweep_pipeline_clean(sweep):
    rep = sweep["parallel.pipeline"]
    _assert_clean(rep)
    # the schedule's ppermute runs n_micro + npp - 1 times
    (c,) = [c for c in rep.collectives if c["kind"] == "ppermute"]
    assert c["count"] == 4 + 8 - 1
    assert any(c["kind"] == "psum" for c in rep.collectives)


def test_sweep_ulysses_clean(sweep):
    rep = sweep["parallel.ulysses"]
    _assert_clean(rep)
    # seq->head and head->seq redistributions, q/k/v then out: 4 total
    assert sum(c["kind"] == "all_to_all" for c in rep.collectives) == 4


def test_sweep_ring_attention_clean(sweep):
    rep = sweep["parallel.ring_attention"]
    _assert_clean(rep)
    # k and v each rotate once per scan step, nsp steps
    perms = [c for c in rep.collectives if c["kind"] == "ppermute"]
    assert len(perms) == 2 and all(c["count"] == 4 for c in perms)


def test_sweep_moe_clean(sweep):
    rep = sweep["parallel.moe"]
    _assert_clean(rep)
    # the expert weights are ep/tp-sharded: per-shard < whole-graph
    assert rep.peak_hbm_bytes_per_shard < rep.peak_hbm_bytes


def test_sweep_gradient_compression_clean(sweep):
    rep = sweep["kvstore.gradient_compression"]
    _assert_clean(rep)
    # the uint8 sign-gather is the only wire traffic
    assert any(c["kind"] == "all_gather" for c in rep.collectives)


# ---------------------------------------------------------------------------
# the choke point: modes, crash contract, Executor wiring, provider
# ---------------------------------------------------------------------------

def test_check_sharding_off_is_inert(mesh):
    prev = sl.set_shard_mode(None)
    try:
        out = sl.check_sharding(lambda a: a + 1.0,
                                (jnp.ones((8, 8)),), mesh=mesh)
        assert out is None
    finally:
        sl.set_shard_mode(prev)


def test_check_sharding_warn_and_strict(mesh):
    x = jnp.zeros((64, 64), jnp.float32)

    def f(a):
        return jax.lax.with_sharding_constraint(
            a * 2.0, NamedSharding(mesh, P(None, "tp")))

    with sl.shard_scope("warn"):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            rep = sl.check_sharding(f, (x,), name="t:warn", mesh=mesh,
                                    in_specs=(P("dp", None),))
        assert rep is not None and rep.findings
        assert any("SL-RESHARD001" in str(x.message) for x in w)

    with sl.shard_scope("strict"):
        with pytest.raises(error.ShardLintError):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                sl.check_sharding(f, (x,), name="t:strict", mesh=mesh,
                                  in_specs=(P("dp", None),))


def test_shardlint_error_is_graphlint_error():
    assert issubclass(error.ShardLintError, error.GraphLintError)
    assert error.get_error_class("ShardLintError") is error.ShardLintError


def test_check_sharding_crash_never_breaks_build():
    with sl.shard_scope("strict"):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out = sl.check_sharding(lambda x: undefined_name,  # noqa: F821
                                    (jnp.ones((4,)),), name="t:crash")
        assert out is None
        assert any("could not analyze" in str(x.message) for x in w)


def test_executor_analyze_carries_shardlint(mesh):
    sl.reset_stats()
    assert not xc.shardlint_active()
    ex = xc.Executor(lambda a: a + 1.0, "t:shard_exec")
    with sl.shard_scope("warn"):
        assert xc.shardlint_active()
        ex.analyze((jnp.zeros((64, 64), jnp.float32),),
                   shardlint=dict(mesh=mesh, in_specs=(P("dp", None),)))
    st = sl.stats()
    site = st["per_site"]["t:shard_exec"]
    assert site["analyses"] == 1
    assert site["peak_hbm_bytes_per_shard"] \
        == site["peak_hbm_bytes"] // 4


def test_stats_provider_in_profiler_dumps(mesh):
    with sl.shard_scope("warn"):
        sl.check_sharding(lambda a: a * 2.0,
                          (jnp.zeros((32, 32), jnp.float32),),
                          name="t:provider", mesh=mesh,
                          in_specs=(P("dp", None),))
    assert "t:provider" in sl.stats()["per_site"]
    assert "shardlint" in profiler.dumps()


# ---------------------------------------------------------------------------
# findings flow through the shared baseline machinery
# ---------------------------------------------------------------------------

def test_findings_baseline_flow(mesh):
    x = jnp.zeros((64, 64), jnp.float32)
    rep = sl.analyze_fn(lambda a: a + 1.0, x, mesh=mesh,
                        in_specs=(P("zz", None),), where="t:baseline")
    (f,) = rep.findings
    baseline = {f.key: "known seed fixture"}
    regressions, suppressed, stale = fnd.apply_baseline([f], baseline)
    assert not regressions and suppressed == [f] and not stale
    # an unreasoned entry does not suppress
    regressions, suppressed, _ = fnd.apply_baseline(
        [f], {f.key: "TODO: justify or fix"})
    assert regressions == [f]


# ---------------------------------------------------------------------------
# export + placement: the per-shard footprint reaches the Placer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_artifact(tmp_path_factory):
    from incubator_mxnet_tpu import deploy
    from incubator_mxnet_tpu.parallel.mesh import (leading_axis_rule,
                                                   make_mesh)
    tmp = tmp_path_factory.mktemp("shardlint_export")
    mesh = make_mesh(dp=8)
    rng = onp.random.RandomState(0)
    params = {"w": rng.randn(64, 64).astype(onp.float32)}
    x = rng.randn(8, 64).astype(onp.float32)

    def fwd(p, xin):
        return jnp.tanh(xin @ p["w"])

    prefix = str(tmp / "sharded")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        meta = deploy.export_model(
            fwd, (x,), prefix, params=params,
            sharding_rule=leading_axis_rule(mesh), sharding_mesh=mesh)
    return prefix, meta


def test_export_meta_carries_per_shard_plan(sharded_artifact):
    prefix, meta = sharded_artifact
    with open(prefix + ".meta.json") as f:
        on_disk = json.load(f)
    plan = on_disk["shardlint"]
    assert plan == meta["shardlint"]
    assert plan["peak_hbm_bytes_per_shard"] > 0
    # the dp-sharded weight shrinks the per-shard plan below memlint's
    assert plan["peak_hbm_bytes_per_shard"] \
        < on_disk["memlint"]["peak_hbm_bytes"]
    assert plan["mesh_axes"]["dp"] == 8
    assert "'dp'" in plan["sharding_spec_tree"]["['w']"]
    assert plan["findings"] == []


def test_placer_charges_per_shard_footprint(sharded_artifact, tmp_path):
    from incubator_mxnet_tpu.serving.placement import (
        Placer, model_footprint_bytes)
    prefix, meta = sharded_artifact
    per_shard = meta["shardlint"]["peak_hbm_bytes_per_shard"]
    whole = meta["memlint"]["peak_hbm_bytes"]
    assert per_shard < whole
    # the ledger charge is the per-shard number, not the whole graph
    assert model_footprint_bytes(prefix) == per_shard

    placer = Placer(budget_bytes=per_shard + 1)
    placer.register_replica("r0")
    rid, evictions = placer.choose("m", model_footprint_bytes(prefix),
                                   ["r0"])
    assert rid == "r0" and evictions == []
    # the whole-graph charge would NOT have fit this budget
    rid, _ = placer.choose("m2", whole, ["r0"])
    assert rid is None

    # unsharded artifact: whole-graph memlint fallback unchanged
    (tmp_path / "plain.meta.json").write_text(
        json.dumps({"memlint": {"peak_hbm_bytes": 12345}}))
    assert model_footprint_bytes(str(tmp_path / "plain")) == 12345
    # no plan at all: documented default
    assert model_footprint_bytes(str(tmp_path / "nope"),
                                 default=777) == 777


def test_fused_step_shardlint_latch():
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8), nn.Dense(4, in_units=16))
    net.initialize()
    net(nd.ones((4, 8)))
    step = make_fused_train_step(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1})
    sl.reset_stats()
    with sl.shard_scope("warn"):
        step(nd.ones((4, 8)), nd.array([0, 1, 2, 3]))
        step(nd.ones((4, 8)), nd.array([0, 1, 2, 3]))
    site = sl.stats()["per_site"].get("fused_step:HybridSequential")
    assert site is not None and site["analyses"] == 1   # latched once
