"""Serving-fleet tests (ISSUE 8 tentpole).

The contract under test: N replicas behind the router keep serving —
bitwise-correct — through a replica kill, through probe-driven
quarantine, and through a zero-downtime rolling reload; a fully
draining fleet answers a typed 503, never a hang.  The `fleet` CI
stage re-runs this file under a pinned seeded ``MXNET_FAULT_SPEC``
(lost routing hops, failed probes, replica-side faults), so every
assertion here must hold with chaos injected as well as without.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as onp
import pytest

import jax.numpy as jnp

from incubator_mxnet_tpu import deploy, profiler
from incubator_mxnet_tpu.error import (FleetDrainingError,
                                       ReplicaUnavailableError)
from incubator_mxnet_tpu.serving import (DeadlineExceeded, FleetRouter,
                                         QueueFullError, ReplicaFleet)
from incubator_mxnet_tpu.serving.fleet import DEAD, READY


def _mlp_fwd(params, x):
    y = x
    for w in params["layers"]:
        y = jnp.tanh(y @ w)
    return y


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    rng = onp.random.RandomState(7)
    params = {"layers": [rng.randn(24, 24).astype(onp.float32) * 0.3
                         for _ in range(3)]}
    x = rng.randn(2, 24).astype(onp.float32)
    prefix = str(tmp_path_factory.mktemp("fleet") / "mlp")
    deploy.export_model(_mlp_fwd, (x,), prefix, params=params)
    return prefix


@pytest.fixture
def predictor(artifact):
    return deploy.load_predictor(artifact)


def _instances(n, seed=0):
    rng = onp.random.RandomState(seed)
    return [rng.randn(24).astype(onp.float32) for _ in range(n)]


def _refs(predictor, instances):
    return [predictor(x[None])[0] for x in instances]


def _fleet(artifact, n=3, **kw):
    """Thread-backend fleet with a small bucket set (fast warmup) and
    a parked prober (tests drive probe_once() deterministically)."""
    kw.setdefault("backend", "thread")
    kw.setdefault("buckets", [1, 2, 4])
    kw.setdefault("probe_ms", 60000.0)
    return ReplicaFleet({"m": artifact}, n=n, **kw).spawn()


def _volley(router, instances, refs, start_hook=None):
    """Concurrent single-instance volley through the router; returns
    the error list (must usually be empty) and verifies bitwise."""
    results = [None] * len(instances)
    errors = []

    def call(i):
        try:
            out, _timing = router.route("m", (instances[i],))
            results[i] = out[0]
        except Exception as e:  # noqa: BLE001 — recorded for assert
            errors.append((i, e))

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(instances))]
    for t in threads[:len(threads) // 2]:
        t.start()
    if start_hook is not None:
        start_hook()
    for t in threads[len(threads) // 2:]:
        t.start()
    for t in threads:
        t.join()
    if not errors:
        for i, (got, ref) in enumerate(zip(results, refs)):
            assert got is not None, f"request {i} lost"
            assert (got == ref).all(), f"request {i} diverged"
    return errors


# ---------------------------------------------------------------------------
# lifecycle + routing
# ---------------------------------------------------------------------------

def test_spawn_states_and_gauges(artifact):
    fleet = _fleet(artifact, n=3)
    try:
        states = fleet.states()
        assert sorted(states) == ["r0", "r1", "r2"]
        for st in states.values():
            assert set(st) == {"state", "healthy", "inflight",
                               "backend", "models"}
            assert st["models"] == ["m"]
            assert st["state"] == READY and st["healthy"]
            assert st["inflight"] == 0 and st["backend"] == "thread"
        assert fleet.ready_count() == 3
    finally:
        fleet.shutdown()


def test_routed_volley_bitwise_equal_unbatched(artifact, predictor):
    fleet = _fleet(artifact, n=3)
    router = FleetRouter(fleet)
    try:
        instances = _instances(24, seed=1)
        refs = _refs(predictor, instances)
        errors = _volley(router, instances, refs)
        assert not errors, errors
        snap = router.metrics.snapshot()
        assert snap["requests"].get(200) == 24
        assert not any(c >= 500 for c in snap["requests"])
    finally:
        router.shutdown()


def test_pick_prefers_least_loaded(artifact):
    fleet = _fleet(artifact, n=3)
    try:
        with fleet.get("r0").track(), fleet.get("r1").track():
            assert fleet.pick().rid == "r2"
        # all idle again: deterministic tiebreak, but excluded rids
        # must be skipped while an alternative exists
        assert fleet.pick(exclude={"r0"}).rid != "r0"
        # every routable excluded -> falls back rather than stranding
        assert fleet.pick(exclude={"r0", "r1", "r2"}) is not None
    finally:
        fleet.shutdown()


# ---------------------------------------------------------------------------
# chaos: kill + failover (the acceptance-criteria volley)
# ---------------------------------------------------------------------------

def test_kill_replica_mid_volley_zero_failed_requests(artifact,
                                                      predictor):
    """The chaos proof: one replica hard-killed mid-volley, every
    client request still completes correctly (failovers absorbed
    within the per-hop budgets) and no 5xx burst shows in the fleet
    counters."""
    fleet = _fleet(artifact, n=3)
    router = FleetRouter(fleet)
    try:
        instances = _instances(30, seed=2)
        refs = _refs(predictor, instances)
        errors = _volley(router, instances, refs,
                         start_hook=lambda: fleet.kill("r1"))
        assert not errors, errors
        snap = router.metrics.snapshot()
        assert snap["requests"].get(200) == 30
        assert not any(c >= 500 for c in snap["requests"]), snap
        assert snap["replicas"]["r1"]["state"] == DEAD
        assert fleet.ready_count() == 2
    finally:
        router.shutdown()


def test_failover_on_connection_error_then_quarantine(artifact,
                                                      predictor):
    fleet = _fleet(artifact, n=2, probe_fails=2)
    router = FleetRouter(fleet)
    try:
        bad = fleet.get("r0")

        def broken(name, inputs, deadline_ms=None, inputs_json=None):
            raise ConnectionResetError("injected: replica wedged")

        bad.predict = broken
        x = _instances(1, seed=3)[0]
        ref = predictor(x[None])[0]
        # every route that lands on r0 fails over to r1 and succeeds
        for _ in range(4):
            out, _ = router.route("m", (x,))
            assert (out[0] == ref).all()
        assert router.metrics.snapshot()["failovers"] >= 1
        # passive health: consecutive failures quarantine r0
        assert not bad.healthy
        assert [r.rid for r in fleet.routable()] == ["r1"]
    finally:
        router.shutdown()


def test_queue_full_sheds_to_other_replica(artifact, predictor):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet)
    try:
        full = fleet.get("r0")

        def overloaded(name, inputs, deadline_ms=None,
                       inputs_json=None):
            raise QueueFullError("queue full (0/0)")

        full.predict = overloaded
        x = _instances(1, seed=4)[0]
        ref = predictor(x[None])[0]
        out, _ = router.route("m", (x,))
        assert (out[0] == ref).all()
        # overload is load, not ill health: r0 stays in rotation
        assert full.healthy
    finally:
        router.shutdown()


def test_fleet_deadline_exhausted_is_typed(artifact):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet, hop_min_ms=5.0)
    try:
        for r in fleet.replicas:
            def parked(name, inputs, deadline_ms=None,
                       inputs_json=None, _r=r):
                time.sleep((deadline_ms or 50.0) / 1000.0 + 0.05)
                raise DeadlineExceeded("hop budget spent",
                                       queue_ms=deadline_ms)
            r.predict = parked
        with pytest.raises(DeadlineExceeded):
            router.route("m", (_instances(1)[0],), deadline_ms=60.0)
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# fleet-aware admission
# ---------------------------------------------------------------------------

def test_fully_draining_fleet_503_typed_never_hangs(artifact):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet)
    try:
        for r in fleet.replicas:
            r.begin_drain()
        t0 = time.monotonic()
        with pytest.raises(FleetDrainingError):
            router.route("m", (_instances(1)[0],))
        assert time.monotonic() - t0 < 5.0   # typed, not a hang
        snap = router.metrics.snapshot()
        assert snap["requests"].get(503, 0) >= 1
    finally:
        router.shutdown()


def test_all_dead_replicas_unavailable_typed(artifact):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet)
    try:
        fleet.kill("r0")
        fleet.kill("r1")
        with pytest.raises(ReplicaUnavailableError):
            router.route("m", (_instances(1)[0],))
        # also catchable as the builtin retry layers use
        with pytest.raises(ConnectionError):
            router.route("m", (_instances(1)[0],))
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# hedged requests
# ---------------------------------------------------------------------------

def test_hedged_request_beats_slow_replica(artifact, predictor):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet, hedge=25.0, hop_min_ms=10.0)
    try:
        slow = fleet.get("r0")
        orig = slow.predict

        def sleepy(name, inputs, deadline_ms=None, inputs_json=None):
            time.sleep(0.3)
            return orig(name, inputs, deadline_ms=deadline_ms,
                        inputs_json=inputs_json)

        slow.predict = sleepy
        x = _instances(1, seed=5)[0]
        ref = predictor(x[None])[0]
        # route until the slow replica is picked as primary at least
        # once (tiebreak may start on either)
        won_race = False
        for _ in range(4):
            t0 = time.monotonic()
            out, _ = router.route("m", (x,))
            assert (out[0] == ref).all()
            won_race |= (time.monotonic() - t0) < 0.25
        snap = router.metrics.snapshot()
        assert snap["hedges_launched"] >= 1
        assert snap["hedges_won"] >= 1
        assert won_race, "hedge never beat the 300ms replica"
    finally:
        router.shutdown()


def test_hedge_win_does_not_reset_stalled_primary_health(artifact,
                                                         predictor):
    """Passive health must be attributed to the replica that actually
    served: a stalled primary whose hedges keep winning must still
    burn ITS failure budget (its hop deadline resolves each stalled
    call), not have it reset by the winner's success."""
    fleet = _fleet(artifact, n=2, probe_fails=3)
    router = FleetRouter(fleet, hedge=20.0, hop_min_ms=10.0,
                         deadline_ms=500.0)
    try:
        stalled = fleet.get("r0")

        def parked(name, inputs, deadline_ms=None, inputs_json=None):
            time.sleep((deadline_ms or 100.0) / 1000.0 + 0.1)
            raise DeadlineExceeded("hop budget spent",
                                   queue_ms=deadline_ms)

        stalled.predict = parked
        x = _instances(1, seed=10)[0]
        ref = predictor(x[None])[0]
        for _ in range(4):
            out, _ = router.route("m", (x,))
            assert (out[0] == ref).all()   # hedge on r1 serves
        time.sleep(1.2)   # let the parked hops resolve their 504s
        assert not stalled.healthy, \
            "hedge wins must not launder the primary's failures"
        assert fleet.get("r1").healthy
    finally:
        router.shutdown()


def test_hedge_p95_mode_needs_samples(artifact):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet, hedge="p95")
    try:
        assert router._hedge_delay_ms() is None   # no distribution yet
        x = _instances(1, seed=6)[0]
        for _ in range(25):
            router.route("m", (x,))
        delay = router._hedge_delay_ms()
        assert delay is not None and delay >= 1.0
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# active probing
# ---------------------------------------------------------------------------

def test_probe_quarantines_and_readmits(artifact):
    fleet = _fleet(artifact, n=2, probe_fails=2)
    try:
        r0 = fleet.get("r0")
        orig = r0.healthz
        r0.healthz = lambda: (_ for _ in ()).throw(
            ConnectionResetError("probe: wedged"))
        for _ in range(10):
            fleet.probe_once()
            if not r0.healthy:
                break
        assert not r0.healthy
        assert [r.rid for r in fleet.routable()] == ["r1"]
        r0.healthz = orig
        for _ in range(10):
            fleet.probe_once()
            if r0.healthy:
                break
        assert r0.healthy and fleet.ready_count() == 2
    finally:
        fleet.shutdown()


def test_probe_counts_into_metrics(artifact):
    from incubator_mxnet_tpu.serving import FleetMetrics
    fleet = _fleet(artifact, n=2, probe_fails=3)
    fleet.metrics = FleetMetrics()
    try:
        r0 = fleet.get("r0")
        r0.healthz = lambda: (_ for _ in ()).throw(
            ConnectionResetError("probe: wedged"))
        fleet.probe_once()
        assert fleet.metrics.snapshot()["probe_failures"].get(
            "r0", 0) >= 1
    finally:
        fleet.shutdown()


# ---------------------------------------------------------------------------
# zero-downtime rolling reload
# ---------------------------------------------------------------------------

def test_rolling_reload_under_load_capacity_never_below_n_minus_1(
        artifact, predictor):
    """The rolling-reload proof: 3 replicas, sustained traffic, a full
    roll — ready capacity never observed (or reported) below 2, every
    replica lands on version 2, zero request errors, responses
    bitwise-stable across the version swap (same artifact)."""
    fleet = _fleet(artifact, n=3)
    router = FleetRouter(fleet)
    try:
        instances = _instances(8, seed=7)
        refs = _refs(predictor, instances)
        stop = threading.Event()
        errors = []
        served = []
        min_sampled = [3]

        def hammer(idx):
            k = 0
            while not stop.is_set():
                i = (idx + k) % len(instances)
                try:
                    out, _ = router.route("m", (instances[i],))
                    assert (out[0] == refs[i]).all()
                    served.append(1)
                except Exception as e:  # noqa: BLE001 — for assert
                    errors.append(e)
                    return
                k += 1

        def sample():
            while not stop.is_set():
                min_sampled[0] = min(min_sampled[0],
                                     fleet.ready_count())
                time.sleep(0.002)

        threads = ([threading.Thread(target=hammer, args=(i,))
                    for i in range(4)]
                   + [threading.Thread(target=sample)])
        for t in threads:
            t.start()
        time.sleep(0.05)           # traffic flowing before the roll
        report = fleet.rolling_reload("m")
        time.sleep(0.05)           # and after it
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors
        assert len(served) > 0
        assert report["min_ready"] >= 2, report
        assert min_sampled[0] >= 2, min_sampled
        assert [e["version"] for e in report["replicas"]] == [2, 2, 2]
        assert all(r.repository.get("m").version == 2
                   for r in fleet.replicas)
    finally:
        router.shutdown()


def test_rolling_reload_includes_quarantined_replica(artifact):
    """A probe-quarantined (READY-but-unhealthy) replica is still in
    rotation lifecycle-wise: the roll must reload it too, or it would
    re-admit itself later serving the OLD version with nothing
    reporting the mixed-version fleet."""
    fleet = _fleet(artifact, n=2, probe_fails=1)
    try:
        r0 = fleet.get("r0")
        orig = r0.healthz
        r0.healthz = lambda: (_ for _ in ()).throw(
            ConnectionResetError("probe: wedged"))
        for _ in range(5):
            fleet.probe_once()
            if not r0.healthy:
                break
        assert not r0.healthy
        r0.healthz = orig
        report = fleet.rolling_reload("m")
        assert {e["replica"] for e in report["replicas"]} == \
            {"r0", "r1"}
        assert all(r.repository.get("m").version == 2
                   for r in fleet.replicas)
    finally:
        fleet.shutdown()


def test_rolling_reload_failure_readmits_old_version(artifact):
    fleet = _fleet(artifact, n=2)
    try:
        with pytest.raises(Exception, match="nosuch"):
            fleet.rolling_reload("m", path="/nosuch/prefix")
        # the failed step's replica is back in rotation on v1
        assert fleet.ready_count() == 2
        assert all(r.repository.get("m").version == 1
                   for r in fleet.replicas)
    finally:
        fleet.shutdown()


# ---------------------------------------------------------------------------
# router HTTP front end
# ---------------------------------------------------------------------------

def _post(port, path, body, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(port, path, timeout=30):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as resp:
        return resp.status, resp.read()


def test_router_http_end_to_end(artifact, predictor):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet)
    port = router.start()
    try:
        instances = _instances(6, seed=8)
        refs = _refs(predictor, instances)
        for i, x in enumerate(instances):
            status, body = _post(port, "/v1/models/m:predict",
                                 {"inputs": [x.tolist()]})
            assert status == 200
            got = onp.asarray(body["outputs"][0], onp.float32)
            assert (got == refs[i]).all()

        status, raw = _get(port, "/healthz")
        health = json.loads(raw)
        assert status == 200 and health["status"] == "ok"
        assert health["ready"] == 2 and health["models"] == ["m"]
        assert set(health["replicas"]["r0"]) == {"state", "healthy",
                                                 "inflight", "backend",
                                                 "models"}
        # additive autoscale contract: no control plane attached, no
        # "autoscale" key (the PR 8 shape is preserved)
        assert "autoscale" not in health
        # same discipline for router HA: no peers configured, no
        # "router_ha" key — the bare single-router shape stays pinned
        assert "router_ha" not in health

        status, raw = _get(port, "/metrics")
        text = raw.decode()
        assert 'mxnet_serving_fleet_replica_state{replica="r0",' \
            'state="ready"} 1' in text
        assert "mxnet_serving_fleet_failovers_total" in text
        assert "mxnet_serving_fleet_ready_replicas 2" in text

        status, report = _post(port, "/v1/models/m:reload", {})
        assert status == 200 and report["min_ready"] >= 1
        assert [e["version"] for e in report["replicas"]] == [2, 2]

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/models/nosuch:predict",
                  {"inputs": [[0.0]]})
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/models/m:predict", {"bad": 1})
        assert ei.value.code == 400
    finally:
        router.shutdown()


def test_router_http_draining_503_with_retry_after(artifact):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet)
    port = router.start()
    try:
        for r in fleet.replicas:
            r.begin_drain()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, "/v1/models/m:predict",
                  {"inputs": [_instances(1)[0].tolist()]})
        assert ei.value.code == 503
        # derived from live state (ISSUE 11 satellite: no longer the
        # hardcoded "1") — but ALWAYS present on a 503, and a sane
        # whole number of seconds
        retry_after = ei.value.headers.get("Retry-After")
        assert retry_after is not None
        assert 1 <= int(retry_after) <= 30
        assert json.loads(ei.value.read())["error"] == \
            "FleetDrainingError"
        status, raw = None, None
        try:
            _get(port, "/healthz")
        except urllib.error.HTTPError as e:
            status, raw = e.code, e.read()
        assert status == 503
        assert json.loads(raw)["status"] == "draining"
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def test_fleet_stats_in_profiler_dumps(artifact):
    fleet = _fleet(artifact, n=2)
    router = FleetRouter(fleet)
    try:
        router.route("m", (_instances(1)[0],))
        stats = profiler.provider_stats()["serving_fleet"]
        assert stats["ready"] == 2
        assert stats["requests"].get(200, 0) >= 1
        assert {"failovers", "hedges_launched", "hedges_won",
                "probe_failures", "route_ms"} <= set(stats)
        assert "[serving_fleet]" in profiler.dumps()
    finally:
        router.shutdown()
    # unregistered at shutdown: a dead fleet must not linger in dumps
    assert "serving_fleet" not in profiler.provider_stats()


# ---------------------------------------------------------------------------
# process backend (real subprocesses; slow — the `fleet` CI stage and
# the `slow` stage run it, tier-1 skips it)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_process_fleet_kill_and_roll_end_to_end(artifact, predictor):
    fleet = ReplicaFleet({"m": artifact}, n=2, backend="process",
                         probe_ms=250.0).spawn()
    router = FleetRouter(fleet)
    port = router.start()
    try:
        instances = _instances(12, seed=9)
        refs = _refs(predictor, instances)
        errors = []
        results = [None] * len(instances)

        def call(i):
            try:
                status, body = _post(port, "/v1/models/m:predict",
                                     {"inputs": [instances[i].tolist()]})
                assert status == 200
                results[i] = onp.asarray(body["outputs"][0],
                                         onp.float32)
            except Exception as e:  # noqa: BLE001 — for assert
                errors.append((i, e))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(instances))]
        for t in threads[:6]:
            t.start()
        fleet.kill("r0")           # SIGKILL a real process mid-volley
        for t in threads[6:]:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        for got, ref in zip(results, refs):
            assert (got == ref).all()
        snap = router.metrics.snapshot()
        assert not any(c >= 500 for c in snap["requests"]), snap
        # rolling reload on the survivor still works over the wire
        status, report = _post(port, "/v1/models/m:reload", {},
                               timeout=300)
        assert status == 200
        assert [e["version"] for e in report["replicas"]] == [2]
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# process replicas run where the operator pointed the fleet (ISSUE 23)
# ---------------------------------------------------------------------------

class _NeverStarts:
    """subprocess.Popen stand-in: records the launch, exits at once."""

    launches = []

    def __init__(self, cmd, env=None, **kw):
        type(self).launches.append(dict(env))
        self.stdout = iter(())
        self.returncode = 1

    def poll(self):
        return self.returncode

    def kill(self):
        pass

    wait = poll


@pytest.fixture
def launches(monkeypatch):
    from incubator_mxnet_tpu.serving import fleet as fleet_mod
    monkeypatch.setattr(fleet_mod.subprocess, "Popen", _NeverStarts)
    _NeverStarts.launches = []
    return _NeverStarts.launches


@pytest.mark.parametrize("platforms", ["cpu", "tpu", None])
def test_process_replica_inherits_jax_platforms(monkeypatch, launches,
                                                platforms):
    """A process replica gets this process's JAX_PLATFORMS — set or
    unset — and never a default of its own ("cpu" used to be one: every
    replica of the router CLI ran on the host unless the variable was
    exported)."""
    from incubator_mxnet_tpu.error import ReplicaUnavailableError
    from incubator_mxnet_tpu.serving.fleet import ProcessReplica
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(ReplicaUnavailableError):
        ProcessReplica("r0", {"m": "/nowhere"}, startup_timeout_s=5).start()
    (env,) = launches
    assert env.get("JAX_PLATFORMS") == platforms
    assert "TPU_VISIBLE_CHIPS" not in env


def test_process_fleet_one_replica_per_tpu_chip(monkeypatch, launches):
    """On a TPU host every process replica owns one chip: each is pinned
    to its own, and one more than the host has is refused at spawn with
    a sentence (several replicas share a chip through the thread
    backend)."""
    from incubator_mxnet_tpu.error import ReplicaUnavailableError
    from incubator_mxnet_tpu.serving import fleet as fleet_mod
    probes = []
    monkeypatch.setattr(fleet_mod, "child_tpu_chips",
                        lambda: probes.append(1) or 2)
    fleet = ReplicaFleet({"m": "/nowhere"}, n=3, backend="process")
    with pytest.raises(ValueError, match="one TPU chip per replica"):
        fleet.spawn()
    assert launches == []               # refused before anything started
    fleet = ReplicaFleet({"m": "/nowhere"}, n=2, backend="process")
    with pytest.raises(ReplicaUnavailableError):
        fleet.spawn()                   # _NeverStarts: no replica comes up
    assert sorted(e["TPU_VISIBLE_CHIPS"] for e in launches) == ["0", "1"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in launches)
    assert len(probes) == 2             # asked once per fleet, not per replica


def test_child_tpu_chips_answers_cpu_without_a_child(monkeypatch):
    from incubator_mxnet_tpu import context
    monkeypatch.setattr(context.subprocess, "run", lambda *a, **k: pytest.fail(
        "JAX_PLATFORMS=cpu must not cost a probe subprocess"))
    assert context.child_tpu_chips({"JAX_PLATFORMS": "cpu"}) is None
    assert context.child_tpu_chips({"JAX_PLATFORMS": "CPU,tpu"}) is None
