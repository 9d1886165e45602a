"""HA router tier tests (ISSUE 17): leased membership, consistent-hash
affinity, forward hops, crash takeover, and the restore-race fix.

The contract under test (docs/serving.md "Router high availability"):
N routers share one view of the fleet and of session ownership through
a leased membership store; a router crash mid-stream re-homes its
session affinities to the survivors, which resume the streams through
the SAME snapshot-restore path a replica death uses (re-base visible
in ``session_steps``, continuation bitwise, zero chunk resends).  A
single-router deployment is bit-for-bit unaffected: no HA thread, no
lease traffic, pinned bare shapes.  The ``routerha`` CI stage re-runs
this file under the pinned seeded chaos spec.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as onp
import pytest

from incubator_mxnet_tpu import flightrec
from incubator_mxnet_tpu.error import (RouterForwardError,
                                       SessionLostError)
from incubator_mxnet_tpu.serving import ReplicaFleet, FleetRouter
from incubator_mxnet_tpu.serving import routerha
from incubator_mxnet_tpu.serving.routerha import (FileLeaseStore,
                                                  HashRing,
                                                  MemoryLeaseStore,
                                                  RouterHA,
                                                  parse_forward_header)
from incubator_mxnet_tpu.serving.sessions import (SessionManager,
                                                  toy_decoder)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POSTMORTEM = os.path.join(REPO, "tools", "postmortem.py")

DIM = 8
SPEC = "toy_decoder:dim=8,max_len=64"


def _x(v=0.1):
    return (onp.full(DIM, v, onp.float32),)


_REF = {"mgr": None, "n": 0}


def _ref_chunks(n_steps, v=0.1):
    """Unbroken single-session reference run (same registry spec)."""
    mgr = _REF["mgr"]
    if mgr is None:
        mgr = _REF["mgr"] = SessionManager(
            "ref", toy_decoder(dim=DIM, max_len=64), buckets=[1],
            warmup=False)
    _REF["n"] += 1
    sid = f"ref{_REF['n']}"
    mgr.create(sid)
    chunks, _ = mgr.step(sid, _x(v), steps=n_steps)
    mgr.close(sid)
    return [onp.asarray(c[0]) for c in chunks]


def _assert_continuation(cont_chunks, timing, v=0.1):
    """Re-base-aware bitwise check: wherever the resumed session
    continued from (``session_steps`` makes the re-base VISIBLE), the
    continuation equals an unbroken run from that step — and never
    re-sends earlier chunks."""
    base = timing["session_steps"] - timing["steps"]
    assert base >= 0
    ref = _ref_chunks(base + timing["steps"], v=v)
    assert len(cont_chunks) == timing["steps"]
    for got, want in zip(cont_chunks, ref[base:]):
        assert (onp.asarray(got[0]) == want).all(), \
            f"continuation diverged from unbroken run (base {base})"
    return base


def _mk_router(tmp_path, rid, store, lease_ttl_s=0.5):
    fleet = ReplicaFleet({}, n=1, backend="thread", warmup=False,
                         probe_ms=60000.0, buckets=[1, 2],
                         session_models={"dec": SPEC},
                         session_dir=str(tmp_path / "snaps")).spawn()
    for r in fleet.replicas:
        r.sessions.get("dec").snapshot_steps = 2
    ha = RouterHA(rid, store, lease_ttl_s=lease_ttl_s)
    return FleetRouter(fleet, ha=ha), ha


def _await_durable_snapshot(tmp_path, sid, nudge=None, deadline_s=20):
    d = tmp_path / "snaps" / "dec" / sid
    end = time.monotonic() + deadline_s
    last_nudge = 0.0
    while time.monotonic() < end:
        if d.is_dir() and any((p / "index.json").exists()
                              for p in d.glob("step_*")):
            return
        now = time.monotonic()
        if nudge is not None and now - last_nudge > 0.5:
            last_nudge = now
            nudge()
        time.sleep(0.05)
    raise AssertionError(f"no durable snapshot for {sid!r}")


# ---------------------------------------------------------------------------
# forward-header hygiene: garbled input is ignored, never an error
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("raw", [
    None, "", "garbage", ";", "x;y", "-1;r1", "1e9;r1", "9999;r1",
    "1;" + "v" * 600, "NaN;a,b", "2",  # bare hops, no via: fine
])
def test_parse_forward_header_garbled_or_edge(raw):
    hops, via = parse_forward_header(raw)
    assert isinstance(hops, int) and hops >= 0
    assert isinstance(via, tuple)
    if raw in (None, "", "garbage", ";", "x;y", "-1;r1", "9999;r1",
               "NaN;a,b") or (raw and len(raw) > 512):
        assert (hops, via) == (0, ())


def test_forward_header_roundtrip():
    raw = routerha.forward_header_value(2, ("rA", "rB"))
    assert parse_forward_header(raw) == (2, ("rA", "rB"))


# ---------------------------------------------------------------------------
# consistent-hash ring: the ~K/N movement bound
# ---------------------------------------------------------------------------

def test_hash_ring_removal_moves_only_dead_members_keys():
    members = [f"router-{i}" for i in range(4)]
    ring = HashRing(members)
    keys = [f"sid-{i:04d}" for i in range(2000)]
    before = {k: ring.owner(k) for k in keys}
    # removal: every key NOT owned by the removed member keeps its
    # owner exactly (the defining consistent-hashing property)
    ring3 = HashRing([m for m in members if m != "router-2"])
    for k in keys:
        if before[k] != "router-2":
            assert ring3.owner(k) == before[k]
    moved = sum(1 for k in keys if before[k] == "router-2")
    # the dead member's share is ~K/N; allow 2x slack on 64 vnodes
    assert moved <= 2 * len(keys) / len(members)


def test_hash_ring_addition_moves_about_k_over_n():
    members = [f"router-{i}" for i in range(4)]
    ring = HashRing(members)
    keys = [f"sid-{i:04d}" for i in range(2000)]
    before = {k: ring.owner(k) for k in keys}
    ring5 = HashRing(members + ["router-new"])
    moved = sum(1 for k in keys if ring5.owner(k) != before[k])
    # only keys claimed by the newcomer move, ~K/(N+1); 2x slack
    assert 0 < moved <= 2 * len(keys) / (len(members) + 1)
    for k in keys:
        if ring5.owner(k) != before[k]:
            assert ring5.owner(k) == "router-new"


def test_hash_ring_stable_across_instances_and_empty():
    a = HashRing(["r1", "r2"])
    b = HashRing(["r2", "r1"])   # order-independent
    for i in range(100):
        assert a.owner(f"s{i}") == b.owner(f"s{i}")
    assert HashRing([]).owner("anything") is None


# ---------------------------------------------------------------------------
# lease stores + membership lifecycle
# ---------------------------------------------------------------------------

def test_file_lease_store_roundtrip_and_torn_reads(tmp_path):
    store = FileLeaseStore(tmp_path / "ha")
    store.publish({"router_id": "rA", "addr": "127.0.0.1:1",
                   "deadline": time.monotonic() + 5})
    store.publish({"router_id": "r/B", "deadline": 0})  # sanitized
    # a torn/garbage file is skipped, never a crash
    (tmp_path / "ha" / "torn.lease.json").write_text("{not json")
    (tmp_path / "ha" / "noise.txt").write_text("ignored")
    entries = store.read_all()
    assert set(entries) == {"rA", "r/B"}
    store.remove("rA")
    store.remove("rA")           # idempotent
    assert set(store.read_all()) == {"r/B"}


def test_lease_expire_and_rejoin_announced_once(tmp_path):
    store = MemoryLeaseStore()
    a = RouterHA("rA", store, lease_ttl_s=0.2)
    b = RouterHA("rB", store, lease_ttl_s=5.0)
    a.beat_once()
    b.beat_once()
    assert set(b.members(refresh=True)) == {"rA", "rB"}
    time.sleep(0.3)              # rA misses its beats
    assert set(b.members(refresh=True)) == {"rB"}
    b.sweep_once()
    assert "rA" in b._announced_dead
    assert b.describe()["expired"] == ["rA"]
    # rejoin with the SAME id clears the obituary: a later death is
    # announced again
    a.beat_once()
    b.sweep_once()
    assert "rA" not in b._announced_dead
    assert set(b.members(refresh=True)) == {"rA", "rB"}


def test_beat_failure_is_typed_and_counted(tmp_path):
    class BrokenStore(MemoryLeaseStore):
        def publish(self, entry):
            raise OSError("disk gone")

    ha = RouterHA("rA", BrokenStore(), lease_ttl_s=1.0)
    from incubator_mxnet_tpu.error import RouterLeaseError
    with pytest.raises(RouterLeaseError):
        ha.beat_once()
    assert isinstance(RouterLeaseError("x"), ConnectionError)
    assert ha.describe()["counters"]["beat_failures"] == 1


# ---------------------------------------------------------------------------
# in-process takeover: the tentpole invariant without subprocesses
# ---------------------------------------------------------------------------

def test_router_crash_takeover_resumes_bitwise(tmp_path):
    store = MemoryLeaseStore()
    rA, haA = _mk_router(tmp_path, "rA", store, lease_ttl_s=0.4)
    rB, haB = _mk_router(tmp_path, "rB", store, lease_ttl_s=5.0)
    try:
        haA.beat_once()
        haB.beat_once()
        sid = rA.session_create("dec", "tko1")["session_id"]
        rA.session_step("dec", sid, _x(), steps=6)
        _await_durable_snapshot(
            tmp_path, sid,
            nudge=lambda: rA.session_step("dec", sid, _x(), steps=1))
        haA.beat_once()          # registry with sid + fresh deadline
        # "crash": rA simply stops beating; its lease ages out
        time.sleep(0.6)
        adopted = haB.sweep_once()
        assert adopted == 1
        cont, t2 = rB.session_step("dec", sid, _x(), steps=3)
        base = _assert_continuation(cont, t2)
        assert base >= 2         # resumed FROM a snapshot, re-based
        assert rB.metrics.snapshot()["migrations"] >= 1
        d = haB.describe()
        assert d["counters"]["takeovers"] == 1
        assert d["counters"]["adopted_sessions"] == 1
        # close works on the adopted session too
        assert rB.session_close("dec", sid)["closed"] is True
    finally:
        rB.shutdown()
        rA.shutdown()


def test_request_path_claim_beats_the_sweep(tmp_path):
    """A step can arrive for a dead router's sid BEFORE any periodic
    sweep ran — the request path itself claims the orphan (ring-owner
    gated) instead of 404ing."""
    store = MemoryLeaseStore()
    rA, haA = _mk_router(tmp_path, "rA", store, lease_ttl_s=0.3)
    rB, haB = _mk_router(tmp_path, "rB", store, lease_ttl_s=5.0)
    try:
        haB.beat_once()
        # find a sid the SURVIVOR will ring-own once rA is dead (the
        # ring then only has rB, so any sid works — but pin the claim
        # gate too: with rA alive the ring may disagree)
        haA.beat_once()
        sid = rA.session_create("dec", "claim1")["session_id"]
        rA.session_step("dec", sid, _x(), steps=4)
        _await_durable_snapshot(
            tmp_path, sid,
            nudge=lambda: rA.session_step("dec", sid, _x(), steps=1))
        haA.beat_once()
        time.sleep(0.5)          # rA's lease expires; NO sweep on rB
        cont, t2 = rB.session_step("dec", sid, _x(), steps=2)
        _assert_continuation(cont, t2)
    finally:
        rB.shutdown()
        rA.shutdown()


def test_clean_stop_leaves_membership(tmp_path):
    store = MemoryLeaseStore()
    flightrec.configure(ring=256, proc="test")
    try:
        ha = RouterHA("rZ", store, lease_ttl_s=5.0)
        ha.beat_once()
        assert "rZ" in store.read_all()
        ha.stop(leave=True)
        assert "rZ" not in store.read_all()
        names = [e.name for e in flightrec.events()]
        assert "router.exited" in names
        assert "router.lease.acquired" in names
    finally:
        flightrec.reset()


# ---------------------------------------------------------------------------
# HTTP tier: forward hop, garbled headers, loop bound, shapes
# ---------------------------------------------------------------------------

def _post(port, path, body, headers=None, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


@pytest.fixture
def http_pair(tmp_path):
    store = MemoryLeaseStore()
    rA, haA = _mk_router(tmp_path, "rA", store, lease_ttl_s=5.0)
    rB, haB = _mk_router(tmp_path, "rB", store, lease_ttl_s=5.0)
    pa = rA.start()
    pb = rB.start()
    yield rA, haA, pa, rB, haB, pb
    rB.shutdown()
    rA.shutdown()


def test_forward_hop_routes_to_owner(http_pair):
    rA, haA, pa, rB, haB, pb = http_pair
    code, d = _post(pa, "/v1/sessions/dec:create", {"session_id": "f1"})
    assert code == 200
    # the NON-owning router serves the step by proxying to the owner
    code, d = _post(pb, "/v1/sessions/dec/f1:step",
                    {"inputs": [_x()[0].tolist()], "steps": 3})
    assert code == 200 and d["steps"] == 3
    assert d["timing"]["session_steps"] == 3
    assert haB.describe()["counters"]["forwards"] >= 1
    # streaming forwards too, chunk for chunk
    req = urllib.request.Request(
        f"http://127.0.0.1:{pb}/v1/sessions/dec/f1:step",
        data=json.dumps({"inputs": [_x()[0].tolist()], "steps": 2,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    lines = []
    with urllib.request.urlopen(req, timeout=60) as resp:
        for line in resp:
            if line.strip():
                lines.append(json.loads(line))
    assert lines[-1].get("done") is True
    assert sum(1 for ln in lines if "outputs" in ln) == 2
    # the owner served 5 steps total, all one session
    assert rA._session_homes["f1"][1] is not None


@pytest.mark.parametrize("raw", ["garbage", ";;;", "-5;rQ",
                                 "1;unknown-router", "NaN;x,y,z"])
def test_garbled_forward_headers_ignored_never_500(http_pair, raw):
    rA, haA, pa, rB, haB, pb = http_pair
    _post(pa, "/v1/sessions/dec:create", {"session_id": "g1"})
    # garbled hop headers on BOTH the owner and the forwarder parse as
    # hop 0 and the request just works — never a 500
    for port in (pa, pb):
        code, d = _post(port, "/v1/sessions/dec/g1:step",
                        {"inputs": [_x()[0].tolist()], "steps": 1},
                        headers={routerha.HEADER: raw})
        assert code == 200


def test_forward_loop_bounded_typed_508(http_pair):
    rA, haA, pa, rB, haB, pb = http_pair
    _post(pa, "/v1/sessions/dec:create", {"session_id": "loop1"})
    # a request arriving at the non-owner with the hop budget already
    # spent must die typed (508), not hop forever
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(pb, "/v1/sessions/dec/loop1:step",
              {"inputs": [_x()[0].tolist()], "steps": 1},
              headers={routerha.HEADER:
                       routerha.forward_header_value(
                           haB.forward_hops, ("rX", "rY"))})
    assert ei.value.code == 508
    payload = json.loads(ei.value.read())
    assert payload["error"] == "RouterForwardError"
    # the self-in-via loop check trips even below the hop budget
    with pytest.raises(urllib.error.HTTPError) as ei2:
        _post(pb, "/v1/sessions/dec/loop1:step",
              {"inputs": [_x()[0].tolist()], "steps": 1},
              headers={routerha.HEADER: "1;rB"})
    assert ei2.value.code == 508


def test_router_ha_block_shape_and_healthz(http_pair):
    rA, haA, pa, rB, haB, pb = http_pair
    with urllib.request.urlopen(
            f"http://127.0.0.1:{pa}/healthz", timeout=30) as resp:
        health = json.loads(resp.read())
    blk = health["router_ha"]
    assert set(blk) == {"router_id", "addr", "lease_ttl_s",
                        "forward_hops", "leased", "lease_remaining_s",
                        "peers", "expired", "counters"}
    assert blk["router_id"] == "rA" and blk["leased"] is True
    assert set(blk["peers"]) == {"rB"}
    assert blk["peers"]["rB"]["fleet"]["replicas"] == 1
    assert set(blk["counters"]) == {"beats", "beat_failures",
                                    "takeovers", "adopted_sessions",
                                    "forwards"}
    assert rA.describe()["router_ha"]["router_id"] == "rA"


def test_bare_router_is_bitwise_unaffected(tmp_path, monkeypatch):
    """No HA configured ⇒ no HA object, no HA thread, no lease
    traffic, and the PINNED bare shapes (the PR 12/14/15 additive
    discipline)."""
    monkeypatch.delenv("MXNET_SERVING_ROUTER_HA_DIR", raising=False)
    fleet = ReplicaFleet({}, n=1, backend="thread", warmup=False,
                         probe_ms=60000.0,
                         session_models={"dec": SPEC}).spawn()
    router = FleetRouter(fleet)
    try:
        assert router.ha is None
        assert fleet.membership is None
        router.start()
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("router-ha-")]
        _, health = router.health()
        assert "router_ha" not in health
        assert "router_ha" not in router.describe()
    finally:
        router.shutdown()


def test_from_env_wiring(tmp_path, monkeypatch):
    assert routerha.from_env() is None
    monkeypatch.setenv("MXNET_SERVING_ROUTER_HA_DIR",
                       str(tmp_path / "ha"))
    monkeypatch.setenv("MXNET_SERVING_ROUTER_ID", "env-r1")
    monkeypatch.setenv("MXNET_SERVING_ROUTER_LEASE_TTL_S", "1.5")
    monkeypatch.setenv("MXNET_SERVING_ROUTER_FORWARD_HOPS", "5")
    ha = routerha.from_env(host="127.0.0.1", port=80)
    assert ha.router_id == "env-r1"
    assert ha.lease_ttl_s == 1.5
    assert ha.forward_hops == 5
    assert ha.addr == "127.0.0.1:80"
    assert isinstance(ha.store, FileLeaseStore)


# ---------------------------------------------------------------------------
# the known flake, dead: restore vs the async snapshotter
# ---------------------------------------------------------------------------

def test_restore_race_with_async_snapshotter_20_of_20(tmp_path):
    """ISSUE 17 satellite: a restore that looks at the snapshot dir
    while the source's async snapshotter is mid-publish (staging dir
    present, committed rename an instant away) must WAIT for the
    commit, not fail the adopt.  The interleaving is forced 20/20
    times: the committed step dir is renamed to its ``.tmp`` staging
    name, the restore starts, and the rename is undone mid-restore."""
    snap = tmp_path / "snaps"
    # snapshot_steps is large on purpose: the ONLY snapshot is the
    # explicit synchronous one below, so the forced rename owns the
    # staging-dir name outright (no background writer racing the race)
    src = SessionManager("dec", toy_decoder(dim=DIM, max_len=64),
                         buckets=[1], warmup=False,
                         snapshot_dir=str(snap), snapshot_steps=100)
    dst = SessionManager("dec", toy_decoder(dim=DIM, max_len=64),
                         buckets=[1], warmup=False,
                         snapshot_dir=str(snap), snapshot_steps=100)
    # the test's own hook, on this instance only: the restore says when
    # it has looked, found the publish in flight and is about to retry —
    # the window the rename below has to land in.  The wait budget is
    # not what is under test, so a loaded host may not run it out.
    in_window = threading.Event()
    in_flight = dst._snapshot_in_flight

    def in_flight_seen(*a):
        racing = in_flight(*a)
        if racing:
            in_window.set()
        return racing

    dst._snapshot_in_flight = in_flight_seen
    dst.RESTORE_RACE_WAIT_S = 60.0
    for i in range(20):
        sid = f"race{i}"
        src.create(sid)
        src.step(sid, _x(), steps=4)
        src.snapshot_all(sync=True)
        d = snap / "dec" / sid
        steps_dirs = sorted(p for p in d.glob("step_*")
                            if not p.name.endswith(".tmp"))
        assert steps_dirs, f"trial {i}: no committed snapshot"
        committed = steps_dirs[-1]
        staged = committed.with_name(committed.name + ".tmp")
        committed.rename(staged)          # snapshotter "mid-publish"

        result = {}

        def adopt():
            try:
                result["info"] = dst.restore(sid)
            except Exception as e:  # noqa: BLE001 - recorded for the assert
                result["err"] = e

        in_window.clear()
        t = threading.Thread(target=adopt)
        t.start()
        assert in_window.wait(30), f"trial {i}: restore never raced"
        staged.rename(committed)          # the "atomic publish" lands
        t.join(timeout=30)
        assert not t.is_alive(), f"trial {i}: restore hung"
        assert "err" not in result, \
            f"trial {i}: restore failed under the race: " \
            f"{result.get('err')!r}"
        assert result["info"]["steps"] >= 2
        dst.close(sid)
        src.close(sid)
    # the race actually happened every trial (first look always saw
    # only the staging dir) — retries prove the fix engaged, the flake
    # did not just get lucky
    assert dst._counters["restore_retries"] >= 20


def test_restore_without_race_evidence_fails_fast(tmp_path):
    """No staging dir, no snapshot ⇒ the typed failure stays IMMEDIATE
    (the retry budget must not add latency to hopeless restores)."""
    snap = tmp_path / "snaps"
    mgr = SessionManager("dec", toy_decoder(dim=DIM, max_len=64),
                         buckets=[1], warmup=False,
                         snapshot_dir=str(snap))
    (snap / "dec" / "ghost").mkdir(parents=True)
    t0 = time.monotonic()
    with pytest.raises(SessionLostError):
        mgr.restore("ghost")
    assert time.monotonic() - t0 < SessionManager.RESTORE_RACE_WAIT_S


# ---------------------------------------------------------------------------
# THE acceptance chaos proof: SIGKILL one of 2 subprocess routers
# mid-stream (slow; the `routerha` CI stage and the `slow` stage run
# it, tier-1 skips it — same split as the replica-kill e2e)
# ---------------------------------------------------------------------------

def _spawn_router(tmp_path, rid, env):
    proc = subprocess.Popen(
        [sys.executable, "-m", "incubator_mxnet_tpu.serving.router",
         "--session-model", f"dec={SPEC}",
         "--session-dir", str(tmp_path / "snaps"),
         "--backend", "thread", "--replicas", "1",
         "--host", "127.0.0.1", "--port", "0", "--no-warmup",
         "--ha-dir", str(tmp_path / "ha"), "--router-id", rid,
         "--lease-ttl", "1.0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, start_new_session=True, cwd=REPO)
    port = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise AssertionError(f"router {rid} died at startup")
        if "routing on" in line:
            port = int(line.rsplit(":", 1)[1].split()[0])
            break
    assert port, f"router {rid} never reported its port"
    return proc, port


def _post_retry(port, path, body, deadline_s=30, headers=None):
    """POST with bounded retry over the takeover window: 503s and
    refused sockets are the EXPECTED transient while the dead
    router's lease ages out — a lost stream is anything that still
    fails past the deadline."""
    end = time.monotonic() + deadline_s
    last = None
    while time.monotonic() < end:
        try:
            return _post(port, path, body, headers=headers,
                         timeout=60)
        except urllib.error.HTTPError as e:
            last = e
            if e.code not in (503,):
                raise
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            last = e
        time.sleep(0.25)
    raise AssertionError(f"request did not land within {deadline_s}s: "
                         f"{last!r}")


@pytest.mark.slow
def test_sigkill_router_midstream_takeover_postmortem(tmp_path):
    """ISSUE 17 acceptance: SIGKILL one of 2 subprocess routers with
    an active mid-stream session.  The survivor must adopt the dead
    router's session (lease expiry → takeover), resume it bitwise
    from its snapshot (re-base visible, zero resends), keep serving
    fresh requests, and `postmortem --gate` must reconstruct
    ``lease.expired → takeover.started → session.restored`` from the
    survivor's flight dump."""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "MXNET_SERVING_SESSION_SNAPSHOT_STEPS": "2",
                "MXNET_FLIGHT_RING": "2048"})
    # the CI stage's spec targets the in-process battery; the
    # subprocess routers get exactly the faults this test stages
    env.pop("MXNET_FAULT_SPEC", None)
    # rA's chunk writes are slowed so the 64-step stream is genuinely
    # in flight when the SIGKILL lands — without the delay the toy
    # decode drains into the socket buffer before the signal arrives
    env_a = dict(env)
    env_a["MXNET_FAULT_SPEC"] = "serving.stream_write:delay:ms=100"
    pa = pb = None
    try:
        pa, port_a = _spawn_router(tmp_path, "rA", env_a)
        pb, port_b = _spawn_router(tmp_path, "rB", env)

        code, d = _post_retry(port_a, "/v1/sessions/dec:create",
                              {"session_id": "kill1"}, deadline_s=60)
        assert code == 200
        code, d = _post(port_a, "/v1/sessions/dec/kill1:step",
                        {"inputs": [_x()[0].tolist()], "steps": 6},
                        timeout=120)
        assert code == 200 and d["timing"]["session_steps"] == 6
        _await_durable_snapshot(
            tmp_path, "kill1",
            nudge=lambda: _post(port_a, "/v1/sessions/dec/kill1:step",
                                {"inputs": [_x()[0].tolist()],
                                 "steps": 1}, timeout=60))

        # mid-stream: a long streaming step is in flight on rA when it
        # dies — the client sees the break VISIBLY, never a hang and
        # never a stream that pretends to complete (the ``done``
        # terminator line is the completeness signal; a SIGKILLed
        # router can only truncate before it)
        stream = {"lines": []}

        def stream_and_die():
            req = urllib.request.Request(
                f"http://127.0.0.1:{port_a}/v1/sessions/dec/"
                f"kill1:step",
                data=json.dumps({"inputs": [_x()[0].tolist()],
                                 "steps": 40,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    for n, line in enumerate(resp):
                        if line.strip():
                            stream["lines"].append(json.loads(line))
                        if n == 1:
                            os.killpg(pa.pid, signal.SIGKILL)
            except Exception as e:  # noqa: BLE001 - a reset IS a visible break
                stream["err"] = e

        t = threading.Thread(target=stream_and_die)
        t.start()
        t.join(timeout=90)
        assert not t.is_alive(), "stream client hung through the kill"
        assert "err" in stream or (
            len(stream["lines"]) < 40
            and not any(ln.get("done") for ln in stream["lines"])), \
            "killed router's stream must break visibly (truncated " \
            "before its done line), not complete"
        pa.wait(timeout=30)

        # ... and the SURVIVOR resumes the session bitwise from its
        # last durable snapshot once rA's lease ages out (zero lost
        # streams: the retry window IS the takeover window)
        code, d = _post_retry(port_b, "/v1/sessions/dec/kill1:step",
                              {"inputs": [_x()[0].tolist()],
                               "steps": 3}, deadline_s=45)
        assert code == 200
        timing = d["timing"]
        base = timing["session_steps"] - d["steps"]
        assert base >= 2, "resume must re-base from a snapshot"
        ref = _ref_chunks(base + d["steps"])
        for got, want in zip(d["outputs"], ref[base:]):
            assert (onp.asarray(got[0]) == want).all(), \
                "takeover continuation diverged from unbroken run"

        # fresh requests keep landing on the survivor
        code, d2 = _post_retry(port_b, "/v1/sessions/dec:create",
                               {"session_id": "fresh1"},
                               deadline_s=30)
        assert code == 200
        code, _ = _post(port_b, "/v1/sessions/dec/fresh1:step",
                        {"inputs": [_x()[0].tolist()], "steps": 2},
                        timeout=60)
        assert code == 200

        # the survivor's healthz names the dead peer + the takeover
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port_b}/healthz",
                timeout=30) as resp:
            blk = json.loads(resp.read())["router_ha"]
        assert blk["counters"]["takeovers"] >= 1
        assert "rA" in blk["expired"] or not blk["peers"]

        # postmortem: the causal chain from the survivor's black box
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port_b}/v1/flight",
                timeout=30) as resp:
            dump = tmp_path / "rB.flight.json"
            dump.write_bytes(resp.read())
        gate = subprocess.run(
            [sys.executable, POSTMORTEM, str(dump), "--gate",
             "router.lease.expired,router.takeover.started,"
             "session.restored"],
            capture_output=True, text=True)
        assert gate.returncode == 0, \
            f"postmortem gate failed:\n{gate.stdout}\n{gate.stderr}"
        assert "gate ok" in gate.stdout
    finally:
        for proc in (pa, pb):
            if proc is not None and proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except OSError:
                    pass
        for proc in (pa, pb):
            if proc is not None:
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    pass


def test_routerforwarderror_is_typed_not_connectionerror():
    # 508 must NOT be retried as transient by generic failover layers
    assert not isinstance(RouterForwardError("x"), ConnectionError)
