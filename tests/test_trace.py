"""Request-scoped distributed tracing (ISSUE 14 tentpole).

The contract under test (docs/observability.md): a sampled request's
spans cover its whole path — router pick/hop/hedge/failover, admission
queue wait vs compute, batcher coalesce/pad/flush with the chosen
bucket, session decode steps — with typed outcomes on every failed
hop and injected faults visible as span events; the header
(``X-MXNET-TRACE``) propagates across process-replica hops with
garbled headers ignored and header-less replicas degrading to a
single-process trace; the bounded ring never splices two traces; and
tracing OFF costs one measured branch.  The ``trace`` CI stage re-runs
this file under a pinned seeded ``MXNET_FAULT_SPEC``, so every
assertion must hold with chaos injected as well as without.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as onp
import pytest

import jax.numpy as jnp

from incubator_mxnet_tpu import deploy, fault, profiler, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _trace_isolation():
    """Every test leaves tracing exactly as it found it: a leaked
    sample rate or a nonempty ring would flip the additive "trace"
    healthz block on for unrelated shape-pinning tests."""
    yield
    trace.reset()
    fault.reset()


def _mlp_fwd(params, x):
    y = x
    for w in params["layers"]:
        y = jnp.tanh(y @ w)
    return y


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    rng = onp.random.RandomState(7)
    params = {"layers": [rng.randn(16, 16).astype(onp.float32) * 0.3
                         for _ in range(2)]}
    x = rng.randn(2, 16).astype(onp.float32)
    prefix = str(tmp_path_factory.mktemp("trace") / "mlp")
    deploy.export_model(_mlp_fwd, (x,), prefix, params=params)
    return prefix


def _x(seed=0):
    return onp.random.RandomState(seed).randn(16).astype(onp.float32)


# ---------------------------------------------------------------------------
# span recorder core
# ---------------------------------------------------------------------------

def test_sampling_off_is_noop():
    trace.reset()
    assert trace.sample_rate() == 0.0
    assert trace.start_trace("x") is None
    assert trace.current_span() is None
    trace.add_event("nothing")            # no active span: no-op
    with trace.span("y") as s:
        assert s is None                  # no parent: no-op
    with trace.activate(None):
        assert trace.current_span() is None
    assert trace.from_header(None, "x") is None
    assert not trace.active()
    assert trace.stats()["spans_recorded"] == 0


def test_sampling_fraction_samples_some_not_all():
    trace.configure(sample=0.5, ring=4096)
    got = sum(trace.start_trace("x") is not None for _ in range(400))
    assert 0 < got < 400


def test_span_tree_context_and_export_shape():
    trace.configure(sample=1.0, ring=64)
    root = trace.start_trace("root", model="m")
    with trace.activate(root):
        assert trace.current_trace_id() == root.trace_id
        with trace.span("child", k=1) as c:
            assert c.parent_id == root.span_id
            assert trace.current_span() is c
            c.event("tick", n=2)
        assert trace.current_span() is root
    root.finish()
    root.finish(outcome="twice")          # idempotent: recorded once
    spans = trace.spans(root.trace_id)
    assert [s.name for s in spans] == ["child", "root"]
    assert spans[1].args["outcome"] == "ok"
    exp = trace.export(root.trace_id, service="me")
    kinds = {(e["ph"], e["name"]) for e in exp["traceEvents"]}
    assert kinds == {("X", "child"), ("X", "root"), ("i", "tick")}
    for e in exp["traceEvents"]:
        assert e["args"]["trace_id"] == root.trace_id
        assert e["args"]["service"] == "me"
    assert exp["displayTimeUnit"] == "ms"


def test_span_ctx_records_typed_outcome_on_error():
    trace.configure(sample=1.0, ring=64)
    root = trace.start_trace("root")
    with trace.activate(root):
        with pytest.raises(ConnectionResetError):
            with trace.span("hop"):
                raise ConnectionResetError("replica died")
    root.finish()
    hop = trace.spans(root.trace_id)[0]
    assert hop.name == "hop"
    assert hop.args["outcome"] == "ConnectionResetError"


def test_header_roundtrip_and_garbled_variants():
    trace.configure(sample=1.0)
    root = trace.start_trace("root")
    hv = trace.header_value(root)
    tid, sid, sampled = trace.parse_header(hv)
    assert (tid, sid, sampled) == (root.trace_id, root.span_id, True)
    adopted = trace.from_header(hv, "server.request")
    assert adopted.trace_id == root.trace_id
    assert adopted.parent_id == root.span_id
    assert adopted.args["adopted"] is True
    # sampled=0 is an upstream "do not record": honored
    assert trace.from_header(f"{tid}-{sid}-0", "x") is None
    # garbled headers are ignored (never a 500), falling back to the
    # local sampling decision
    for bad in ("", "zz", "a-b", "a-b-c-d", f"{tid}-{sid}-7",
                f"{tid[:-1]}-{sid}-1", f"{tid}-{sid}x-1",
                "GG" * 8 + f"-{sid}-1", None, "  "):
        assert trace.parse_header(bad) is None, bad
    fresh = trace.from_header("garbled!!", "x")
    assert fresh is not None                 # local sampling kicked in
    assert fresh.trace_id != root.trace_id
    assert "adopted" not in fresh.args
    assert trace.header_value(None) is None


def test_adopted_header_records_even_when_sampling_off():
    """A replica that never set MXNET_TRACE_SAMPLE still honors an
    upstream sampled=1 header — that is what makes the router's knob
    cover the whole fleet."""
    trace.reset()
    assert not trace.enabled()
    s = trace.from_header("ab" * 8 + "-" + "cd" * 4 + "-1", "adoptee")
    assert s is not None and s.trace_id == "ab" * 8
    s.finish()
    assert trace.active()                # spans recorded ⇒ observable
    assert trace.stats()["spans_recorded"] == 1


def test_ring_wraparound_never_splices_traces():
    """Eviction is whole-span: after heavy wraparound with two traces
    interleaved, every export is still partitioned cleanly by trace
    id and the drop count explains the loss exactly."""
    trace.configure(sample=1.0, ring=6)
    t_a = trace.start_trace("a")
    t_b = trace.start_trace("b")
    for i in range(20):
        parent = t_a if i % 2 == 0 else t_b
        parent.child(f"s{i}", i=i).finish()
    st = trace.stats()
    assert st["spans_in_ring"] == 6
    assert st["spans_dropped"] == 20 - 6
    for tid, other in ((t_a.trace_id, t_b.trace_id),
                       (t_b.trace_id, t_a.trace_id)):
        evs = trace.export(tid)["traceEvents"]
        assert evs, "wrapped ring lost a whole trace's tail"
        assert all(e["args"]["trace_id"] == tid for e in evs)
        assert all(e["args"]["trace_id"] != other for e in evs)
    # survivor set is the newest 6 spans, in order
    names = [s.name for s in trace.spans()]
    assert names == [f"s{i}" for i in range(14, 20)]


def test_trace_stats_provider_in_profiler_dumps_json():
    trace.configure(sample=1.0, ring=32)
    trace.start_trace("t").finish()
    payload = json.loads(profiler.dumps(format="json"))
    assert "aggregate" in payload and "providers" in payload
    tstats = payload["providers"]["trace"]
    assert tstats["spans_recorded"] >= 1
    assert tstats["enabled"] is True
    # the table format still renders, and bad formats are typed
    assert "[trace]" in profiler.dumps()
    with pytest.raises(ValueError):
        profiler.dumps(format="xml")


# ---------------------------------------------------------------------------
# exemplars (metrics ↔ trace ids)
# ---------------------------------------------------------------------------

def test_slow_exemplars_keep_k_slowest_per_window():
    from incubator_mxnet_tpu.serving.metrics import SlowExemplars
    ex = SlowExemplars(k=2, window=8)
    for i in range(8):
        ex.note(float(i), f"t{i}")
    got = ex.exemplars()
    assert [e["trace_id"] for e in got] == ["t7", "t6"]
    # next window: previous exemplars still visible until it fills
    ex.note(100.0, "big")
    got = ex.exemplars()
    assert got[0]["trace_id"] == "big" and len(got) == 2
    ex.note(1.0, None)                    # untraced: ignored
    assert len(ex.exemplars()) == 2


def test_serving_metrics_exemplars_render_and_snapshot():
    from incubator_mxnet_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    m.record_request("m", 200, e2e_ms=5.0, trace_id="aa" * 8)
    m.record_request("m", 200, e2e_ms=50.0, trace_id="bb" * 8)
    m.record_request("m", 200, e2e_ms=1.0)      # untraced
    page = m.render()
    ex_lines = [ln for ln in page.splitlines()
                if ln.startswith("# exemplar")]
    assert any("bb" * 8 in ln for ln in ex_lines)
    slow = m.snapshot()["m.slow_traces"]
    assert slow[0]["trace_id"] == "bb" * 8 and slow[0]["ms"] == 50.0


def test_fleet_metrics_route_exemplars():
    from incubator_mxnet_tpu.serving.metrics import FleetMetrics
    fm = FleetMetrics()
    fm.record_route(200, ms=3.0, model=None, trace_id="cc" * 8)
    fm.record_route(200, ms=30.0, model=None, trace_id="dd" * 8)
    assert "# exemplar mxnet_serving_fleet_route_ms" in fm.render()
    assert fm.snapshot()["slow_traces"][0]["trace_id"] == "dd" * 8


# ---------------------------------------------------------------------------
# healthz / describe: the additive "trace" block
# ---------------------------------------------------------------------------

def test_healthz_trace_block_additive():
    from incubator_mxnet_tpu import flightrec
    from incubator_mxnet_tpu.serving.model_repository import \
        ModelRepository
    from incubator_mxnet_tpu.serving.server import health_body
    repo = ModelRepository()
    flightrec.configure(ring=0)    # flight off: the PR 3 bare shape
    try:
        # bare server: pinned PR 3 shape, no "trace" key
        _, body = health_body(repo, time.monotonic())
        assert set(body) == {"status", "uptime_s", "queue_depth",
                             "models"}
        trace.configure(sample=1.0)
        _, body2 = health_body(repo, time.monotonic())
        assert set(body2) == {"status", "uptime_s", "queue_depth",
                              "models", "trace"}
        assert set(body2["trace"]) == {"sample", "ring", "spans",
                                       "dropped", "slow_k"}
    finally:
        flightrec.reset()
        repo.drain_all()


# ---------------------------------------------------------------------------
# the batcher: queue-wait vs compute split
# ---------------------------------------------------------------------------

def test_dynamic_batcher_spans_split_queue_and_compute(artifact):
    from incubator_mxnet_tpu.serving.model_repository import \
        ModelRepository
    trace.configure(sample=1.0, ring=256)
    repo = ModelRepository(buckets=[1, 2])
    try:
        repo.load("m", artifact, warmup=True)
        root = trace.start_trace("root")
        with trace.activate(root):
            out, timing = repo.predict("m", (_x(),))
        root.finish()
        spans = {s.name: s for s in trace.spans(root.trace_id)}
        assert {"batch.queue", "batch.execute", "root"} <= set(spans)
        q, e = spans["batch.queue"], spans["batch.execute"]
        assert q.parent_id == root.span_id
        assert e.parent_id == root.span_id
        assert e.args["padded_to"] in (1, 2) and e.args["rows"] >= 1
        # the split brackets the timing the response reports
        assert q.t1 <= e.t1
        # an unsampled request records nothing new
        before = trace.stats()["spans_recorded"]
        repo.predict("m", (_x(1),))
        assert trace.stats()["spans_recorded"] == before
    finally:
        repo.drain_all()


def test_continuous_batcher_decode_step_spans():
    """Decode-step boundaries land as one span per step per sampled
    stream (fake step/owner: no jax in the loop, pure span logic)."""
    from incubator_mxnet_tpu.serving.batcher import ContinuousBatcher

    class Owner:
        def checkout(self, sid):
            return 0.0

        def writeback(self, sid, carry, step_ms):
            return 1

        def release(self, sid):
            pass

    def step_batch(carries, inputs, padded_to):
        return [c for c in carries], [("y",) for _ in carries]

    trace.configure(sample=1.0, ring=256)
    cb = ContinuousBatcher("toy", step_batch, Owner(), buckets=[1, 2],
                           max_batch=2)
    try:
        root = trace.start_trace("root")
        with trace.activate(root):
            handle = cb.submit("sid-1", ("x",), n_steps=3)
        chunks, timing = handle.result()
        assert len(chunks) == 3
        spans = trace.spans(root.trace_id)
        steps = [s for s in spans if s.name == "session.decode_step"]
        assert [s.args["step"] for s in steps] == [0, 1, 2]
        assert all(s.parent_id == root.span_id for s in steps)
        assert all(s.args["outcome"] == "ok" for s in steps)
        queues = [s for s in spans if s.name == "session.queue"]
        assert len(queues) == 1 and queues[0].args["sid"] == "sid-1"
    finally:
        cb.drain()
        root.finish()


# ---------------------------------------------------------------------------
# the router: hops, failover, hedging — typed outcomes + fault events
# ---------------------------------------------------------------------------

def _fleet_router(artifact, n=2, **kw):
    from incubator_mxnet_tpu.serving import FleetRouter, ReplicaFleet
    fleet = ReplicaFleet({"m": artifact}, n=n, backend="thread",
                         buckets=[1, 2], probe_ms=60000.0).spawn()
    return FleetRouter(fleet, **kw)


def test_router_failover_hop_spans_typed(artifact):
    """The injected fault fires exactly once: the first hop span must
    finish with the typed outcome AND carry the fault event; the
    failover event and the winning second hop follow."""
    trace.configure(sample=1.0, ring=256)
    router = _fleet_router(artifact)
    try:
        fault.configure("serving.replica_exec:error:n=1")
        root = trace.start_trace("router.request", model="m")
        with trace.activate(root):
            out, _ = router.route("m", (_x(),))
        root.set(code=200)
        root.finish()
        spans = trace.spans(root.trace_id)
        hops = [s for s in spans if s.name == "router.hop"]
        assert len(hops) == 2
        assert hops[0].args["outcome"] == "TransientFault"
        fault_evs = [n for (_, n, _a) in hops[0].events]
        assert "fault.serving.replica_exec" in fault_evs
        assert hops[1].args["outcome"] == "ok"
        assert hops[0].args["replica"] != hops[1].args["replica"]
        failovers = [n for (_, n, _a) in root.events
                     if n == "router.failover"]
        assert failovers == ["router.failover"]
        # the winning hop's replica-side work parents under it
        exec_spans = [s for s in spans if s.name == "batch.execute"]
        assert exec_spans and all(
            s.parent_id == hops[1].span_id for s in exec_spans)
    finally:
        router.shutdown()


def test_router_hedge_span_and_events(artifact):
    """A one-shot delay stalls the primary past the hedge budget: the
    hedge launches (event on the request span), runs as its own
    ``router.hedge`` span, and wins."""
    trace.configure(sample=1.0, ring=256)
    router = _fleet_router(artifact, hedge=20.0)
    try:
        fault.configure("serving.replica_exec:delay:ms=300:n=1")
        root = trace.start_trace("router.request", model="m")
        with trace.activate(root):
            out, _ = router.route("m", (_x(),), deadline_ms=10000.0)
        root.finish()
        # the stalled primary's hop span may still be open; the hedge
        # decided the request
        ev_names = [n for (_, n, _a) in root.events]
        assert "router.hedge_launched" in ev_names
        assert "router.hedge_won" in ev_names
        hedges = [s for s in trace.spans(root.trace_id)
                  if s.name == "router.hedge"]
        assert hedges and hedges[0].args["outcome"] == "ok"
    finally:
        router.shutdown()


def test_router_http_trace_header_echo_and_dump(artifact):
    """Wire-level: a client-supplied header forces the trace, the
    response echoes the id, and GET /v1/trace?trace_id= returns only
    that trace's spans."""
    trace.reset()                          # sampling OFF: adoption only
    router = _fleet_router(artifact)
    port = router.start()
    try:
        tid = "5a" * 8
        body = json.dumps({"inputs": [_x().tolist()]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/m:predict", data=body,
            headers={"Content-Type": "application/json",
                     trace.HEADER: f"{tid}-{'1f' * 4}-1"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            echo = resp.headers.get(trace.HEADER)
            assert resp.status == 200
        assert echo is not None and echo.split("-")[0] == tid
        dump = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/trace?trace_id={tid}",
            timeout=30).read())
        names = {e["name"] for e in dump["traceEvents"]}
        assert "router.request" in names and "router.hop" in names
        assert all(e["args"]["trace_id"] == tid
                   for e in dump["traceEvents"])
        # a garbled client header is ignored, never a 500
        req2 = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/m:predict", data=body,
            headers={"Content-Type": "application/json",
                     trace.HEADER: "not-a-trace-header!!"})
        with urllib.request.urlopen(req2, timeout=60) as resp2:
            assert resp2.status == 200
        # router healthz/describe grew the additive block (spans were
        # recorded), and the exemplar names the forced trace
        code, health = router.health()
        assert "trace" in health
        assert "trace" in router.describe()
        page = router.metrics.render()
        assert f"trace_id={tid}" in page
    finally:
        router.shutdown()


def test_replica_without_header_degrades_to_router_only_trace(
        artifact):
    """A replica that predates the header (simulated by a backend
    whose predict ignores trace context entirely) still serves; the
    trace simply contains only router-side spans."""
    from incubator_mxnet_tpu.serving import FleetRouter, ReplicaFleet
    from incubator_mxnet_tpu.serving.fleet import ThreadReplica

    class LegacyReplica(ThreadReplica):
        def predict(self, name, inputs, deadline_ms=None,
                    inputs_json=None):
            # swallow the ambient context like a pre-header binary
            # would: no spans, no adoption
            import contextvars
            ctx = contextvars.Context()   # empty: no active span
            return ctx.run(ThreadReplica.predict, self, name, inputs,
                           deadline_ms, inputs_json)

    trace.configure(sample=1.0, ring=256)
    fleet = ReplicaFleet({"m": artifact}, n=1, backend="thread",
                         buckets=[1, 2], probe_ms=60000.0)
    r = LegacyReplica("r0", {"m": artifact}, buckets=[1, 2])
    r.start()
    fleet.adopt(r)
    router = FleetRouter(fleet)
    try:
        root = trace.start_trace("router.request", model="m")
        with trace.activate(root):
            out, _ = router.route("m", (_x(),))
        root.finish()
        names = {s.name for s in trace.spans(root.trace_id)}
        assert names == {"router.request", "router.hop"}
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# traceview CLI
# ---------------------------------------------------------------------------

def _span_event(tid, sid, parent, name, ts, dur, svc, **args):
    a = dict(trace_id=tid, span_id=sid, parent_id=parent, service=svc,
             outcome=args.pop("outcome", "ok"), **args)
    return {"name": name, "cat": "trace", "ph": "X", "ts": ts,
            "dur": dur, "pid": 1, "tid": 1, "args": a}


def test_traceview_merges_processes_and_computes_coverage(tmp_path):
    tid = "ee" * 8
    router_dump = {"traceEvents": [
        _span_event(tid, "r" * 8, None, "router.request", 1000, 1000,
                    "router"),
        _span_event(tid, "h" * 8, "r" * 8, "router.hop", 1050, 900,
                    "router"),
    ], "displayTimeUnit": "ms"}
    replica_dump = {"traceEvents": [
        _span_event(tid, "s" * 8, "h" * 8, "server.request", 1100,
                    800, "replica"),
    ], "displayTimeUnit": "ms"}
    f1, f2 = tmp_path / "router.json", tmp_path / "replica.json"
    f1.write_text(json.dumps(router_dump))
    f2.write_text(json.dumps(replica_dump))
    merged = tmp_path / "merged.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "traceview.py"),
         str(f1), str(f2), "--coverage", "--json", str(merged)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert "router.request" in proc.stdout
    assert "server.request" in proc.stdout
    assert "2 process(es)" in proc.stdout
    assert "coverage: 90.0%" in proc.stdout   # hop covers 900/1000
    assert len(json.loads(merged.read_text())["traceEvents"]) == 3
    # the gate arm: 95% floor must fail this 90% trace
    proc2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "traceview.py"),
         str(f1), str(f2), "--min-coverage", "0.95"],
        capture_output=True, text=True)
    assert proc2.returncode == 1


def test_traceview_stats_mode(tmp_path):
    trace.configure(sample=1.0)
    trace.start_trace("t").finish()
    dump = tmp_path / "profile.json"
    dump.write_text(profiler.dumps(format="json"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "traceview.py"),
         "--stats", str(dump)], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.splitlines()[0] == "[trace]"
    assert "spans_recorded" in proc.stdout


# ---------------------------------------------------------------------------
# training side: chunk dispatch + prefetch ring
# ---------------------------------------------------------------------------

def test_prefetch_ring_fill_and_drain_spans():
    from incubator_mxnet_tpu.gluon.data.dataloader import \
        DevicePrefetchRing
    trace.configure(sample=1.0, ring=256)
    rng = onp.random.RandomState(0)
    batches = [(rng.rand(2, 4).astype("f"), rng.rand(2).astype("f"))
               for _ in range(5)]
    root = trace.start_trace("train.epoch")
    with trace.activate(root):
        ring = DevicePrefetchRing(batches, chunk_steps=2)
        blocks = list(ring)
    root.finish()
    assert [b[0] for b in blocks] == ["chunk", "chunk", "tail"]
    spans = trace.spans(root.trace_id)
    fills = [s for s in spans if s.name == "prefetch.fill"]
    assert len(fills) == 3                 # 2 chunks + the tail draw
    drains = [s for s in spans if s.name == "prefetch.drain"]
    assert drains, "first next() waits on a fill: drain span expected"
    assert all(s.parent_id == root.span_id for s in drains)


def test_chunked_loop_epoch_trace_and_chunk_spans():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.fuse_loop import ChunkedTrainLoop
    from incubator_mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=4))
    net.initialize()
    net(nd.random.uniform(shape=(1, 4)))
    step = make_fused_train_step(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1},
        chunk_steps=2)
    loop = ChunkedTrainLoop(step)
    rng = onp.random.RandomState(1)
    batches = [(nd.array(rng.rand(2, 4).astype("f")),
                nd.array(rng.rand(2, 4).astype("f")))
               for _ in range(4)]
    trace.configure(sample=1.0, ring=256)
    loop.run_epoch(batches)
    roots = [s for s in trace.spans() if s.name == "train.epoch"]
    assert len(roots) == 1
    spans = trace.spans(roots[0].trace_id)
    chunks = [s for s in spans if s.name == "train.chunk"]
    assert [s.args["chunk"] for s in chunks] == [0, 1]
    assert all(s.args["steps"] == 2 for s in chunks)
    assert {s.name for s in spans} >= {"train.epoch", "train.chunk",
                                       "prefetch.fill"}
    # executor build-vs-cache events ride the same timeline when the
    # compile choke point fires inside a traced region — here the
    # loop executable was built before tracing was on, so just pin
    # that a traced rebuild records the event
    with trace.activate(roots[0]):
        trace.add_event("executor.created", site="fused_loop:test")
    assert any(n == "executor.created"
               for (_, n, _a) in roots[0].events)


# ---------------------------------------------------------------------------
# the fused step: spans on the profiler's clock, counters at the choke point
# ---------------------------------------------------------------------------

def _dense_step():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(4))
    net.initialize()
    net(nd.random.uniform(shape=(1, 4)))
    step = make_fused_train_step(net, gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1})
    rng = onp.random.RandomState(1)
    return step, jnp.asarray(rng.rand(2, 4).astype("f")), \
        jnp.asarray(rng.rand(2, 4).astype("f"))


def _host_spans(trace_dir):
    """``(line, name, start, end)`` of the host plane's events in the one
    ``.xplane.pb`` under ``trace_dir``."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_step_spans_reach_the_profilers_trace_with_sampling_off(tmp_path):
    import jax
    step, x, y = _dense_step()
    step(x, y).block_until_ready()          # compiled before the session
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            step(x, y).block_until_ready()
        with trace.span("user.region", note="any span, not only ours"):
            pass
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by_name = {}
    for line, name, start, end in spans:
        by_name.setdefault(name, []).append((line, start, end))
    for name in ("fused_step.call", "fused_step.key_split",
                 "fused_step.analyses", "executor.call"):
        assert len(by_name[name]) == 3, (name, sorted(by_name))
    assert len(by_name["user.region"]) == 1
    # on one thread's line, each call encloses its key split, its latches'
    # check and the jitted call, in that order
    for call, split, latch, jitted in zip(
            *(sorted(by_name[n]) for n in (
                "fused_step.call", "fused_step.key_split",
                "fused_step.analyses", "executor.call"))):
        assert call[0] == split[0] == latch[0] == jitted[0]
        assert call[1] <= split[1] <= split[2] <= latch[1] <= latch[2] \
            <= jitted[1] <= jitted[2] <= call[2]
    # head sampling stayed off: nothing reached the ring
    assert not trace.enabled() and trace.spans() == []
    assert trace.stats()["spans_recorded"] == 0


def test_step_spans_reach_the_ring_under_a_sampled_parent():
    step, x, y = _dense_step()
    trace.configure(sample=1.0, ring=64)
    root = trace.start_trace("train.epoch")
    with trace.activate(root):
        step(x, y)
    root.finish()
    spans = {s.name: s for s in trace.spans(root.trace_id)}
    call = spans["fused_step.call"]
    # the step's first call is a process span, which joins the trace of
    # the request that pays it and encloses the call's own span
    first = spans["fused_step.first_call"]
    assert first.parent_id == root.span_id
    assert call.parent_id == first.span_id
    assert spans["fused_step.key_split"].parent_id == call.span_id
    assert spans["executor.call"].parent_id == call.span_id
    assert spans["executor.call"].args["site"] == \
        "fused_step:HybridSequential"


# ---------------------------------------------------------------------------
# the process trace: what happens once a process, a net or a compiled
# signature records always, in a store of its own
# ---------------------------------------------------------------------------

def test_process_span_records_with_sampling_off_and_no_profiler():
    assert not trace.enabled()
    with trace.process_span("unit.once", what="x") as sp:
        assert trace.current_span() is sp
    assert trace.current_span() is None
    (rec,) = trace.process_spans()
    assert rec["name"] == "unit.once" and rec["parent"] == "process"
    assert rec["args"]["what"] == "x" and rec["args"]["outcome"] == "ok"
    # the ring, its provider's counters and the healthz gate are the
    # requests': the process trace moves none of them
    assert trace.spans() == [] and not trace.active()
    assert trace.stats()["spans_recorded"] == 0


def test_ordinary_span_records_as_a_process_spans_child_and_not_outside():
    with trace.span("before"):
        pass
    with trace.process_span("unit.once"):
        with trace.span("inner", k=1):
            with trace.span("innermost"):
                pass
    with trace.span("after"):
        pass
    by_name = {r["name"]: r for r in trace.process_spans()}
    assert sorted(by_name) == ["inner", "innermost", "unit.once"]
    assert by_name["inner"]["parent"] == "unit.once"
    assert by_name["inner"]["parent_id"] == by_name["unit.once"]["span_id"]
    assert by_name["innermost"]["parent"] == "inner"
    assert by_name["unit.once"]["t0"] <= by_name["inner"]["t0"] \
        <= by_name["inner"]["t1"] <= by_name["unit.once"]["t1"]


def test_process_span_joins_the_trace_of_a_current_request():
    trace.configure(sample=1.0, ring=64)
    root = trace.start_trace("server.request")
    with trace.activate(root):
        with trace.process_span("unit.once", model="m"):
            pass
        trace.record_process_span("jit.compile", 0.25, fun="f")
    root.finish()
    spans = {s.name: s for s in trace.spans(root.trace_id)}
    assert spans["unit.once"].parent_id == root.span_id
    assert spans["jit.compile"].parent_id == root.span_id
    assert abs(spans["jit.compile"].t1 - spans["jit.compile"].t0
               - 0.25) < 1e-9
    assert trace.process_spans() == []


def test_process_store_evicts_oldest_first_and_counts(monkeypatch):
    monkeypatch.setattr(trace, "_process_store", trace._Ring(4))
    for i in range(7):
        with trace.process_span("unit.once", i=i):
            pass
    assert [r["args"]["i"] for r in trace.process_spans()] == [3, 4, 5, 6]
    summary = trace.process_summary()
    assert summary["dropped"] == 3 and summary["cap"] == 4
    assert summary["spans"]["unit.once"]["count"] == 4
    # request traffic has its own ring and evicts nothing here
    trace.configure(sample=1.0, ring=2)
    for _ in range(5):
        trace.start_trace("server.request").finish()
    assert len(trace.process_spans()) == 4


def test_process_spans_are_on_perf_counters_clock():
    before = time.perf_counter()
    with trace.process_span("unit.once"):
        inside = time.perf_counter()
    after = time.perf_counter()
    trace.record_process_span("unit.import")    # since the package's import
    once, imported = trace.process_spans()
    assert before <= once["t0"] <= inside <= once["t1"] <= after
    assert imported["t0"] < before and after <= imported["t1"]
    summary = trace.process_summary()["spans"]
    assert summary["unit.import"]["first_start_s"] == 0.0
    assert summary["unit.once"]["first_start_s"] > 0.0
    assert abs(summary["unit.once"]["total_s"]
               - (once["t1"] - once["t0"])) < 1e-5


def test_export_carries_the_process_trace_under_its_root():
    assert trace.export()["traceEvents"] == []      # an empty store: no root
    with trace.process_span("unit.once", site="s"):
        with trace.span("inner"):
            pass
    events = {e["name"]: e for e in trace.export(service="t")["traceEvents"]}
    assert sorted(events) == ["inner", "process", "unit.once"]
    root, once = events["process"], events["unit.once"]
    assert once["args"]["parent_id"] == root["args"]["span_id"]
    assert events["inner"]["args"]["parent_id"] == once["args"]["span_id"]
    assert len({e["args"]["trace_id"] for e in events.values()}) == 1
    assert root["ts"] <= once["ts"] \
        and once["ts"] + once["dur"] <= root["ts"] + root["dur"] + 1
    # a request's id selects the ring's spans alone
    assert trace.export("ab" * 8)["traceEvents"] == []
    assert {e["name"] for e in trace.export(
        root["args"]["trace_id"])["traceEvents"]} == set(events)


def test_compile_counters_move_at_the_first_call_and_then_never():
    from incubator_mxnet_tpu import executor_cache as xc
    step, x, y = _dense_step()
    site = "fused_step:HybridSequential"
    before = dict(xc.stats()["jit"]["per_function"].get(
        site, dict.fromkeys(xc._JIT_FIELDS, 0)))
    n_log = len(xc.compile_log())
    step(x, y).block_until_ready()
    first = xc.stats()["jit"]
    mine = first["per_function"][site]
    assert mine["compiles"] == before["compiles"] + 1
    assert mine["trace_s"] > before["trace_s"]
    assert mine["lower_s"] > before["lower_s"]
    assert mine["backend_compile_s"] > before["backend_compile_s"]
    # the key split compiled too, under its function's own name
    assert first["compiles"] >= mine["compiles"] + 1
    assert first["compiles"] == sum(
        f["compiles"] for f in first["per_function"].values())
    record = [r for r in xc.compile_log()[n_log:] if r["site"] == site][-1]
    assert record["fun"] == "step" and record["at"] <= time.perf_counter()
    assert record["trace_s"] > 0 and record["backend_compile_s"] > 0
    # ten more steps: one executable, and the listeners are never reached
    n_first = len(xc.compile_log())
    for _ in range(10):
        step(x, y)
    step(x, y).block_until_ready()
    assert step._executor.compile_count == 1
    assert xc.stats()["jit"] == first
    assert len(xc.compile_log()) == n_first
    assert "jit" in profiler.provider_stats()["cold_start"]


def test_scope_names_key_the_persistent_cache(tmp_path, monkeypatch):
    """An executable served from the persistent cache carries the names
    it was compiled with: two programs that differ only in their
    ``jax.named_scope`` must not share an entry, or a profile attributes
    device time to scopes that are gone."""
    import jax
    from jax._src import compilation_cache as _jcc
    from incubator_mxnet_tpu import executor_cache as xc

    def program(scope):
        def f(a):
            with jax.named_scope(scope):
                return (a * 2.0).sum()
        return f

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_compilation_cache_include_metadata_in_key")}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    xc._reset_compile_cache_for_tests()
    try:
        assert xc.ensure_compile_cache() == str(tmp_path)
        x = jnp.arange(8.0)
        records = []
        for scope in ("forward", "forward", "optimizer"):
            ex = xc.Executor(program(scope), "test:scope_key")
            ex(x).block_until_ready()
            records.append(xc.compile_log()[-1])
        assert [r["site"] for r in records] == ["test:scope_key"] * 3
        assert [(r["cache_hits"], r["cache_misses"]) for r in records] == \
            [(0, 1), (1, 0), (0, 1)]
        assert records[1]["cache_retrieval_s"] > 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        xc._reset_compile_cache_for_tests()
        _jcc.reset_cache()


# ---------------------------------------------------------------------------
# end-to-end: process-replica fleet, merged timeline, coverage gate
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_process_fleet_merged_timeline_covers_client_wall_time(
        artifact, tmp_path):
    """The ISSUE 14 acceptance drive: one request through a REAL
    subprocess-replica fleet with an injected fault on the first hop.
    The merged router+replica timeline must show the fault, the typed
    failed hop, the winning failover hop, the replica-side spans
    parented across the process boundary — and account for >= 95% of
    the router-observed wall time (no dark latency)."""
    from incubator_mxnet_tpu.serving import FleetRouter, ReplicaFleet
    fleet = ReplicaFleet({"m": artifact}, n=2,
                         backend="process").spawn()
    router = FleetRouter(fleet)
    port = router.start()
    try:
        body = json.dumps({"inputs": [_x().tolist()]}).encode()
        # one untraced warm request: the router's meta cache and the
        # replicas' request paths are primed, so the traced request
        # measures the serving path, not one-time setup
        warm = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/m:predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(warm, timeout=120) as r0:
            assert r0.status == 200
        # exactly one replica-side fault: hop 1 fails typed, hop 2 wins
        fault.configure("serving.replica_exec:error:n=1")
        tid = "ad" * 8
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/m:predict", data=body,
            headers={"Content-Type": "application/json",
                     trace.HEADER: f"{tid}-{'2e' * 4}-1"})
        t0 = time.monotonic()
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
        client_ms = (time.monotonic() - t0) * 1000.0

        dumps = []
        router_dump = tmp_path / "router.json"
        router_dump.write_text(json.dumps(trace.export(
            tid, service="router")))
        dumps.append(str(router_dump))
        for i, r in enumerate(fleet.replicas):
            raw = urllib.request.urlopen(
                f"http://127.0.0.1:{r.port}/v1/trace?trace_id={tid}",
                timeout=30).read()
            p = tmp_path / f"replica{i}.json"
            p.write_text(raw.decode())
            dumps.append(str(p))

        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "traceview.py"), *dumps,
             "--trace", tid, "--coverage", "--min-coverage", "0.95"],
            capture_output=True, text=True)
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        out = proc.stdout
        assert "router.request" in out
        assert "!! TransientFault" in out      # the failed hop, typed
        assert "fault.serving.replica_exec" in out
        assert "server.request" in out         # replica-side adopted
        assert "batch.execute" in out

        # cross-process parenting: the replica's server.request hangs
        # off a router hop span
        merged = []
        for d in dumps:
            merged.extend(json.loads(open(d).read())["traceEvents"])
        spans = [e for e in merged if e["ph"] == "X"]
        by_id = {e["args"]["span_id"]: e for e in spans}
        server_spans = [e for e in spans
                        if e["name"] == "server.request"]
        assert server_spans
        for e in server_spans:
            parent = by_id.get(e["args"]["parent_id"])
            assert parent is not None
            assert parent["name"] == "router.hop"
            assert parent["args"]["service"] == "router"

        # the root span is within sanity distance of the client clock
        root = max((e for e in spans
                    if e["name"] == "router.request"),
                   key=lambda e: e["dur"])
        root_ms = root["dur"] / 1000.0
        assert root_ms <= client_ms + 1.0
        assert root_ms >= 0.5 * client_ms, (root_ms, client_ms)
    finally:
        router.shutdown()
