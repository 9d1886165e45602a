"""Serving subsystem tests (ISSUE 3 tentpole).

The core contract: N concurrent single requests through the dynamic
batcher produce outputs **bitwise equal** to N sequential unbatched
``load_predictor`` calls — across padding-bucket boundaries, through
the HTTP front end, and under a pinned chaos spec.  Plus admission
(429/504), atomic reload, warmup compile-count flatline, and drain.
"""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import deploy, fault, profiler
from incubator_mxnet_tpu.serving import (DeadlineExceeded, DynamicBatcher,
                                         InferenceServer, ModelRepository,
                                         QueueFullError, ServingMetrics)
from incubator_mxnet_tpu.serving.admission import Admission, ModelNotFound
from incubator_mxnet_tpu.serving.batcher import parse_buckets


def _mlp_fwd(params, x):
    y = x
    for w in params["layers"]:
        y = jnp.tanh(y @ w)
    return y


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    """One exported MLP shared by the module (export is the slow bit)."""
    rng = onp.random.RandomState(7)
    params = {"layers": [rng.randn(24, 24).astype(onp.float32) * 0.3
                         for _ in range(3)]}
    x = rng.randn(2, 24).astype(onp.float32)
    prefix = str(tmp_path_factory.mktemp("serving") / "mlp")
    deploy.export_model(_mlp_fwd, (x,), prefix, params=params)
    return prefix


@pytest.fixture
def predictor(artifact):
    return deploy.load_predictor(artifact)


def _instances(n, seed=0):
    rng = onp.random.RandomState(seed)
    return [rng.randn(24).astype(onp.float32) for _ in range(n)]


def _unbatched_refs(predictor, instances):
    return [predictor(x[None])[0] for x in instances]


# ---------------------------------------------------------------------------
# batcher core
# ---------------------------------------------------------------------------

def test_parse_buckets_env(monkeypatch):
    assert parse_buckets() == [1, 2, 4, 8, 16, 32]
    monkeypatch.setenv("MXNET_SERVING_BATCH_BUCKETS", "4,1,4,9")
    assert parse_buckets() == [1, 4, 9]
    with pytest.raises(ValueError):
        parse_buckets("0,2")
    with pytest.raises(ValueError):
        parse_buckets("a,b")


def test_batched_outputs_bitwise_equal_unbatched(predictor):
    """The acceptance-criteria property: concurrent singles through the
    batcher == sequential unbatched calls, bit for bit, with N chosen
    to straddle bucket boundaries (23 -> buckets 1..32)."""
    batcher = DynamicBatcher("m", predictor, max_latency_ms=20.0)
    try:
        instances = _instances(23)
        refs = _unbatched_refs(predictor, instances)
        results = [None] * len(instances)

        def call(i):
            out, _ = batcher.submit((instances[i],))
            results[i] = out

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(instances))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, (got, ref) in enumerate(zip(results, refs)):
            assert got.dtype == ref.dtype
            assert (got == ref).all(), f"request {i} diverged"
    finally:
        batcher.close()


def test_submit_async_multiplexed_inflight(predictor):
    """One caller thread holding many single requests in flight via
    submit_async (the async-front-end shape): all coalesce into few
    batches, results bitwise equal to unbatched."""
    metrics = ServingMetrics()
    batcher = DynamicBatcher("m", predictor, metrics=metrics,
                             max_latency_ms=20.0)
    try:
        instances = _instances(16, seed=21)
        refs = _unbatched_refs(predictor, instances)
        handles = [batcher.submit_async((x,)) for x in instances]
        outs = [h.result()[0] for h in handles]
        for got, ref in zip(outs, refs):
            assert (got == ref).all()
        snap = metrics.snapshot()
        assert 1 <= snap["m.batches"] <= 2   # 16 singles, not 16 execs
    finally:
        batcher.close()


def test_batcher_coalesces_under_concurrency(predictor):
    """Synchronized submits must land in fewer device launches than
    requests (that is the whole point)."""
    metrics = ServingMetrics()
    batcher = DynamicBatcher("m", predictor, metrics=metrics,
                             max_latency_ms=25.0)
    try:
        instances = _instances(16, seed=3)
        barrier = threading.Barrier(len(instances))

        def call(i):
            barrier.wait()
            batcher.submit((instances[i],))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(instances))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = metrics.snapshot()
        assert snap["m.requests"] == 0  # only server records requests
        assert 1 <= snap["m.batches"] < len(instances)
        assert snap["m.batch_size"]["count"] == snap["m.batches"]
    finally:
        batcher.close()


def test_batcher_partial_batch_timer_flush(predictor):
    """A lone request must not wait for a full bucket: the
    MXNET_SERVING_MAX_LATENCY_MS timer flushes it."""
    batcher = DynamicBatcher("m", predictor, max_latency_ms=10.0)
    try:
        t0 = time.monotonic()
        out, timing = batcher.submit((_instances(1)[0],))
        elapsed_ms = (time.monotonic() - t0) * 1000.0
        assert out.shape == (24,)
        assert elapsed_ms < 5000.0
        assert timing["queue_ms"] >= 0.0
    finally:
        batcher.close()


def test_batcher_mixed_signatures_not_mixed(predictor, artifact):
    """Requests with different instance shapes must never share a
    batch (the padded batch must stay rectangular)."""
    batcher = DynamicBatcher("m", predictor, max_latency_ms=10.0)
    try:
        good = _instances(1)[0]
        out, _ = batcher.submit((good,))
        assert out.shape == (24,)
        with pytest.raises(Exception):
            # wrong trailing shape is rejected by the predictor; the
            # error must come back to this caller, not poison others
            batcher.submit((onp.zeros(7, onp.float32),))
        out, _ = batcher.submit((good,))   # batcher still serves
        assert out.shape == (24,)
    finally:
        batcher.close()


def test_batcher_deadline_504_with_time_split(predictor):
    batcher = DynamicBatcher("m", predictor, max_latency_ms=60000.0,
                             max_batch=64)
    try:
        with pytest.raises(DeadlineExceeded) as ei:
            # deadline shorter than the flush timer: request dies queued
            batcher.submit((_instances(1)[0],), deadline_ms=30.0)
        err = ei.value
        assert err.http_status == 504
        payload = err.payload()
        assert payload.get("queue_ms", 0) > 0
    finally:
        batcher.close()


def test_batcher_drain_finishes_inflight(predictor):
    batcher = DynamicBatcher("m", predictor, max_latency_ms=500.0)
    results = []
    try:
        t = threading.Thread(target=lambda: results.append(
            batcher.submit((_instances(1)[0],))[0]))
        t.start()
        time.sleep(0.05)    # request is queued, timer not yet ripe
    finally:
        assert batcher.drain(timeout=30.0)
    t.join(10.0)
    assert len(results) == 1 and results[0].shape == (24,)
    from incubator_mxnet_tpu.serving import ShuttingDown
    with pytest.raises(ShuttingDown):
        batcher.submit((_instances(1)[0],))


# ---------------------------------------------------------------------------
# chaos: pinned fault spec through the batcher
# ---------------------------------------------------------------------------

def test_batching_correct_under_pinned_chaos(predictor):
    """The acceptance-criteria chaos clause: with deterministic
    transient faults on serving.execute (retried away by fault.retry)
    and delays on serving.enqueue, outputs are still bitwise equal."""
    # n=2 < MXNET_SERVING_RETRIES(3): the first batch execution fails
    # twice deterministically and succeeds on the final retry attempt
    fault.configure("serving.execute:error:n=2,"
                    "serving.enqueue:delay:ms=2")
    try:
        batcher = DynamicBatcher("m", predictor, max_latency_ms=15.0)
        try:
            instances = _instances(17, seed=11)
            refs = _unbatched_refs(predictor, instances)
            results = [None] * len(instances)

            def call(i):
                from incubator_mxnet_tpu.serving.admission import \
                    checked_enqueue
                checked_enqueue("m")
                out, _ = batcher.submit((instances[i],))
                results[i] = out

            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(len(instances))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for got, ref in zip(results, refs):
                assert got is not None, "request lost under chaos"
                assert (got == ref).all()
            calls, fired = fault.stats()["serving.execute"]
            assert fired > 0, "chaos spec never fired — test is vacuous"
        finally:
            batcher.close()
    finally:
        fault.configure(None)
        fault.reset()


def test_permanent_fault_surfaces_to_all_requests(predictor):
    fault.configure("serving.execute:error:class=permanent:n=1")
    try:
        batcher = DynamicBatcher("m", predictor, max_latency_ms=5.0)
        try:
            with pytest.raises(Exception) as ei:
                batcher.submit((_instances(1)[0],))
            assert "permanent" in str(ei.value)
            fault.configure(None)
            out, _ = batcher.submit((_instances(1)[0],))  # recovers
            assert out.shape == (24,)
        finally:
            batcher.close()
    finally:
        fault.configure(None)
        fault.reset()


# ---------------------------------------------------------------------------
# admission
# ---------------------------------------------------------------------------

def test_admission_queue_full_429():
    adm = Admission(queue_depth=4)
    adm.admit("m", 3)
    with pytest.raises(QueueFullError) as ei:
        adm.admit("m", 4)
    assert ei.value.http_status == 429


def test_admission_deadline_cap():
    adm = Admission(default_deadline_ms=1000.0)
    assert adm.deadline_ms(None) == 1000.0
    assert adm.deadline_ms(200.0) == 200.0
    assert adm.deadline_ms(5000.0) == 1000.0  # server cap wins


def test_admission_drain_503():
    from incubator_mxnet_tpu.serving import ShuttingDown
    adm = Admission()
    adm.begin_drain()
    with pytest.raises(ShuttingDown):
        adm.admit("m", 0)


# ---------------------------------------------------------------------------
# model repository
# ---------------------------------------------------------------------------

def test_repository_load_warmup_compile_flatline(artifact):
    repo = ModelRepository(metrics=ServingMetrics())
    try:
        info = repo.load("mlp", artifact)
        assert info["version"] == 1 and info["batch_polymorphic"]
        warmed = repo.compile_counts()["mlp"]
        assert warmed >= len(info["buckets"])
        # traffic at every bucket size: zero new executables
        for n in (1, 3, 5, 8, 17, 32):
            outs = [repo.predict("mlp", (x,))
                    for x in _instances(min(n, 4), seed=n)]
            assert all(o[0].shape == (24,) for o in outs)
        assert repo.compile_counts()["mlp"] == warmed
    finally:
        repo.drain_all()


def test_repository_duplicate_load_rejected(artifact):
    repo = ModelRepository()
    try:
        repo.load("m", artifact, warmup=False)
        with pytest.raises(Exception, match="already loaded"):
            repo.load("m", artifact, warmup=False)
    finally:
        repo.drain_all()


def test_repository_unload_and_missing(artifact):
    repo = ModelRepository()
    try:
        repo.load("m", artifact, warmup=False)
        assert repo.unload("m")["unloaded"] == "m"
        with pytest.raises(ModelNotFound):
            repo.get("m")
        with pytest.raises(ModelNotFound):
            repo.unload("m")
    finally:
        repo.drain_all()


def test_repository_reload_atomic_swap(artifact):
    """Reload under load: the swap bumps the version, no request ever
    errors, and in-flight requests complete on whichever version they
    entered with (outputs match the single shared artifact here, so
    correctness == bitwise match against the reference)."""
    repo = ModelRepository(metrics=ServingMetrics())
    try:
        repo.load("m", artifact, warmup=False)
        pred = deploy.load_predictor(artifact)
        instances = _instances(24, seed=2)
        refs = _unbatched_refs(pred, instances)
        errors, results = [], [None] * len(instances)

        def call(i):
            try:
                results[i] = repo.predict("m", (instances[i],))[0]
            except Exception as e:   # noqa: BLE001 — recorded for assert
                errors.append(e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(instances))]
        for t in threads[:12]:
            t.start()
        info = repo.reload("m")          # swap mid-traffic
        for t in threads[12:]:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert info["version"] == 2
        assert repo.get("m").version == 2
        for got, ref in zip(results, refs):
            assert (got == ref).all()
    finally:
        repo.drain_all()


def test_pending_result_cancel_skips_execution(predictor):
    """A cancelled queued request is dropped by the flush worker
    without device time; result() reports the withdrawal as a typed
    DeadlineExceeded instead of returning garbage."""
    metrics = ServingMetrics()
    batcher = DynamicBatcher("m", predictor, metrics=metrics,
                             max_latency_ms=40.0)
    try:
        handles = [batcher.submit_async((x,))
                   for x in _instances(3, seed=31)]
        for h in handles:
            h.cancel()
        for h in handles:
            with pytest.raises(DeadlineExceeded, match="cancelled"):
                h.result()
        assert metrics.snapshot().get("m.batches", 0) == 0  # no exec
        out, _ = batcher.submit((_instances(1)[0],))  # still serves
        assert out.shape == (24,)
    finally:
        batcher.close()


def test_reload_under_sustained_load_window(artifact):
    """The reload-under-load satellite: a concurrent predict volley
    runs *through* two :reload swaps — zero errors, every response
    bitwise-stable across the version flips (same artifact on both
    sides, so stability == bitwise match with the reference)."""
    repo = ModelRepository(metrics=ServingMetrics())
    try:
        repo.load("m", artifact, warmup=False)
        pred = deploy.load_predictor(artifact)
        instances = _instances(8, seed=13)
        refs = _unbatched_refs(pred, instances)
        stop = threading.Event()
        errors, served = [], []

        def hammer(idx):
            k = 0
            while not stop.is_set():
                i = (idx + k) % len(instances)
                try:
                    out = repo.predict("m", (instances[i],))[0]
                    assert (out == refs[i]).all(), \
                        f"response drifted across swap (instance {i})"
                    served.append(1)
                except Exception as e:  # noqa: BLE001 — for assert
                    errors.append(e)
                    return
                k += 1

        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)                    # volley in flight
        info = repo.reload("m")             # swap #1 under load
        info = repo.reload("m")             # swap #2 under load
        time.sleep(0.05)                    # volley outlives the roll
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors
        assert len(served) > 0
        assert info["version"] == 3
        assert repo.get("m").version == 3
    finally:
        repo.drain_all()


# ---------------------------------------------------------------------------
# structured /healthz (probe contract)
# ---------------------------------------------------------------------------

def test_healthz_structured_state_json_shape(artifact):
    """Pin the /healthz JSON shape: per-model state must distinguish
    loading (warming, do not admit) / ready / draining, with queue
    depth — the contract fleet probes and rolling reload route on."""
    from incubator_mxnet_tpu import flightrec
    from incubator_mxnet_tpu.serving.server import health_body
    # the always-on flight recorder is additive the same way tracing
    # is: with recording off the PR 3 bare shape below stays pinned
    # exactly; the flight-on additive subshape is pinned at the end
    flightrec.configure(ring=0)
    repo = ModelRepository(metrics=ServingMetrics())
    try:
        repo.load("mlp", artifact, warmup=False)
        code, body = health_body(repo, time.monotonic())
        assert code == 200
        assert set(body) == {"status", "uptime_s", "queue_depth",
                             "models"}
        assert set(body["models"]["mlp"]) == {"state", "version",
                                              "queue_depth",
                                              "compile_count",
                                              "cold_start_ms",
                                              "aot_buckets",
                                              "aot_load_failures",
                                              "device"}
        assert body["status"] == "ok"
        assert body["queue_depth"] == 0
        m = dict(body["models"]["mlp"])
        # load+warmup duration: present and positive for a ready model
        assert m.pop("cold_start_ms") > 0
        assert m == {
            "state": "ready", "version": 1, "queue_depth": 0,
            "compile_count": repo.compile_counts()["mlp"],
            "aot_buckets": [], "aot_load_failures": 0,
            # where the model's parameters live, as JAX reports it
            # (chip_smoke.py reads this to prove the server is on the
            # chip)
            "device": {"platform": "cpu",
                       "kind": jax.devices()[0].device_kind}}
        # a model mid-build reports `loading` (not absent, not ready)
        with repo._loading_state("incoming"):
            assert repo.loading_names() == ["incoming"]
            _, b2 = health_body(repo, time.monotonic())
            assert b2["models"]["incoming"] == {
                "state": "loading", "version": None,
                "queue_depth": 0, "compile_count": None,
                "cold_start_ms": None, "aot_buckets": [],
                "aot_load_failures": 0, "device": None}
        _, b3 = health_body(repo, time.monotonic())
        assert "incoming" not in b3["models"]
        # draining flips status, the code, and every model's state
        repo.admission.begin_drain()
        code4, b4 = health_body(repo, time.monotonic())
        assert code4 == 503 and b4["status"] == "draining"
        assert b4["models"]["mlp"]["state"] == "draining"
        # request-scoped tracing is ADDITIVE: the "trace" block
        # appears only while tracing is on (everything pinned above
        # ran with it off — the bare-server shape), with this exact
        # subshape (docs/observability.md)
        from incubator_mxnet_tpu import trace
        try:
            trace.configure(sample=1.0)
            _, b5 = health_body(repo, time.monotonic())
            assert set(b5) == {"status", "uptime_s", "queue_depth",
                               "models", "trace"}
            assert set(b5["trace"]) == {"sample", "ring", "spans",
                                        "dropped", "slow_k"}
            # flight recorder: additive the same way — the key appears
            # only once recording is on AND something recorded, with
            # this exact subshape (docs/observability.md)
            flightrec.configure(ring=64)
            _, b6 = health_body(repo, time.monotonic())
            assert "flight" not in b6          # nothing recorded yet
            flightrec.record("lifecycle", "shape-pin")
            _, b7 = health_body(repo, time.monotonic())
            assert set(b7) == {"status", "uptime_s", "queue_depth",
                               "models", "trace", "flight"}
            assert set(b7["flight"]) == {"ring", "events", "evictions",
                                         "dumps"}
        finally:
            trace.reset()
            flightrec.reset()
    finally:
        repo.drain_all()


def test_http_healthz_reports_structured_state(server):
    """The wire shape matches health_body (one implementation)."""
    status, raw = _get(server.port, "/healthz")
    body = json.loads(raw)
    assert status == 200
    assert body["models"]["mlp"]["state"] == "ready"
    assert "queue_depth" in body and "queue_depth" in \
        body["models"]["mlp"]


# ---------------------------------------------------------------------------
# HTTP server end-to-end
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


@pytest.fixture
def server(artifact):
    srv = InferenceServer()
    srv.repository.load("mlp", artifact)
    srv.start()
    yield srv
    srv.shutdown()


def test_http_predict_bitwise_and_metrics(server, artifact, predictor):
    port = server.port
    instances = _instances(9, seed=4)
    refs = _unbatched_refs(predictor, instances)
    results = [None] * len(instances)

    def call(i):
        status, body = _post(port, "/v1/models/mlp:predict",
                             {"inputs": [instances[i].tolist()]})
        assert status == 200
        results[i] = onp.asarray(body["outputs"][0], onp.float32)

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(instances))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for got, ref in zip(results, refs):
        assert (got == ref).all()   # JSON round-trips f32 exactly

    status, raw = _get(port, "/metrics")
    assert status == 200
    text = raw.decode()
    assert 'mxnet_serving_requests_total{model="mlp",code="200"} 9' \
        in text
    assert 'mxnet_serving_compile_total{model="mlp"}' in text
    # compile count scraped now == scraped after more warm traffic
    before = [l for l in text.splitlines()
              if l.startswith("mxnet_serving_compile_total")]
    call(0)
    after = [l for l in _get(port, "/metrics")[1].decode().splitlines()
             if l.startswith("mxnet_serving_compile_total")]
    assert before == after, "compile count grew on warm traffic"


def test_http_healthz_and_model_listing(server):
    status, raw = _get(server.port, "/healthz")
    body = json.loads(raw)
    assert status == 200 and body["status"] == "ok"
    assert body["models"]["mlp"]["version"] == 1
    status, raw = _get(server.port, "/v1/models")
    assert json.loads(raw)["models"]["mlp"]["batch_polymorphic"]


def test_http_errors(server):
    port = server.port
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, "/v1/models/nosuch:predict", {"inputs": [[0.0]]})
    assert ei.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, "/v1/models/mlp:predict", {"bad": 1})
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, "/v1/models/mlp:predict",
              {"inputs": [[0.0, 1.0]]})    # wrong instance shape
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, "/v1/models/mlp:predict",
              {"inputs": [[0.0] * 24], "timeout_ms": 0.001})
    assert ei.value.code == 504
    body = json.loads(ei.value.read())
    assert "queue_ms" in body


def test_http_admin_load_reload_unload(server, artifact):
    port = server.port
    status, body = _post(port, "/v1/models/second:load",
                         {"path": artifact, "warmup": False})
    assert status == 200 and body["version"] == 1
    status, body = _post(port, "/v1/models/second:reload", {})
    assert status == 200 and body["version"] == 2
    x = _instances(1, seed=9)[0]
    status, body = _post(port, "/v1/models/second:predict",
                         {"inputs": [x.tolist()]})
    assert status == 200
    status, body = _post(port, "/v1/models/second:unload", {})
    assert status == 200
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, "/v1/models/second:predict", {"inputs": [x.tolist()]})
    assert ei.value.code == 404


def test_http_graceful_drain(artifact):
    srv = InferenceServer()
    srv.repository.load("mlp", artifact, warmup=False)
    port = srv.start()
    srv.repository.admission.begin_drain()
    status, raw = None, None
    try:
        _get(port, "/healthz")
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    assert status == 503
    assert json.loads(raw)["status"] == "draining"
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, "/v1/models/mlp:predict",
              {"inputs": [_instances(1)[0].tolist()]})
    assert ei.value.code == 503
    srv.shutdown()


# ---------------------------------------------------------------------------
# profiler integration
# ---------------------------------------------------------------------------

def test_serving_stats_in_profiler_dumps(artifact):
    srv = InferenceServer()
    try:
        srv.repository.load("mlp", artifact, warmup=False)
        port = srv.start()
        _post(port, "/v1/models/mlp:predict",
              {"inputs": [_instances(1)[0].tolist()]})
        table = profiler.dumps()
        assert "[serving]" in table and "[bulk_stats]" in table
        assert "mlp.requests" in table
        snap = profiler.provider_stats()["serving"]
        assert snap["mlp.requests"] == 1
        assert snap["compile_total"] >= 1
    finally:
        srv.shutdown()
