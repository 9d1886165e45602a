#!/usr/bin/env python
"""Serving benchmark: dynamic batching vs sequential unbatched predict.

Measures what the serving subsystem exists to deliver — throughput on
concurrent single requests — and emits a BENCH-style JSON record so the
serving perf trajectory is tracked like `BENCH_r0*.json`:

  baseline   sequential `load_predictor` calls at batch 1 (what a
             naive request-per-call server does per request)
  batched    closed-loop load: N concurrent clients (default 64) each
             issuing single requests back-to-back for --rounds rounds
             through the warmed InferenceServer repository (requests
             coalesce into padded buckets); best of --trials volleys
             is reported, same total request count as the baseline

Modes:
  (default)      batcher-level measurement, full N=64
  --check        exit 1 unless batched >= 3x baseline (the ISSUE 3
                 acceptance floor), outputs bitwise equal, and the
                 compile count did not move after warmup
  --smoke        CI stage: ephemeral HTTP server end-to-end — warmup,
                 concurrent requests over the wire, /metrics scrape,
                 compile-count stability (no perf floor: wire + JSON
                 overhead and CI noise are not what we gate on)
  --model-zoo M  run against a real model_zoo artifact (exported via
                 scripts/export_model_zoo.py) instead of the toy MLP
  --replicas N   fleet scaling curve (ISSUE 8): closed-loop volleys
                 through the FleetRouter over 1, 2, ... N replicas
                 (process backend by default — real per-replica
                 isolation), reporting throughput + p99 per count.
                 With --check, enforces zero failed requests, output
                 parity, and the 2-replica >= 1.6x single-replica
                 floor — the floor is enforced only where the host
                 has >= 2 CPUs to express replica parallelism (a
                 1-core container timeshares the replicas, so the
                 ratio is physics, not a regression; the record then
                 carries floor_checked=false with the reason)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as onp   # noqa: E402

from incubator_mxnet_tpu.serving.loadgen.clients import (  # noqa: E402
    percentile, provenance, sync_volley, wave_volley)


def _toy_artifact(prefix, width=128, depth=6):
    """Dispatch-overhead-dominated MLP: the regime a request-per-call
    server wastes, which batching reclaims.  The fleet bench widens it
    (width 256, depth 8) so replica-side compute dominates the router
    hop and replica scaling is what gets measured."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu import deploy

    def fwd(params, x):
        y = x
        for w in params["layers"]:
            y = jnp.tanh(y @ w)
        return y

    rng = onp.random.RandomState(0)
    params = {"layers": [rng.randn(width, width).astype(onp.float32)
                         * 0.1 for _ in range(depth)]}
    x = rng.randn(1, width).astype(onp.float32)
    deploy.export_model(fwd, (x,), prefix, params=params)
    return prefix


def _platform():
    """Where the work ran, asked of the process that ran it."""
    import jax
    return jax.devices()[0].platform


def _zoo_artifact(prefix, model):
    from scripts.export_model_zoo import main as export_main
    export_main(["--model", model, "--out", prefix,
                 "--image-size", "32", "--classes", "10"])
    return prefix


def _instances(meta, n, seed=1):
    rng = onp.random.RandomState(seed)
    shapes = [tuple(s["shape"][1:]) for s in meta["inputs"]]
    dtypes = [s["dtype"] for s in meta["inputs"]]
    return [tuple(rng.randn(*sh).astype(dt)
                  for sh, dt in zip(shapes, dtypes)) for _ in range(n)]


def bench(args):
    from incubator_mxnet_tpu import deploy
    from incubator_mxnet_tpu.serving import InferenceServer

    prefix = os.path.join(args.workdir, "serving_bench_model")
    if args.model_zoo:
        _zoo_artifact(prefix, args.model_zoo)
    else:
        _toy_artifact(prefix)

    pred = deploy.load_predictor(prefix)
    instances = _instances(pred.meta, args.requests)
    total = args.requests * args.rounds
    pred(*[x[None] for x in instances[0]])   # warm batch-1 off-clock

    # throughput-mode flush window (docs/serving.md tuning guide): give
    # bursts time to fill buckets instead of fragmenting into partial
    # flushes; a latency-sensitive deployment would lower this
    os.environ.setdefault("MXNET_SERVING_MAX_LATENCY_MS", "15")
    srv = InferenceServer()
    srv.repository.load("bench", prefix)           # load + warm buckets
    compile_before = srv.repository.compile_counts()["bench"]
    results = [None] * args.requests

    def baseline_pass():
        lat = []
        t0 = time.monotonic()
        for k in range(total):
            t1 = time.monotonic()
            pred(*[x[None] for x in instances[k % args.requests]])
            lat.append((time.monotonic() - t1) * 1000.0)
        dt = time.monotonic() - t0
        return {"rps": total / dt, "p99_ms": percentile(lat, 0.99),
                "total_s": dt}

    def batched_volley():
        # args.requests single requests stay concurrently in flight,
        # multiplexed over a few client threads via predict_async —
        # the shape an async HTTP front end gives the batcher
        # (loadgen.clients.wave_volley owns the engine)
        res = wave_volley(
            lambda i: srv.repository.predict_async(
                "bench", instances[i]),
            args.requests, rounds=args.rounds, clients=args.clients,
            resolve=lambda h: h.result()[0])
        if res.errors:
            raise res.errors[0][1]
        results[:] = res.results
        return {"rps": res.rps, "p99_ms": res.p99_ms(),
                "total_s": res.total_s}

    # interleave baseline/batched trials and take the best of each:
    # shared-box throughput wobbles run to run, so measuring the two
    # sides in the same window (and at their respective bests) is what
    # makes the speedup ratio reproducible
    baseline, batched = None, None
    for _ in range(args.trials):
        b0 = baseline_pass()
        if baseline is None or b0["rps"] > baseline["rps"]:
            baseline = b0
        b1 = batched_volley()
        if batched is None or b1["rps"] > batched["rps"]:
            batched = b1
    compile_after = srv.repository.compile_counts()["bench"]
    snap = srv.metrics.snapshot()
    srv.shutdown()

    import jax
    bitwise_ok = True
    for i in range(0, args.requests, max(1, args.requests // 8)):
        ref = pred(*[x[None] for x in instances[i]])
        for a, b in zip(jax.tree_util.tree_leaves(results[i]),
                        jax.tree_util.tree_leaves(ref)):
            if not (onp.asarray(a) == onp.asarray(b)[0]).all():
                bitwise_ok = False
    rec = {
        "metric": ("serving_throughput_rps_zoo" if args.model_zoo
                   else "serving_throughput_rps"),
        "value": round(batched["rps"], 2),
        "unit": "req/s",
        "p99_ms": round(batched["p99_ms"], 3),
        "concurrency": args.requests,
        "requests": total,
        "flush_ms": float(os.environ["MXNET_SERVING_MAX_LATENCY_MS"]),
        "baseline_rps": round(baseline["rps"], 2),
        "baseline_p99_ms": round(baseline["p99_ms"], 3),
        "speedup_vs_unbatched": round(batched["rps"] / baseline["rps"],
                                      2),
        "batches": snap.get("bench.batches"),
        "mean_batch": round(
            snap["bench.batch_size"]["sum"]
            / max(1, snap["bench.batch_size"]["count"]), 2),
        "compile_total": compile_after,
        "compile_stable": compile_after == compile_before,
        "bitwise_equal_unbatched": bool(bitwise_ok),
        "platform": _platform(),
    }
    return rec


def fleet_bench(args):
    """Fleet scaling curve: closed-loop volleys through the router
    over growing replica counts.  Spawn/warmup time is off-clock; the
    measured window is pure request traffic."""
    import json as _json

    from incubator_mxnet_tpu import deploy
    from incubator_mxnet_tpu.serving import FleetRouter, ReplicaFleet

    prefix = os.path.join(args.workdir, "serving_fleet_model")
    _toy_artifact(prefix, width=256, depth=8)
    pred = deploy.load_predictor(prefix)
    instances = _instances(pred.meta, args.requests, seed=3)
    refs = [pred(*[x[None] for x in inst]) for inst in instances]
    encoded = [_json.dumps([x.tolist() for x in inst])
               for inst in instances]     # one serialization, reused
    total = args.requests * args.rounds

    counts = [1]
    c = 2
    while c < args.replicas:
        counts.append(c)
        c *= 2
    if args.replicas > 1:
        counts.append(args.replicas)
    counts = sorted(set(counts))

    curve = {}
    failed = []
    verified = True
    import jax
    for n in counts:
        fleet = ReplicaFleet({"bench": prefix}, n=n,
                             backend=args.backend).spawn()
        router = FleetRouter(fleet)
        try:
            def call(i):
                out, _t = router.route("bench", instances[i],
                                       inputs_json=encoded[i])
                return out

            res = sync_volley(call, args.requests,
                              rounds=args.rounds,
                              clients=args.clients)
            results = res.results
            failed.extend((n, i, repr(e)) for i, e in res.errors)
            curve[n] = {"rps": round(res.rps, 2),
                        "p99_ms": (round(res.p99_ms(), 3)
                                   if res.lat_ms else None),
                        "total_s": round(res.total_s, 3)}
            for i in range(0, args.requests,
                           max(1, args.requests // 8)):
                if results[i] is None:
                    continue
                for a, b in zip(results[i],
                                jax.tree_util.tree_leaves(refs[i])):
                    got = onp.asarray(a, dtype=onp.asarray(b).dtype)
                    if not (got == onp.asarray(b)[0]).all():
                        verified = False
        finally:
            router.shutdown()

    cpus = os.cpu_count() or 1
    scaling_2x = (round(curve[2]["rps"] / curve[1]["rps"], 2)
                  if 2 in curve and 1 in curve else None)
    floor_checked = scaling_2x is not None and cpus >= 2
    top = max(curve)
    rec = {
        "metric": "serving_fleet_scaling_rps",
        "value": curve[top]["rps"],
        "unit": "req/s",
        "replicas": top,
        "backend": args.backend,
        "per_replicas": {str(n): v for n, v in sorted(curve.items())},
        "scaling_2x": scaling_2x,
        "floor_checked": floor_checked,
        "floor_skip_reason": (
            "" if floor_checked else
            (f"host has {cpus} cpu(s); replica parallelism is not "
             f"expressible" if scaling_2x is not None
             else "needs --replicas >= 2")),
        "failed_requests": len(failed),
        "requests_per_count": total,
        "verified": bool(verified),
        "platform": _platform(),
    }
    failures = []
    if failed:
        failures.append(f"{len(failed)} failed requests "
                        f"(first: {failed[0]})")
    if not verified:
        failures.append("fleet outputs diverged from unbatched "
                        "baseline")
    if args.check and floor_checked and scaling_2x < 1.6:
        failures.append(
            f"2-replica scaling {scaling_2x}x < 1.6x floor")
    if args.check and not floor_checked:
        print(f"[serving_bench] scaling floor advisory only: "
              f"{rec['floor_skip_reason']}", file=sys.stderr,
              flush=True)
    return rec, failures


def _overhead_rig(args, prefix_name, seed):
    """Shared rig for the trace/flight overhead gates: toy artifact,
    1-replica thread fleet behind a router, a closed-loop volley
    closure, and the bitwise-parity checker — ONE harness, so a fix
    to the volley/parity machinery cannot diverge between the two
    gates.  Returns ``(router, volley, parity_of, total)``; the caller
    owns ``router.shutdown()``."""
    from incubator_mxnet_tpu import deploy
    from incubator_mxnet_tpu.serving import FleetRouter, ReplicaFleet

    prefix = os.path.join(args.workdir, prefix_name)
    _toy_artifact(prefix)
    pred = deploy.load_predictor(prefix)
    instances = _instances(pred.meta, args.requests, seed=seed)
    refs = [pred(*[x[None] for x in inst]) for inst in instances]
    total = args.requests * args.rounds

    fleet = ReplicaFleet({"bench": prefix}, n=1, backend="thread",
                         probe_ms=60000.0).spawn()
    router = FleetRouter(fleet)

    def volley():
        res = sync_volley(
            lambda i: router.route("bench", instances[i])[0],
            args.requests, rounds=args.rounds, clients=args.clients,
            collect_latency=False)
        return res.rps, res.results, [repr(e) for _, e in res.errors]

    def parity_of(results):
        import jax
        ok = True
        for i in range(args.requests):
            if results[i] is None:
                continue
            for a, b in zip(results[i],
                            jax.tree_util.tree_leaves(refs[i])):
                got = onp.asarray(a, dtype=onp.asarray(b).dtype)
                if not (got == onp.asarray(b)[0]).all():
                    ok = False
        return ok

    return router, volley, parity_of, total


def trace_overhead(args):
    """Tracing overhead gate (docs/observability.md): the router path
    volleyed three times — tracing OFF, head-sampled at 1.0, OFF
    again.  The off/off spread is the measurement noise band; the
    sampled run reports the full-tracing cost and must stay bitwise
    equal to the unbatched baseline.  The off-path per-call cost of
    the tracing hooks (one branch + one contextvar read) is measured
    directly — THAT is the "within noise of the pre-PR baseline"
    contract made checkable: with sampling off the only new code on
    the hot path is the measured hook."""
    from incubator_mxnet_tpu import trace

    router, volley, parity_of, total = _overhead_rig(
        args, "serving_trace_model", seed=5)
    failures = []
    try:
        volley()                       # warm the route path off-clock
        trace.configure(sample=0.0)
        off1, _res, err1 = volley()
        trace.configure(sample=1.0, ring=args.requests * 16)
        on_rps, on_results, err2 = volley()
        sampled_spans = trace.stats()["spans_recorded"]
        trace.configure(sample=0.0)
        off2, _res, err3 = volley()
        if err1 or err2 or err3:
            failures.append(f"failed requests: "
                            f"{(err1 + err2 + err3)[:1]}")
        parity = parity_of(on_results)
    finally:
        trace.reset()
        router.shutdown()

    # the off-path hook cost: what every untraced request pays per
    # instrumentation point (sampling branch / contextvar read)
    n = 200_000
    t0 = time.monotonic()
    for _ in range(n):
        trace.start_trace("x")
        trace.current_span()
    offpath_ns = (time.monotonic() - t0) / n * 1e9 / 2

    off_best = max(off1, off2)
    rec = {
        "metric": "serving_trace_overhead",
        "value": round(off_best, 2),
        "unit": "req/s",
        "trace_off_rps": round(off_best, 2),
        "trace_off_noise_pct": round(
            abs(off1 - off2) / off_best * 100.0, 2),
        "trace_sampled_rps": round(on_rps, 2),
        "sampled_overhead_pct": round(
            (1.0 - on_rps / off_best) * 100.0, 2),
        "sampled_spans": sampled_spans,
        "offpath_ns_per_hook": round(offpath_ns, 1),
        "bitwise_equal_with_tracing": bool(parity),
        "requests_per_volley": total,
        "platform": _platform(),
    }
    if args.check:
        if not parity:
            failures.append("outputs with tracing on != unbatched "
                            "baseline")
        if sampled_spans <= 0:
            failures.append("sampled volley recorded no spans")
        # one branch + one contextvar read must stay sub-microsecond:
        # at that cost even a 10k-rps router spends < 0.1% in hooks —
        # the "tracing OFF within 1% of pre-PR" contract, measured at
        # the only place new cost exists
        if offpath_ns > 2000:
            failures.append(
                f"off-path hook cost {offpath_ns:.0f}ns > 2µs")
        if rec["sampled_overhead_pct"] > 25.0:
            failures.append(
                f"sampled-at-1.0 overhead "
                f"{rec['sampled_overhead_pct']}% > 25%")
    return rec, failures


def flight_overhead(args):
    """Flight-recorder overhead gate (docs/observability.md "Flight
    recorder"): the router path volleyed ring-off / ring-on (the
    always-on default) / ring-off.  The off/off spread is the noise
    band; ring-on must sit inside it — a HEALTHY request appends
    nothing to the ring, so the only per-request cost is the emitters'
    enabled checks.  The emit cost itself (what a quarantine or
    failover pays) is microbenched directly and gated < 2 µs."""
    from incubator_mxnet_tpu import flightrec

    router, volley, parity_of, total = _overhead_rig(
        args, "serving_flight_model", seed=9)
    failures = []
    try:
        volley()                       # warm the route path off-clock
        flightrec.configure(ring=0)
        off1, _res, err1 = volley()
        flightrec.configure(ring=4096)
        on_rps, on_results, err2 = volley()
        on_events = flightrec.stats()["events_recorded"]
        flightrec.configure(ring=0)
        off2, _res, err3 = volley()
        if err1 or err2 or err3:
            failures.append(f"failed requests: "
                            f"{(err1 + err2 + err3)[:1]}")
        parity = parity_of(on_results)
        # the emit cost: what one operationally-interesting event (a
        # quarantine, a failover, a scale decision) pays to land in
        # the ring — the ONLY hot-path-adjacent cost of the recorder
        flightrec.configure(ring=4096)
        n = 200_000
        t0 = time.monotonic()
        for k in range(n):
            flightrec.record("health", "bench.emit", i=k)
        emit_ns = (time.monotonic() - t0) / n * 1e9
        # and the disabled-path cost (ring=0): one cached int compare
        flightrec.configure(ring=0)
        t0 = time.monotonic()
        for k in range(n):
            flightrec.record("health", "bench.emit", i=k)
        disabled_ns = (time.monotonic() - t0) / n * 1e9
    finally:
        flightrec.reset()
        router.shutdown()

    off_best = max(off1, off2)
    rec = {
        "metric": "serving_flight_overhead",
        "value": round(off_best, 2),
        "unit": "req/s",
        "flight_off_rps": round(off_best, 2),
        "flight_off_noise_pct": round(
            abs(off1 - off2) / off_best * 100.0, 2),
        "flight_on_rps": round(on_rps, 2),
        "flight_on_overhead_pct": round(
            (1.0 - on_rps / off_best) * 100.0, 2),
        "flight_on_events": on_events,
        "emit_ns_per_event": round(emit_ns, 1),
        "disabled_ns_per_call": round(disabled_ns, 1),
        "bitwise_equal_with_flight": bool(parity),
        "requests_per_volley": total,
        "platform": _platform(),
    }
    if args.check:
        if not parity:
            failures.append("outputs with flight recording on != "
                            "unbatched baseline")
        if emit_ns > 2000:
            failures.append(
                f"emitter cost {emit_ns:.0f}ns > 2µs")
        # a healthy volley appends nothing: ring-on must be flat
        # within the measurement noise (generous floor — CPU CI boxes
        # jitter more than the recorder costs)
        band = max(3.0 * rec["flight_off_noise_pct"], 10.0)
        if rec["flight_on_overhead_pct"] > band:
            failures.append(
                f"flight-on overhead {rec['flight_on_overhead_pct']}% "
                f"outside the noise band ({band:.1f}%)")
    return rec, failures


def routerha_overhead(args):
    """Router-HA overhead gate (docs/serving.md "Router high
    availability"): the router path volleyed HA-off / HA-on (leased
    member of a two-wide membership, beat thread running against a
    file store) / HA-off.  The off/off spread is the noise band;
    HA-on must sit inside it — the stateless route path never touches
    the lease store, so the only candidate costs are the background
    beat thread and the attach itself.  The per-session-request cost
    (``owner_of``: registry scan + consistent-hash ring lookup) is
    microbenched directly and gated < 50 µs."""
    import shutil
    from incubator_mxnet_tpu.serving.routerha import (FileLeaseStore,
                                                      RouterHA)

    router, volley, parity_of, total = _overhead_rig(
        args, "serving_routerha_model", seed=11)
    store_dir = os.path.join(args.workdir, "serving_routerha_store")
    shutil.rmtree(store_dir, ignore_errors=True)
    failures = []
    ha = None
    try:
        volley()                       # warm the route path off-clock
        off1, _res, err1 = volley()
        store = FileLeaseStore(store_dir)
        # a fake second member makes the membership two-wide so every
        # sweep and every ownership lookup does real multi-router
        # work; its registry carries the microbench sids so owner_of
        # below exercises the common (registry-hit) path
        store.publish({"router_id": "bench-peer", "addr": None,
                       "deadline": time.monotonic() + 3600.0,
                       "ttl_s": 3600.0, "epoch": 1,
                       "sessions": {f"bench-sid-{k}": "bench"
                                    for k in range(256)},
                       "fleet": None})
        ha = RouterHA("bench-r1", store, lease_ttl_s=1.0,
                      addr="127.0.0.1:0")
        ha.attach(router)
        ha.start()
        on_rps, on_results, err2 = volley()
        on_beats = ha.describe()["counters"]["beats"]
        ha.stop(leave=True)
        router.ha = None
        router.fleet.membership = None
        ha = None
        off2, _res, err3 = volley()
        if err1 or err2 or err3:
            failures.append(f"failed requests: "
                            f"{(err1 + err2 + err3)[:1]}")
        parity = parity_of(on_results)
        # the per-session-request cost: one owner_of lookup — the
        # common path hits a peer's published registry (dict lookups
        # only); the miss path additionally builds the consistent-hash
        # ring (64 sha1 vnodes per member), paid only by unknown or
        # orphaned sids
        ha2 = RouterHA("bench-r1", store, lease_ttl_s=60.0,
                       addr="127.0.0.1:0").attach(router)
        ha2.beat_once()
        n = 20_000
        t0 = time.monotonic()
        for k in range(n):
            ha2.owner_of(f"bench-sid-{k % 256}")
        owner_ns = (time.monotonic() - t0) / n * 1e9
        n_miss = 2_000
        t0 = time.monotonic()
        for k in range(n_miss):
            ha2.owner_of(f"orphan-sid-{k % 256}")
        owner_miss_ns = (time.monotonic() - t0) / n_miss * 1e9
        ha2.stop(leave=True)
        router.ha = None
        router.fleet.membership = None
    finally:
        if ha is not None:
            ha.stop(leave=True)
        router.ha = None
        if getattr(router, "fleet", None) is not None:
            router.fleet.membership = None
        router.shutdown()
        shutil.rmtree(store_dir, ignore_errors=True)

    off_best = max(off1, off2)
    rec = {
        "metric": "serving_routerha_overhead",
        "value": round(off_best, 2),
        "unit": "req/s",
        "routerha_off_rps": round(off_best, 2),
        "routerha_off_noise_pct": round(
            abs(off1 - off2) / off_best * 100.0, 2),
        "routerha_on_rps": round(on_rps, 2),
        "routerha_on_overhead_pct": round(
            (1.0 - on_rps / off_best) * 100.0, 2),
        "routerha_on_beats": on_beats,
        "owner_lookup_ns": round(owner_ns, 1),
        "owner_lookup_miss_ns": round(owner_miss_ns, 1),
        "bitwise_equal_with_ha": bool(parity),
        "requests_per_volley": total,
        "platform": _platform(),
    }
    if args.check:
        if not parity:
            failures.append("outputs with router HA on != unbatched "
                            "baseline")
        if on_beats <= 0:
            failures.append("HA-on volley recorded no lease beats")
        # the common (registry-hit) lookup is dict reads only; 50µs
        # is a generous ceiling even on loaded CI boxes.  The miss
        # path builds the ring — gate it at 2ms so a vnode blowup or
        # an accidental store read on the request path still fails.
        if owner_ns > 50_000:
            failures.append(
                f"owner_of lookup {owner_ns:.0f}ns > 50µs")
        if owner_miss_ns > 2_000_000:
            failures.append(
                f"owner_of ring-miss lookup {owner_miss_ns:.0f}ns "
                f"> 2ms")
        # the route path never touches the store: HA-on must be flat
        # within the measurement noise (same generous floor as the
        # trace/flight gates — CPU CI boxes jitter)
        band = max(3.0 * rec["routerha_off_noise_pct"], 10.0)
        if rec["routerha_on_overhead_pct"] > band:
            failures.append(
                f"router-HA overhead {rec['routerha_on_overhead_pct']}%"
                f" outside the noise band ({band:.1f}%)")
    return rec, failures


def smoke(args):
    """CI serving stage: ephemeral HTTP server end-to-end."""
    prefix = os.path.join(args.workdir, "serving_smoke_model")
    if args.model_zoo:
        _zoo_artifact(prefix, args.model_zoo)
    else:
        _toy_artifact(prefix)
    # recompile sentinel (docs/graph_analysis.md): observe the
    # predictor sites through warmup + traffic — the signature count
    # must be FLAT after warmup (the serving bucketing contract)
    from incubator_mxnet_tpu.analysis import recompile as _rc
    _prev_sentinel = _rc.set_mode("warn")

    def _predictor_compiles():
        return sum(s["compiles"]
                   for name, s in _rc.stats()["per_site"].items()
                   if name.startswith("predictor:"))

    try:
        return _smoke_instrumented(args, prefix, _predictor_compiles)
    finally:
        # a failed scrape/request must not leak warn-mode into later
        # benchmarks in this process (it would instrument new jit
        # sites and skew the numbers this suite measures)
        _rc.set_mode(_prev_sentinel)


def _smoke_instrumented(args, prefix, _predictor_compiles):
    import urllib.request
    from incubator_mxnet_tpu import deploy
    from incubator_mxnet_tpu.serving import InferenceServer

    pred = deploy.load_predictor(prefix)
    n = min(args.requests, 16)
    instances = _instances(pred.meta, n, seed=2)
    refs = [pred(*[x[None] for x in inst]) for inst in instances]

    srv = InferenceServer()
    srv.repository.load("smoke", prefix)
    port = srv.start()

    def scrape_compiles():
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30).read()
        for line in raw.decode().splitlines():
            if line.startswith('mxnet_serving_compile_total'
                               '{model="smoke"}'):
                return int(float(line.rsplit(" ", 1)[1]))
        raise AssertionError("compile_total not in /metrics")

    compiles_warm = scrape_compiles()
    sentinel_warm = _predictor_compiles()
    codes, results = [None] * n, [None] * n

    def call(i):
        body = json.dumps(
            {"inputs": [x.tolist() for x in instances[i]]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/models/smoke:predict",
            data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            codes[i] = resp.status
            results[i] = json.loads(resp.read())["outputs"]

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    compiles_after = scrape_compiles()
    sentinel_after = _predictor_compiles()
    health = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=30).read())
    srv.shutdown()

    import jax
    ok_bitwise, ok_close = True, True
    for i in range(n):
        for out_leaf, ref_leaf in zip(
                results[i], jax.tree_util.tree_leaves(refs[i])):
            ref = onp.asarray(ref_leaf)[0]
            got = onp.asarray(out_leaf, dtype=ref.dtype)
            ok_bitwise &= bool((got == ref).all())
            ok_close &= bool(onp.allclose(got, ref, rtol=1e-5,
                                          atol=1e-6))
    rec = {
        "metric": "serving_http_smoke",
        "value": float(sum(c == 200 for c in codes)),
        "unit": "ok_responses",
        "requests": n,
        "compile_total": compiles_after,
        "compile_stable": compiles_after == compiles_warm,
        "sentinel_compiles": sentinel_after,
        "sentinel_flat": sentinel_after == sentinel_warm,
        "bitwise_equal_unbatched": bool(ok_bitwise),
        "allclose_unbatched": bool(ok_close),
        "health": health["status"],
        "platform": _platform(),
    }
    failures = []
    if any(c != 200 for c in codes):
        failures.append(f"non-200 responses: {codes}")
    if not rec["compile_stable"]:
        failures.append(
            f"compile count moved {compiles_warm}->{compiles_after}")
    if not rec["sentinel_flat"]:
        failures.append(
            f"recompile sentinel saw predictor compiles after warmup "
            f"({sentinel_warm}->{sentinel_after})")
    # conv models (the zoo path) reassociate across batch sizes at ULP
    # level, so the wire gate is allclose; the MLP path must stay
    # bitwise (tests/test_serving.py holds the strict contract)
    if not ok_close:
        failures.append("HTTP outputs diverged from unbatched baseline")
    if not args.model_zoo and not ok_bitwise:
        failures.append("toy-MLP outputs not bitwise equal unbatched")
    if health["status"] != "ok":
        failures.append(f"healthz: {health}")
    return rec, failures


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=64,
                   help="concurrent clients (batched volley width)")
    p.add_argument("--rounds", type=int, default=4,
                   help="request waves per client per volley")
    p.add_argument("--clients", type=int, default=8,
                   help="client threads multiplexing the in-flight "
                        "requests (async submit)")
    p.add_argument("--trials", type=int, default=3,
                   help="volleys; best throughput reported")
    p.add_argument("--output", default=None)
    p.add_argument("--check", action="store_true",
                   help="enforce the 3x + compile-stable + bitwise floor")
    p.add_argument("--smoke", action="store_true",
                   help="HTTP end-to-end smoke (CI serving stage)")
    p.add_argument("--model-zoo", default=None, metavar="MODEL",
                   help="bench a model_zoo artifact (e.g. resnet18_v1)")
    p.add_argument("--replicas", type=int, default=0, metavar="N",
                   help="fleet scaling mode: volley through the "
                        "FleetRouter over 1..N replicas")
    p.add_argument("--trace-check", action="store_true",
                   help="tracing overhead gate: off/sampled/off "
                        "router volleys + off-path hook microbench "
                        "(docs/observability.md)")
    p.add_argument("--flight-check", action="store_true",
                   help="flight-recorder overhead gate: ring-off/"
                        "ring-on/ring-off router volleys + emitter "
                        "microbench (docs/observability.md)")
    p.add_argument("--routerha-check", action="store_true",
                   help="router-HA overhead gate: off/leased-member/"
                        "off router volleys + owner_of microbench "
                        "(docs/serving.md)")
    p.add_argument("--backend", choices=("thread", "process"),
                   default="process",
                   help="replica backend for --replicas mode")
    p.add_argument("--workdir", default="/tmp")
    args = p.parse_args(argv)

    failures = []
    if args.trace_check:
        rec, failures = trace_overhead(args)
    elif args.flight_check:
        rec, failures = flight_overhead(args)
    elif args.routerha_check:
        rec, failures = routerha_overhead(args)
    elif args.replicas:
        rec, failures = fleet_bench(args)
    elif args.smoke:
        rec, failures = smoke(args)
    else:
        rec = bench(args)
        if args.check:
            if rec["speedup_vs_unbatched"] < 3.0:
                failures.append(
                    f"speedup {rec['speedup_vs_unbatched']}x < 3x floor")
            if not rec["compile_stable"]:
                failures.append("compile count grew after warmup")
            if not rec["bitwise_equal_unbatched"]:
                failures.append("batched outputs != unbatched outputs")
    # reproduction keys (loadgen discipline): which volley, which
    # instance seed, and whatever chaos spec the environment carried
    if args.trace_check:
        wl, seed = "volley:overhead=trace", 5
    elif args.flight_check:
        wl, seed = "volley:overhead=flight", 9
    elif args.routerha_check:
        wl, seed = "volley:overhead=routerha", 11
    elif args.replicas:
        wl, seed = (f"volley:fleet,requests={args.requests},"
                    f"rounds={args.rounds}"), 3
    elif args.smoke:
        wl, seed = "volley:smoke", 2
    else:
        wl, seed = (f"volley:batched,requests={args.requests},"
                    f"rounds={args.rounds}"), 1
    rec.update(provenance(wl, seed))
    line = json.dumps(rec)
    print(line, flush=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")
    if failures:
        print(f"[serving_bench] FAIL: {failures}", file=sys.stderr,
              flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
