#!/usr/bin/env python
"""Local CI pipeline — the reference's ci/ (Jenkinsfile stages +
runtime_functions.sh) recast as one dependency-free driver.

Stages (each isolated, failures collected, nonzero exit if any fail):
  build      native libs (libmxtpu, capi, predict) + C++ selftest
  sanity     compileall + import smoke
  unit       tier-1 pytest suite (shardable: --shard i/n for parallel hosts)
  slow       the slow-marked tests the tier-1 '-m not slow' sweep excludes
  bulking    opperf op-bulking smoke: bulked vs per-op dispatch outputs
             compared, fails on numeric divergence beyond ULP noise
  memlint    liveness-based HBM analysis (docs/graph_analysis.md): the
             zoo infer+train sweep must report ZERO error-severity
             findings with the train step donating 100% of its
             parameter/optimizer-state buffers, a nonzero
             donated-bytes-reclaimed profiler gauge, and a BENCH-style
             per-model peak-HBM record; the seeded-violation selftest
             (undonated train step under strict mode) must fail its
             subprocess — the stage's negative control
  shardlint  SPMD sharding analysis gate (docs/graph_analysis.md
             "shardlint"): the tests/test_shardlint.py battery (full
             pytest output teed to .ci_shardlint_stage.log), the
             tools/shardlint.py --selftest proving every SL-* rule
             fires plus a seeded over-budget shard, the parallel-stack
             dryrun-mesh sweep at ZERO error findings (--check), and
             a seeded reshard violation failing its own strict-mode
             subprocess — the stage's negative control
  multichip  __graft_entry__.dryrun_multichip on a virtual 8-device mesh
  chaos      kvstore + checkpoint test subset re-run under a fixed
             MXNET_FAULT_SPEC (deterministic transient faults on the
             PS transport, delays on checkpoint writes) so every PR
             exercises the retry/dedup/integrity paths
  elastic    elastic-runtime scenario under its own pinned seeded spec
             (lost heartbeats, lost acks, slow checkpoint reads): a
             worker is killed mid-run, evicted within the heartbeat
             budget, the survivors converge, the worker rejoins and
             bootstraps — final weights must match an uninterrupted
             run; plus the reshard-restore smoke bench (mesh A→B) for
             the recovery-path perf trajectory
  serving    inference-server smoke: export a real model_zoo resnet,
             start the dynamic-batching HTTP server on an ephemeral
             port, warm it, fire concurrent requests, scrape /metrics,
             assert the compile count did not move and responses match
             the unbatched baseline bitwise
  coldstart  cold-start gate: fresh-subprocess process-start→first-
             inference must be >= 3x faster with a warm persistent
             compile cache and with AOT executables in the artifact
             (which must report compile_total == 0 from process
             start); corrupted AOT blob must degrade to recompile;
             then a resnet18 artifact with AOT buckets must load +
             serve in a fresh subprocess without compiling
  fleet      multi-replica serving sweep under a pinned seeded spec
             (lossy routing hops, failed probes, replica-side faults):
             kill-a-replica chaos volley with zero failed client
             requests, probe quarantine/readmit, rolling reload under
             load with capacity never below N-1, subprocess-backend
             SIGKILL end-to-end; plus the --replicas scaling bench
             with its 2-replica >= 1.6x floor (multicore hosts)
  sessions   stateful-session chaos sweep under its own pinned seeded
             spec (decode-step faults, snapshot faults, replica-side
             faults, route delays): continuous-batching bitwise
             parity, SIGKILL-a-replica-mid-stream with sessions
             resuming bitwise from their CRC'd snapshots or failing
             typed (never a hang, never a silent restart); then
             session_bench --check enforces its continuous-vs-
             sequential floor with the compile count flat across
             session join/leave
  autoscale  autoscaling control-plane sweep (docs/serving.md
             "Autoscaling"): the test_autoscale.py battery — placement
             under the HBM budget with LRU eviction, SLO shed order,
             WFQ, scale-from-zero, session-aware shrink — under a
             pinned seeded spec with errors AND delays on
             serving.scale (dropped decisions must be re-derived, a
             laggy control plane must still converge); then
             autoscale_bench --check replays the bursty two-model
             trace gating zero dropped interactive requests,
             scale-from-zero first-request latency < 1.5 s via the
             AOT path, and total replica-seconds strictly below the
             equivalent static fleet's

  flight     always-on flight recorder sweep (docs/observability.md
             "Flight recorder"): tests/test_flightrec.py under a
             pinned seeded spec — ring semantics, crash-dump safety
             (write failures swallowed+counted, never masking the
             typed error), SIGUSR2 wedge dumps, per-subsystem
             emitters, the SIGKILL-a-replica postmortem
             reconstruction gated by tools/postmortem.py --gate —
             with full pytest output teed to .ci_flight_stage.log;
             then serving_bench --flight-check (ring-on vs ring-off
             router volley flat within noise, emitter microbench
             < 2 µs, bitwise parity)

  routerha   highly-available router tier sweep (docs/serving.md
             "Router high availability"): tests/test_routerha.py —
             lease join/renew/expire, consistent-hash ring stability,
             bounded X-MXNET-ROUTER forward hops, crash takeover with
             bitwise session resume, the SIGKILL-a-router-mid-stream
             subprocess end-to-end gated by postmortem --gate — under
             a pinned seeded spec (jittered lease beats and forward
             hops, retried decode-step faults), full pytest output
             teed to .ci_routerha_stage.log; then serving_bench
             --routerha-check (leased-member volley flat within noise
             of HA-off, owner_of microbench, bitwise parity)

  soak       production-shaped soak (docs/capacity.md):
             tests/test_loadgen.py — schedule determinism, the
             heavy-tail sampler's pinned statistics, virtual-time
             incident scheduling, the zero-lost-streams ledger's
             negative controls, the SLO reader on real /metrics text
             — teed to .ci_soak_stage.log; then soak_bench --check:
             a time-compressed flash crowd + mid-crowd replica
             SIGKILL + pre-armed fault burst on a 2-replica
             subprocess fleet, gated on the capacity curve (knee
             identified), per-class SLO conformance, postmortem
             --gate per incident, and zero lost streams (bitwise)

  trace      request-scoped tracing sweep (docs/observability.md):
             tests/test_trace.py under a pinned seeded spec — span
             recorder semantics, header-propagation edge cases, ring
             wraparound, failover/hedge spans with typed outcomes,
             the subprocess end-to-end merged-timeline coverage gate
             — with full pytest output teed to .ci_trace_stage.log;
             then serving_bench --trace-check (tracing-off hook cost,
             sampled-at-1.0 overhead, bitwise parity with tracing on)

  lint       mxlint (docs/static_analysis.md) over the python surface:
             framework-invariant rules (env-var/docs sync, fault-point
             registry, flight-event vocabulary, monotonic clocks,
             bulkable purity, lock order, typed-error propagation);
             fails on any finding not in the (normally empty)
             ci/mxlint_baseline.json
  locklint   whole-program lock-discipline gate (tools/locklint.py):
             zero findings over the named-lock registry (cross-module
             order cycles, blocking calls under a held lock,
             half-guarded attributes), --selftest proving every rule +
             the runtime witness fire, and a seeded violation failing
             its own subprocess as the negative control; the fleet and
             sessions chaos stages additionally run their whole pytest
             battery under MXNET_LOCK_WITNESS=1 gating zero observed
             lock-order violations
  race       engine + bulking test subset re-run under
             MXNET_ENGINE_RACE_CHECK=1 so every op's actual NDArray
             accesses are checked against its declared read/write sets
             (an undeclared access raises EngineRaceError mid-test)
  graphlint  IR-level lint of traced graphs (docs/graph_analysis.md):
             jaxpr passes over a real model-zoo net (infer + train)
             and the curated central-op sweep must report ZERO
             findings (f64 leaks, mixed-precision promotion, bf16
             accumulation, baked constants, dead compute, host
             callbacks, degenerate tile layouts); plus a recompile-
             sentinel smoke — a bucketed-shape replay stays inside its
             per-site XLA compile budget with the sentinel raising

Usage:
  python ci/run_ci.py                  # everything
  python ci/run_ci.py --stages unit --shard 1/4
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sh(cmd, timeout=1800, env=None):
    e = dict(os.environ)
    e.setdefault("JAX_PLATFORMS", "cpu")
    e.update(env or {})
    proc = subprocess.run(cmd, cwd=REPO, env=e, capture_output=True,
                          text=True, timeout=timeout)
    return proc


def stage_build(args):
    for target in ("all", "capi", "predict", "selftest"):
        proc = sh(["make", "-C", "src", target], timeout=600)
        if proc.returncode != 0:
            return False, f"make {target}: {proc.stderr[-400:]}"
    proc = sh([os.path.join(REPO, "tools", "bin", "mxt_selftest")],
              timeout=300)
    if proc.returncode != 0:
        return False, f"native selftest: {proc.stdout[-400:]}"
    return True, "native libs + C++ selftest"


def stage_sanity(args):
    proc = sh([sys.executable, "-m", "compileall", "-q",
               "incubator_mxnet_tpu", "tools", "scripts", "benchmark"],
              timeout=300)
    if proc.returncode != 0:
        return False, proc.stderr[-400:]
    # import smoke on the host CPU (CI never takes a chip)
    code = ("import jax; jax.config.update('jax_platforms','cpu'); "
            "import incubator_mxnet_tpu as mx; "
            "assert mx.nd.ones((2,2)).sum().asscalar() == 4.0")
    proc = sh([sys.executable, "-c", code], timeout=300)
    if proc.returncode != 0:
        return False, f"import smoke: {proc.stderr[-400:]}"
    return True, "compileall + import smoke"


def stage_unit(args):
    # mirror the tier-1 verify command (ROADMAP.md): skip slow-marked
    # tests, survive collection errors, no state-carrying plugins
    cmd = [sys.executable, "-m", "pytest", "tests/", "-q",
           "-m", "not slow", "--continue-on-collection-errors",
           "-p", "no:cacheprovider", "--durations=10"]
    if args.shard:
        i, n = (int(v) for v in args.shard.split("/"))
        if not 1 <= i <= n:
            return False, f"bad shard {args.shard}: want 1<=i<=n"
        # stable sharding without plugins: split by test file
        import glob
        files = sorted(glob.glob(os.path.join(REPO, "tests", "test_*.py")))
        mine = [f for k, f in enumerate(files) if k % n == i - 1]
        if not mine:
            return True, "empty shard (more shards than test files)"
        cmd = [sys.executable, "-m", "pytest", "-q", *mine]
    proc = sh(cmd, timeout=3600)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    return proc.returncode == 0, tail


def stage_slow(args):
    """Slow-marked tests: the unit stage mirrors the tier-1 command
    ('-m not slow'), so this stage keeps the excluded tests covered."""
    proc = sh([sys.executable, "-m", "pytest", "tests/", "-q", "-m", "slow",
               "--continue-on-collection-errors", "-p", "no:cacheprovider"],
              timeout=1800)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode == 5:  # nothing collected / all deselected
        return True, "no slow-marked tests"
    return proc.returncode == 0, tail


def stage_bulking(args):
    """Op-bulking smoke: the tier-1 unit stage runs first (stage order),
    then the fast-mode opperf chain compares bulked vs per-op dispatch
    and fails on numeric divergence beyond FMA-contraction ULP noise."""
    out = os.path.join(REPO, ".ci_bulk_smoke.json")
    try:
        proc = sh([sys.executable, "benchmark/opperf.py", "--bulk-chain",
                   "--steps", "5", "--warmup", "1", "--check",
                   "--output", out], timeout=600)
        if proc.returncode != 0:
            return False, (proc.stderr or proc.stdout).strip()[-300:]
        with open(out) as f:
            res = json.load(f)["bulk_chain"]
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"{res['bulked_launches_per_run']} launches for "
                  f"{res['chain_len']} ops, "
                  f"{res['ops_per_segment_mean']} ops/segment, "
                  f"max {res['max_ulp_diff']:.1f} ulp")


# Fixed chaos spec (docs/fault_tolerance.md): seeded so every run
# replays the same fault schedule — a chaos failure bisects like any
# other deterministic test failure.  The serving points ride along
# (seeded errors on batch execution, delays on enqueue) with a retry
# budget deep enough that p=0.05 per-attempt faults cannot exhaust it
# on a sustained volley (0.05**6 per batch).
CHAOS_SPEC = ("kvstore.send:error:p=0.05:seed=7,"
              "kvstore.recv:error:p=0.05:seed=11,"
              "checkpoint.write:delay:ms=20,"
              "serving.enqueue:delay:ms=1,"
              "serving.execute:error:p=0.05:seed=13")


def stage_chaos(args):
    """Fault-tolerance sweep: the kvstore + checkpoint + serving subset
    must pass with deterministic transient faults injected on the PS
    transport, checkpoint writes, and the serving enqueue/execute path
    (client retries + push dedup + CRC + batcher-retry paths)."""
    # yarn/sge shim tests exercise scheduler CLIs, not fault paths
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_fault.py", "tests/test_distributed.py",
               "tests/test_checkpoint.py", "tests/test_serving.py",
               "-m", "not slow", "-k", "not yarn and not sge",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_FAULT_SPEC": CHAOS_SPEC,
                                 "MXNET_SERVING_RETRIES": "6"})
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    return proc.returncode == 0, f"spec={CHAOS_SPEC!r}: {tail}"


# Pinned elastic-chaos spec: lost membership beats, lost acks on the PS
# transport, slow checkpoint-shard reads.  Seeded like CHAOS_SPEC so an
# elastic failure replays deterministically from the spec string.
ELASTIC_SPEC = ("kvstore.heartbeat:error:p=0.2:seed=5,"
                "kvstore.recv:error:p=0.05:seed=11,"
                "checkpoint.read:delay:ms=5")


def stage_elastic(args):
    """Elastic runtime sweep (docs/fault_tolerance.md "Elasticity"):
    the kill/evict/rejoin scenario + resharding tests must pass under
    the pinned seeded spec, and the reshard-restore bench must emit a
    well-formed BENCH record with every restore verified."""
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_elastic.py",
               "-m", "not slow", "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_FAULT_SPEC": ELASTIC_SPEC})
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, f"spec={ELASTIC_SPEC!r}: {tail}"
    out = os.path.join(REPO, ".ci_reshard_smoke.json")
    try:
        proc2 = sh([sys.executable, "benchmark/reshard_bench.py",
                    "--smoke", "--output", out], timeout=600)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-300:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    if not rec.get("verified") or rec.get("value", 0) <= 0:
        return False, f"reshard bench record malformed: {rec}"
    return True, (f"spec ok: {tail}; reshard {rec['metric']}="
                  f"{rec['value']}ms over {rec['restore_ms_by_shape']}")


# Pinned fleet-chaos spec: slow/lossy routing hops, failed health
# probes, replica-side execution faults, jittered device execution —
# the router's failover/hedging/probing paths all under fire, seeded
# so a fleet failure replays from the spec string.
FLEET_SPEC = ("serving.route:delay:ms=1:p=0.25:seed=3,"
              "serving.probe:error:p=0.1:seed=5,"
              "serving.replica_exec:error:p=0.05:seed=17,"
              "serving.execute:delay:ms=2:p=0.2:seed=19")


def stage_fleet(args):
    """Fleet sweep (docs/serving.md "fleet"): the whole test_fleet.py
    battery — kill-a-replica chaos volley, probe quarantine, rolling-
    reload-under-load, draining-fleet 503s, plus the process-backend
    (subprocess SIGKILL) end-to-end — under the pinned seeded spec;
    then the multi-replica scaling bench with its CI-checked floor
    (2 replicas >= 1.6x one replica where the host has the cores to
    express it).  Runs under MXNET_LOCK_WITNESS=1: every named-lock
    order the chaos interleavings draw is witnessed, and any observed
    cycle fails its test at teardown (tests/conftest.py gate)."""
    log = os.path.join(REPO, ".ci_fleet_stage.log")
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_fleet.py",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_FAULT_SPEC": FLEET_SPEC,
                                 "MXNET_LOCK_WITNESS": "1"})
    with open(log, "w") as f:
        f.write(proc.stdout or "")
        if proc.stderr:
            f.write("\n--- stderr ---\n" + proc.stderr)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, (f"spec={FLEET_SPEC!r} witness=1: {tail} "
                       f"(full output: {log})")
    out = os.path.join(REPO, ".ci_fleet_bench.json")
    try:
        proc2 = sh([sys.executable, "benchmark/serving_bench.py",
                    "--replicas", "2", "--check", "--requests", "32",
                    "--rounds", "2", "--output", out], timeout=1200)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-300:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"spec ok: {tail}; scaling 2x={rec['scaling_2x']} "
                  f"(floor {'checked' if rec['floor_checked'] else 'advisory: ' + rec['floor_skip_reason']}), "
                  f"errors={rec['failed_requests']}")


# Pinned session-chaos spec: transient faults on the decode step
# (retried inside the continuous batcher), failed snapshot writes
# (counted, never fatal — migrations re-base on whatever landed),
# replica-side faults (absorbed by the router's owner-retry), and
# jittered routing.  Seeded like the other specs so a failure replays
# from the spec string alone.
SESSIONS_SPEC = ("serving.session_step:error:p=0.05:seed=23,"
                 "serving.session_snapshot:error:p=0.1:seed=29,"
                 "serving.replica_exec:error:p=0.05:seed=17,"
                 "serving.route:delay:ms=1:p=0.25:seed=3")


def stage_sessions(args):
    """Stateful-session sweep (docs/serving.md "Sessions"): the whole
    session battery — continuous-batching parity, TTL/cap eviction,
    snapshot/restore bitwise continuation, subprocess SIGKILL
    mid-stream with migration-or-typed-loss — under the pinned seeded
    spec; then the continuous-batching bench with its floor and the
    compile-flatline gate.  Runs under MXNET_LOCK_WITNESS=1: any
    lock-order cycle a chaos interleaving draws fails its test at
    teardown (tests/conftest.py gate)."""
    log = os.path.join(REPO, ".ci_sessions_stage.log")
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_sessions.py", "tests/test_session_fleet.py",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_FAULT_SPEC": SESSIONS_SPEC,
                                 "MXNET_SERVING_RETRIES": "6",
                                 "MXNET_LOCK_WITNESS": "1"})
    with open(log, "w") as f:
        f.write(proc.stdout or "")
        if proc.stderr:
            f.write("\n--- stderr ---\n" + proc.stderr)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, (f"spec={SESSIONS_SPEC!r} witness=1: {tail} "
                       f"(full output: {log})")
    out = os.path.join(REPO, ".ci_session_bench.json")
    try:
        proc2 = sh([sys.executable, "benchmark/session_bench.py",
                    "--check", "--output", out], timeout=900)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-300:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"spec ok: {tail}; continuous {rec['value']}x "
                  f"(floor {rec['floor']}), parity="
                  f"{rec['parity_bitwise']}, compiles flat at "
                  f"{rec['compile_total']}, crash smoke "
                  f"{rec['crash_smoke_bitwise']}")


# Pinned autoscale-chaos spec: the control plane's own fault point
# takes errors (decisions dropped for a tick — the loop must re-derive
# them) while routing hops are jittered; seeded so a scale-decision
# failure replays from the spec string.  serving.scale gets the error
# kind and the route point the delay kind (one kind per point in the
# spec grammar); the delay side of serving.scale is covered by
# test_autoscale's own delay-spec test.
AUTOSCALE_SPEC = ("serving.scale:error:p=0.15:seed=31,"
                  "serving.route:delay:ms=1:p=0.2:seed=3")


def stage_autoscale(args):
    """Autoscaling sweep (docs/serving.md "Autoscaling"): the whole
    test_autoscale.py battery under the pinned seeded spec, then the
    bursty two-model trace bench with its hard gates (zero dropped
    interactive requests, scale-from-zero < 1.5 s, replica-seconds
    strictly below static, compile flatline)."""
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_autoscale.py",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_FAULT_SPEC": AUTOSCALE_SPEC})
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, f"spec={AUTOSCALE_SPEC!r}: {tail}"
    out = os.path.join(REPO, ".ci_autoscale_bench.json")
    try:
        proc2 = sh([sys.executable, "benchmark/autoscale_bench.py",
                    "--check", "--output", out], timeout=900)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-400:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"spec ok: {tail}; replica-seconds "
                  f"{rec['replica_seconds']} vs static "
                  f"{rec['static_replica_seconds']} "
                  f"(peak {rec['peak_replicas']}), hi p99 "
                  f"{rec['hi_p99_ms']}ms, dropped {rec['hi_dropped']}, "
                  f"scale-from-zero {rec['scale_from_zero_ms']}ms, "
                  f"compiles {rec['compile_total']}")


# Pinned flight-chaos spec: jittered routing hops, lost probes and
# dropped scale decisions — the control-plane paths whose events the
# flight assertions pin must hold WITH chaos landing in the same ring.
# Seeded like every other spec so a failure replays from the string.
FLIGHT_SPEC = ("serving.route:delay:ms=1:p=0.2:seed=3,"
               "serving.probe:error:p=0.1:seed=5,"
               "serving.scale:error:p=0.1:seed=31")


def stage_flight(args):
    """Flight-recorder sweep (docs/observability.md "Flight
    recorder"): the whole test_flightrec.py battery — ring/eviction
    semantics, dump-safety (never masks the typed error), SIGUSR2
    re-entrancy, emitter coverage across the subsystems, postmortem
    merge/narrow/report/gate, and the SIGKILL-and-reconstruct
    end-to-end — under the pinned seeded spec with FULL pytest output
    teed to a log (no lastfailed cache in stages); then the
    serving_bench overhead gate (ring-on within noise of ring-off,
    emitter < 2 µs, bitwise parity)."""
    log = os.path.join(REPO, ".ci_flight_stage.log")
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_flightrec.py",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_FAULT_SPEC": FLIGHT_SPEC,
                                 "MXNET_SERVING_RETRIES": "6"})
    with open(log, "w") as f:
        f.write(proc.stdout or "")
        if proc.stderr:
            f.write("\n--- stderr ---\n" + proc.stderr)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, (f"spec={FLIGHT_SPEC!r}: {tail} "
                       f"(full output: {log})")
    out = os.path.join(REPO, ".ci_flight_bench.json")
    try:
        proc2 = sh([sys.executable, "benchmark/serving_bench.py",
                    "--flight-check", "--check", "--requests", "32",
                    "--rounds", "2", "--output", out], timeout=900)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-400:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"spec ok: {tail}; off {rec['flight_off_rps']} rps "
                  f"(noise {rec['flight_off_noise_pct']}%), on "
                  f"{rec['flight_on_rps']} rps "
                  f"({rec['flight_on_overhead_pct']}% overhead), emit "
                  f"{rec['emit_ns_per_event']}ns, parity="
                  f"{rec['bitwise_equal_with_flight']}")


# Pinned router-HA chaos spec: jittered lease beats and forward hops
# (the membership layer must tolerate a laggy store and a slow peer
# without spurious expiry) plus retried decode-step faults (absorbed by
# the router's failover machinery — the HA battery's bitwise
# continuation contracts must hold with replica faults landing).
# Delay-only on the HA points: a lease beat that ERRORS is a scenario
# the battery stages deterministically (typed RouterLeaseError tests);
# injecting it at random would race those pins.  Seeded so a failure
# replays from the spec string alone.
ROUTERHA_SPEC = ("serving.router_lease:delay:ms=2:p=0.2:seed=41,"
                 "serving.router_forward:delay:ms=2:p=0.2:seed=43,"
                 "serving.session_step:error:p=0.05:seed=23")


def stage_routerha(args):
    """Router-HA sweep (docs/serving.md "Router high availability"):
    the whole test_routerha.py battery — forward-header hygiene,
    ring stability, lease store semantics, expire/rejoin obituaries,
    crash takeover with bitwise resume, HTTP forward hop + loop
    bounds, the restore-vs-snapshotter race 20/20, and the
    SIGKILL-a-router-mid-stream subprocess end-to-end (postmortem
    --gate asserts lease.expired → takeover.started →
    session.restored) — under the pinned seeded spec with FULL pytest
    output teed to a log; then the serving_bench overhead gate (a
    leased two-wide member within noise of HA-off, owner_of
    microbench, bitwise parity)."""
    log = os.path.join(REPO, ".ci_routerha_stage.log")
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_routerha.py",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_FAULT_SPEC": ROUTERHA_SPEC,
                                 "MXNET_SERVING_RETRIES": "6"})
    with open(log, "w") as f:
        f.write(proc.stdout or "")
        if proc.stderr:
            f.write("\n--- stderr ---\n" + proc.stderr)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, (f"spec={ROUTERHA_SPEC!r}: {tail} "
                       f"(full output: {log})")
    out = os.path.join(REPO, ".ci_routerha_bench.json")
    try:
        proc2 = sh([sys.executable, "benchmark/serving_bench.py",
                    "--routerha-check", "--check", "--requests", "32",
                    "--rounds", "2", "--output", out], timeout=900)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-400:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"spec ok: {tail}; off {rec['routerha_off_rps']} rps "
                  f"(noise {rec['routerha_off_noise_pct']}%), on "
                  f"{rec['routerha_on_rps']} rps "
                  f"({rec['routerha_on_overhead_pct']}% overhead), "
                  f"owner_of {rec['owner_lookup_ns']}ns, parity="
                  f"{rec['bitwise_equal_with_ha']}")


# Pinned soak chaos spec: a low-probability route fault burst (armed
# in every subprocess, verified post-hoc by its fault.serving.route
# flight events) plus a perturbed incident-scheduler tick — chaos on
# the chaos injector itself.  Seeded so a soak failure replays from
# the spec string alone (the bench also prints its one-line repro).
SOAK_SPEC = ("serving.route:error:p=0.01:seed=3,"
             "loadgen.tick:delay:ms=5:n=3")


def stage_soak(args):
    """Production-shaped soak (docs/capacity.md): the test_loadgen.py
    battery — deterministic schedule compilation, pinned heavy-tail
    sampler statistics, virtual-time incident scheduling, the
    zero-lost-streams ledger's negative controls, the SLO reader on
    real /metrics exposition — teed to a log; then soak_bench
    --check: capacity curve (>=2 replica counts x >=3 offered points,
    knee identified) + a time-compressed flash crowd over a
    2-replica subprocess fleet with a mid-crowd replica SIGKILL and a
    pre-armed fault burst, gated on per-class SLO conformance,
    postmortem --gate per incident, and zero lost streams."""
    log = os.path.join(REPO, ".ci_soak_stage.log")
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_loadgen.py",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"], timeout=600)
    with open(log, "w") as f:
        f.write(proc.stdout or "")
        if proc.stderr:
            f.write("\n--- stderr ---\n" + proc.stderr)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, f"{tail} (full output: {log})"
    out = os.path.join(REPO, ".ci_soak_bench.json")
    try:
        proc2 = sh([sys.executable, "benchmark/soak_bench.py",
                    "--check", "--chaos", SOAK_SPEC,
                    "--output", out], timeout=600)
        with open(log, "a") as f:
            f.write("\n--- soak_bench ---\n")
            f.write(proc2.stdout or "")
            if proc2.stderr:
                f.write("\n--- soak_bench stderr ---\n" + proc2.stderr)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-400:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    soak = rec["soak"]
    inter = soak["slo"].get("interactive", {})
    return True, (f"{tail}; knee "
                  f"{rec['capacity']['knee']['knee_replicas']} "
                  f"replica(s) @ {rec['value']} rps, "
                  f"{soak['sessions']} streams / "
                  f"{soak['lost_streams']} lost, interactive p99 "
                  f"{inter.get('p99_ms')}ms "
                  f"({len(inter.get('violating_minutes', []))} "
                  f"violating min), "
                  f"{len(soak['incidents'])} incidents gated")


# Pinned trace-chaos spec: replica-side faults (absorbed by failover —
# each failed hop must land as a SPAN with a typed outcome and the
# injected fault as a span event) plus jittered device execution.
# Seeded like every other spec so a trace-stage failure replays from
# the spec string alone.
TRACE_SPEC = ("serving.replica_exec:error:p=0.1:seed=17,"
              "serving.execute:delay:ms=1:p=0.2:seed=19")


def stage_trace(args):
    """Request-scoped tracing sweep (docs/observability.md): the whole
    test_trace.py battery — span recorder semantics, header
    propagation edge cases, ring wraparound, router failover/hedge
    spans with typed outcomes, the subprocess-replica end-to-end
    merged-timeline coverage gate — under the pinned seeded spec, with
    FULL pytest output teed to a log (this stage has no lastfailed
    cache; a bare exit code is undebuggable); then the tracing
    overhead gate (tracing off = one measured branch, sampled-at-1.0
    reported, bitwise parity with tracing on)."""
    log = os.path.join(REPO, ".ci_trace_stage.log")
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_trace.py",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_FAULT_SPEC": TRACE_SPEC,
                                 "MXNET_SERVING_RETRIES": "6"})
    with open(log, "w") as f:
        f.write(proc.stdout or "")
        if proc.stderr:
            f.write("\n--- stderr ---\n" + proc.stderr)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, (f"spec={TRACE_SPEC!r}: {tail} "
                       f"(full output: {log})")
    out = os.path.join(REPO, ".ci_trace_bench.json")
    try:
        proc2 = sh([sys.executable, "benchmark/serving_bench.py",
                    "--trace-check", "--check", "--requests", "32",
                    "--rounds", "2", "--output", out], timeout=900)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-400:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"spec ok: {tail}; off {rec['trace_off_rps']} rps "
                  f"(noise {rec['trace_off_noise_pct']}%), sampled "
                  f"{rec['trace_sampled_rps']} rps "
                  f"({rec['sampled_overhead_pct']}% overhead, "
                  f"{rec['sampled_spans']} spans), hook "
                  f"{rec['offpath_ns_per_hook']}ns, parity="
                  f"{rec['bitwise_equal_with_tracing']}")


def stage_serving(args):
    """Serving smoke (docs/serving.md): HTTP end-to-end against a real
    gluon model_zoo artifact — warmup, concurrent requests, /metrics
    scrape, compile-count stability, bitwise parity with unbatched."""
    out = os.path.join(REPO, ".ci_serving_smoke.json")
    try:
        proc = sh([sys.executable, "benchmark/serving_bench.py",
                   "--smoke", "--model-zoo", "resnet18_v1",
                   "--requests", "8", "--output", out], timeout=900)
        if proc.returncode != 0:
            return False, (proc.stderr or proc.stdout).strip()[-300:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"{int(rec['value'])}/{rec['requests']} ok, "
                  f"{rec['compile_total']} executables "
                  f"(stable={rec['compile_stable']}), "
                  f"bitwise={rec['bitwise_equal_unbatched']}")


def stage_coldstart(args):
    """Cold-start gate (docs/performance.md "Cold start"): the
    coldstart bench's fresh-subprocess sweep must show the persistent
    compile cache and the AOT artifact layer working — warm and AOT
    process-start→first-inference >= 3x faster than cold, the AOT
    replica reporting compile_total == 0 FROM PROCESS START, and the
    corrupted-blob negative control degrading to recompile (never a
    crash); then a real model_zoo resnet18 artifact with AOT buckets
    must load + serve in a fresh subprocess without compiling."""
    out = os.path.join(REPO, ".ci_coldstart.json")
    try:
        proc = sh([sys.executable, "benchmark/coldstart_bench.py",
                   "--check", "--output", out], timeout=900)
        if proc.returncode != 0:
            return False, (proc.stderr or proc.stdout).strip()[-400:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    try:
        proc2 = sh([sys.executable, "benchmark/coldstart_bench.py",
                    "--check", "--model-zoo", "resnet18_v1",
                    "--buckets", "1,2", "--floor", "1.3",
                    "--aot-tolerance", "2.0", "--output", out],
                   timeout=1500)
        if proc2.returncode != 0:
            return False, ("zoo: "
                           + (proc2.stderr or proc2.stdout).strip()[-400:])
        with open(out) as f:
            zoo = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"toy warm {rec['value']}x / aot {rec['aot_speedup_x']}x "
                  f"vs cold {rec['cold_ms']:.0f}ms, aot compiles "
                  f"{rec['aot_compile_total']}, corrupt-fallback ok; "
                  f"resnet18 aot {zoo['aot_speedup_x']}x "
                  f"({zoo['aot_ms']:.0f}ms vs {zoo['cold_ms']:.0f}ms), "
                  f"compiles {zoo['aot_compile_total']}")


def stage_trainloop(args):
    """Whole-loop compilation sweep (docs/performance.md "Chunked
    training loop"): chunked-vs-sequential parity tests (weights, PRNG
    streams, tail fallback, K=1 degeneration, graphlint/memlint pins
    on the scanned program), then the train-loop bench with its hard
    gates — chunked steps/s >= 1.5x the per-step fused path at small
    batch, exactly one loop compile per bucket, zero compiles
    mid-epoch, final-weight parity."""
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_fuse_loop.py",
               "-m", "not slow", "--continue-on-collection-errors",
               "-p", "no:cacheprovider"], timeout=1200)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, tail
    out = os.path.join(REPO, ".ci_trainloop_bench.json")
    try:
        proc2 = sh([sys.executable, "benchmark/train_loop_bench.py",
                    "--check", "--output", out], timeout=900)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-400:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    return True, (f"{tail}; chunked {rec['value']}x per-step at "
                  f"bs={rec['batch']} K={rec['chunk_steps']}, "
                  f"{rec['loop_compiles_total']} compiles/"
                  f"{rec['buckets_driven']} buckets, "
                  f"mid-epoch {rec['mid_epoch_compiles']}, "
                  f"{'bitwise' if rec['weights_bitwise'] else 'allclose'}"
                  " parity")


def stage_lint(args):
    """Framework-aware static analysis (tools/mxlint.py): exit 0 means
    no findings beyond the baseline — and the baseline stays empty
    unless an entry carries a written justification."""
    proc = sh([sys.executable, "tools/mxlint.py", "incubator_mxnet_tpu",
               "tools", "scripts", "benchmark", "ci"], timeout=300)
    out = (proc.stdout or proc.stderr).strip()
    tail = out.splitlines()[-1] if out else ""
    if proc.returncode != 0:
        return False, out[-600:]
    return True, tail


def stage_locklint(args):
    """Lock-discipline gate (tools/locklint.py, docs/static_analysis.md
    "locklint"): the package must lint clean against the (empty)
    baseline, --selftest must prove every static rule AND the runtime
    witness fire on seeded violations, and a seeded blocking-under-lock
    file must FAIL its own lint subprocess — the negative control that
    keeps a green gate honest."""
    proc = sh([sys.executable, "tools/locklint.py"], timeout=300)
    if proc.returncode != 0:
        return False, (proc.stdout or proc.stderr).strip()[-600:]
    out = proc.stdout.strip()
    tail = out.splitlines()[-1] if out else ""
    proc2 = sh([sys.executable, "tools/locklint.py", "--selftest"],
               timeout=300)
    if proc2.returncode != 0:
        return False, ("selftest: "
                       + (proc2.stdout or proc2.stderr).strip()[-600:])
    import tempfile
    seed = ("import time\n"
            "import threading\n"
            "_lock = threading.Lock()\n"
            "def poll():\n"
            "    with _lock:\n"
            "        time.sleep(1.0)\n")
    with tempfile.TemporaryDirectory(prefix="ci_locklint_") as td:
        bad = os.path.join(td, "seeded.py")
        with open(bad, "w") as f:
            f.write(seed)
        proc3 = sh([sys.executable, "tools/locklint.py", bad], timeout=300)
    if proc3.returncode == 0:
        return False, ("seeded blocking-under-lock violation did NOT "
                       "fail the lint run — enforcement is broken")
    return True, f"{tail}; selftest ok; seeded violation fails"


def stage_race(args):
    """Dependency-engine race check: the engine/bulking/ndarray subset
    must pass with every op's actual accesses verified against its
    declared const/mutable vars (violations raise EngineRaceError)."""
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_bulking.py", "tests/test_ndarray.py",
               "tests/test_native.py",
               # the C++ selftest subprocess never sees the flag; it is
               # load-flaky and covered by the unit stage already
               "-k", "not cpp_selftest",
               "-m", "not slow", "--continue-on-collection-errors",
               "-p", "no:cacheprovider"],
              timeout=1800, env={"MXNET_ENGINE_RACE_CHECK": "1"})
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    return proc.returncode == 0, f"race-check on: {tail}"


def stage_graphlint(args):
    """IR lint over the compiled surface CI can afford (a real zoo net
    both modes + the op sweep + the seeded-violation selftest,
    tools/graphlint.py exit 0 against the empty baseline) and the
    recompile-sentinel bucketed-replay smoke."""
    proc = sh([sys.executable, "tools/graphlint.py", "--zoo", "resnet18_v1",
               "--batch", "4", "--ops-smoke", "--selftest"], timeout=900)
    if proc.returncode != 0:
        # stderr first: a crash traceback must not be hidden behind
        # the selftest's stdout progress lines
        return False, (proc.stderr or proc.stdout).strip()[-600:]
    out = (proc.stdout or proc.stderr).strip()
    tail = out.splitlines()[-1] if out else ""
    code = (
        "import incubator_mxnet_tpu as mx\n"
        "from incubator_mxnet_tpu.analysis import recompile as rc\n"
        "buckets = [1, 2, 4, 8]\n"
        "with rc.sentinel_scope('raise', len(buckets) + 1):\n"
        "    for _ in range(3):\n"
        "        for b in buckets:\n"
        "            mx.nd.ones((b, 8)).sum().asscalar()\n"
        "s = rc.stats()\n"
        "assert s['storming_sites'] == [], s\n"
        "assert s['compiles_total'] <= len(buckets) + 1, s\n"
        "print('sentinel: %d compiles over %d replayed buckets'\n"
        "      % (s['compiles_total'], len(buckets)))\n")
    proc2 = sh([sys.executable, "-c", code], timeout=600)
    if proc2.returncode != 0:
        return False, f"sentinel smoke: {(proc2.stderr or proc2.stdout)[-300:]}"
    return True, f"{tail}; {proc2.stdout.strip()}"


def stage_memlint(args):
    """HBM planner/analyzer gate (tools/memlint.py): seeded violations
    must surface (--selftest), the zoo train step must donate every
    param/opt-state buffer at strict coverage (--check), and the
    undonated negative control must FAIL its subprocess."""
    out = os.path.join(REPO, ".ci_memlint.json")
    try:
        proc = sh([sys.executable, "tools/memlint.py", "--zoo",
                   "resnet18_v1", "--batch", "4", "--selftest",
                   "--check", "--output", out], timeout=900)
        if proc.returncode != 0:
            return False, (proc.stderr or proc.stdout).strip()[-600:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    if rec.get("problems"):
        return False, f"gate problems: {rec['problems']}"
    if not rec.get("profiler_donated_bytes_reclaimed"):
        return False, "donated_bytes_reclaimed gauge is zero"
    # negative control: an undonated train step under strict mode must
    # fail — a green gate that cannot catch the seeded violation is lying
    proc2 = sh([sys.executable, "tools/memlint.py", "--seed-violation"],
               timeout=600)
    if proc2.returncode == 0:
        return False, ("seeded undonated-step violation did NOT fail "
                       "the strict run — enforcement is broken")
    train = rec["models"]["resnet18_v1"]["train"]
    return True, (f"peak {train['peak_hbm_bytes'] // (1 << 20)}MiB, "
                  f"donated {train['donated_bytes_reclaimed'] // (1 << 20)}"
                  f"MiB reclaimed, coverage {train['donation_coverage']}, "
                  "seeded violation fails strict")


def stage_shardlint(args):
    """SPMD sharding gate (tools/shardlint.py, docs/graph_analysis.md
    "shardlint"): the pytest battery (rule fixtures, collective cost
    model, per-module parallel-stack pins, export/placement round
    trip), the CLI --selftest firing every SL-* rule, the dryrun-mesh
    parallel sweep at zero error findings, and the seeded reshard
    violation failing its own strict subprocess."""
    log = os.path.join(REPO, ".ci_shardlint_stage.log")
    proc = sh([sys.executable, "-m", "pytest", "-q",
               "tests/test_shardlint.py",
               "--continue-on-collection-errors",
               "-p", "no:cacheprovider"], timeout=1800)
    with open(log, "w") as f:
        f.write(proc.stdout or "")
        if proc.stderr:
            f.write("\n--- stderr ---\n" + proc.stderr)
    tail = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
    if proc.returncode != 0:
        return False, f"{tail} (full output: {log})"
    out = os.path.join(REPO, ".ci_shardlint.json")
    try:
        proc2 = sh([sys.executable, "tools/shardlint.py", "--selftest",
                    "--check", "--output", out], timeout=900)
        if proc2.returncode != 0:
            return False, (proc2.stderr or proc2.stdout).strip()[-600:]
        with open(out) as f:
            rec = json.load(f)
    finally:
        if os.path.exists(out):
            os.remove(out)
    if rec.get("error_findings"):
        return False, f"sweep error findings: {rec['error_findings']}"
    # negative control: a seeded cross-mesh reshard under strict mode
    # must fail — a green gate that cannot catch it is lying
    proc3 = sh([sys.executable, "tools/shardlint.py",
                "--seed-violation"], timeout=600)
    if proc3.returncode == 0:
        return False, ("seeded reshard violation did NOT fail the "
                       "strict run — enforcement is broken")
    comm = rec.get("value", 0)   # parallel_stack_comm_bytes_per_step
    return True, (f"{tail}; {len(rec.get('surfaces', {}))} surfaces "
                  f"clean, comm {comm}B/step, seeded violation "
                  "fails strict")


def stage_multichip(args):
    code = "import __graft_entry__ as g; g.dryrun_multichip(8)"
    proc = sh([sys.executable, "-c", code], timeout=1200)
    return proc.returncode == 0, (proc.stdout or proc.stderr)[-200:]


STAGES = {"build": stage_build, "sanity": stage_sanity,
          "lint": stage_lint, "locklint": stage_locklint,
          "unit": stage_unit, "slow": stage_slow,
          "bulking": stage_bulking, "chaos": stage_chaos,
          "elastic": stage_elastic,
          "serving": stage_serving, "fleet": stage_fleet,
          "sessions": stage_sessions, "autoscale": stage_autoscale,
          "trace": stage_trace,
          "flight": stage_flight,
          "routerha": stage_routerha,
          "soak": stage_soak,
          "coldstart": stage_coldstart,
          "trainloop": stage_trainloop,
          "race": stage_race,
          "graphlint": stage_graphlint,
          "memlint": stage_memlint,
          "shardlint": stage_shardlint,
          "multichip": stage_multichip}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--stages", default=",".join(STAGES))
    p.add_argument("--shard", default=None,
                   help="unit shard as i/n (1-based)")
    args = p.parse_args(argv)
    names = [s for s in args.stages.split(",") if s]
    unknown = [s for s in names if s not in STAGES]
    if unknown:
        p.error(f"unknown stages {unknown}; have {sorted(STAGES)}")
    failures = []
    for name in names:
        t0 = time.monotonic()
        try:
            ok, detail = STAGES[name](args)
        except Exception as e:  # mxlint: allow-broad-except(a crashed stage is recorded as a FAIL, not an abort of the pipeline)
            ok, detail = False, f"{type(e).__name__}: {e}"
        dt = time.monotonic() - t0
        print(f"[ci] {name:10s} {'PASS' if ok else 'FAIL'} "
              f"({dt:.0f}s) {detail}", flush=True)
        if not ok:
            failures.append(name)
    if failures:
        print(f"[ci] FAILED stages: {failures}")
        return 1
    print("[ci] all stages green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
