#!/usr/bin/env python
"""Soak benchmark: the capacity curve + a chaos soak, in one record.

This is the end-to-end gate behind ROADMAP item 6 — the bench that
turns "serves heavy traffic from millions of users" into numbers:

  determinism   the workload spec compiles twice to the SAME schedule
                (sha256 fingerprint) — a soak failure replays from
                ``(workload, seed, time_scale, chaos_spec)`` alone
  capacity      an offered-load x replica-count sweep (in-process
                thread fleet, open-loop arrivals) emitting the
                capacity curve: which offered points CONFORM to the
                SLO targets, per-replica capacity, and the knee
  soak          a time-compressed production-shaped replay (flash
                crowd + heavy-tailed sessions + multi-tenant mix)
                against a REAL subprocess fleet under a seeded chaos
                spec, with a scripted mid-run replica SIGKILL and a
                pre-armed fault burst — judged on per-class SLO
                minutes, ZERO lost streams (bitwise ledger vs
                unbroken references) and ``postmortem --gate``
                reconstruction of every incident

``--check`` gates all three; on failure it prints the one-line repro
command.  The ``soak`` CI stage runs it time-compressed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as onp   # noqa: E402

WIDTH = 16

DEFAULT_WORKLOAD = ("flash_crowd:duration=60,base=2,peak=8,"
                    "sessions=0.15,"
                    "tenants=hi@interactive*2+lo@standard*1")
DEFAULT_CHAOS = ("serving.route:error:p=0.01:seed=3,"
                 "loadgen.tick:delay:ms=5:n=3")


def _artifact(tmp, name="soak_model"):
    import jax.numpy as jnp
    from incubator_mxnet_tpu import deploy

    def fwd(params, x):
        y = x
        for w in params["layers"]:
            y = jnp.tanh(y @ w)
        return y

    rng = onp.random.RandomState(11)
    params = {"layers": [rng.randn(WIDTH, WIDTH).astype(onp.float32)
                         * 0.1 for _ in range(2)]}
    x = rng.randn(1, WIDTH).astype(onp.float32)
    prefix = os.path.join(tmp, name)
    deploy.export_model(fwd, (x,), prefix, params=params,
                        aot_buckets=[1, 2, 4])
    return prefix


def repro_line(args):
    return (f"MXNET_SOAK_SEED={args.seed} "
            f"MXNET_FAULT_SPEC='{args.chaos}' "
            f"python benchmark/soak_bench.py "
            f"--workload '{args.workload}' "
            f"--time-scale {args.time_scale} --check")


def bench(args):
    import jax
    from incubator_mxnet_tpu import fault
    from incubator_mxnet_tpu.serving.loadgen import parse_workload
    from incubator_mxnet_tpu.serving.loadgen.capacity import (
        sweep_capacity)
    from incubator_mxnet_tpu.serving.loadgen.harness import (
        Incident, SoakHarness)

    spec = parse_workload(args.workload)
    s1 = spec.compile(seed=args.seed, time_scale=args.time_scale)
    s2 = parse_workload(spec.describe()).compile(
        seed=args.seed, time_scale=args.time_scale)
    deterministic = s1.fingerprint() == s2.fingerprint()

    record = {
        "bench": "soak",
        "metric": "capacity_knee_rps",
        "unit": "rps",
        "workload": spec.describe(),
        "seed": args.seed,
        "time_scale": args.time_scale,
        "chaos_spec": args.chaos,
        "fingerprint": s1.fingerprint(),
        "schedule_deterministic": deterministic,
        "arrivals": len(s1.arrivals),
        "repro": repro_line(args),
        "platform": jax.devices()[0].platform,
    }

    with tempfile.TemporaryDirectory() as tmp:
        prefix = _artifact(tmp)
        t0 = time.monotonic()
        record["capacity"] = sweep_capacity(
            prefix,
            replica_counts=args.replica_counts,
            load_fractions=(0.25, 0.5, 1.0),
            requests=args.requests, width=WIDTH)
        record["capacity_s"] = round(time.monotonic() - t0, 2)

        knee = record["capacity"]["knee"]
        record["value"] = (knee["capacity_rps"]
                           .get(str(knee["knee_replicas"]), 0.0)
                           if knee["knee_replicas"] else 0.0)

        # chaos soak: replica SIGKILL mid-crowd + pre-armed fault
        # burst, judged post-hoc by the flight rings
        mid = spec.params["duration"] * 0.5
        incidents = [
            Incident(t=mid, kind="kill_replica", target=0,
                     gate="replica.exited,replica.state"),
            Incident(t=spec.params["duration"] * 0.25,
                     kind="fault_burst",
                     gate="fault.serving.route"),
        ]
        fault.configure(args.chaos or None)
        try:
            t0 = time.monotonic()
            harness = SoakHarness(
                tmp, s1, chaos_spec=args.chaos,
                incidents=incidents, routers=1,
                replicas=args.soak_replicas, backend="process",
                width=WIDTH)
            with harness:
                harness.warm()
                soak = harness.run()
        finally:
            fault.reset()
        soak.pop("anchored_at", None)
        record["soak"] = soak
        record["soak_s"] = round(time.monotonic() - t0, 2)
    return record


def check(record, args):
    problems = []
    if not record["schedule_deterministic"]:
        problems.append("same seed did NOT reproduce the same "
                        "schedule (fingerprint mismatch)")
    cap = record["capacity"]
    counts = {p["replicas"] for p in cap["points"]}
    per_count = min((sum(1 for p in cap["points"]
                         if p["replicas"] == c) for c in counts),
                    default=0)
    if len(counts) < 2 or per_count < 3:
        problems.append(
            f"capacity curve too small: {len(counts)} replica "
            f"count(s) x {per_count} offered point(s) "
            f"(want >=2 x >=3)")
    if cap["knee"]["knee_replicas"] is None:
        problems.append("no conformant offered point — knee "
                        "unidentified")
    soak = record["soak"]
    if soak["lost_streams"]:
        problems.append(
            f"{soak['lost_streams']} lost stream(s): "
            f"{soak['stream_failures'][:2]}")
    if soak["error_count"]:
        problems.append(f"soak errors: {soak['errors'][:3]}")
    inter = soak["slo"].get("interactive")
    if inter is None:
        problems.append("workload produced no interactive-class "
                        "traffic to judge")
    elif inter["violating_minutes"]:
        problems.append(
            f"interactive SLO violated in minute(s) "
            f"{inter['violating_minutes']} "
            f"(p99 {inter['p99_ms']}ms vs {inter['target_ms']}ms)")
    gates = soak["incidents"]
    if len(gates) < 2:
        problems.append(f"expected >=2 gated incidents, got "
                        f"{len(gates)}")
    for g in gates:
        if not g["gate_ok"]:
            problems.append(
                f"incident {g['kind']}@{g['t']} not reconstructed: "
                f"gate '{g['gate']}' failed")
    return problems


def main(argv=None):
    p = argparse.ArgumentParser(
        description="workload-replay soak + capacity curve")
    p.add_argument("--workload", default=DEFAULT_WORKLOAD)
    p.add_argument("--chaos", default=DEFAULT_CHAOS,
                   help="MXNET_FAULT_SPEC for every soak process "
                        "(recorded in the JSON artifact)")
    p.add_argument("--seed", type=int,
                   default=int(os.environ.get("MXNET_SOAK_SEED", 7)))
    p.add_argument("--time-scale", type=float, default=5.0,
                   help="virtual->real compression for the soak "
                        "replay (t_real = t_virtual / time_scale)")
    p.add_argument("--replica-counts", default="1,2",
                   help="capacity-sweep replica counts")
    p.add_argument("--soak-replicas", type=int, default=2)
    p.add_argument("--requests", type=int, default=48,
                   help="requests per capacity probe point")
    p.add_argument("--check", action="store_true")
    p.add_argument("--output", default=None)
    args = p.parse_args(argv)
    args.replica_counts = tuple(
        int(v) for v in str(args.replica_counts).split(","))

    record = bench(args)
    line = json.dumps(record)
    print(line, flush=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")

    if args.check:
        problems = check(record, args)
        if problems:
            print("soak_bench --check FAILED:\n  - "
                  + "\n  - ".join(problems)
                  + f"\n  repro: {record['repro']}",
                  file=sys.stderr)
            return 1
        knee = record["capacity"]["knee"]
        inter = record["soak"]["slo"].get("interactive", {})
        print(f"soak_bench --check ok: knee "
              f"{knee['knee_replicas']} replica(s) @ "
              f"{record['value']} rps, "
              f"{record['soak']['sessions']} streams / 0 lost, "
              f"interactive p99 {inter.get('p99_ms')}ms, "
              f"{len(record['soak']['incidents'])} incidents "
              f"reconstructed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
