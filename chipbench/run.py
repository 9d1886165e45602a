#!/usr/bin/env python3
"""chipbench: one cell of BENCHMARK.json, measured on the chip it is started on.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It loads the cell, sets it up, warms up,
measures for ``--seconds`` and prints, as the last line of its standard
output, one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced).  With ``--trace 0`` the metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result: there is no CPU number.

This file names no cell, configuration or metric.  It finds each by the name
``BENCHMARK.json`` gives it, in a file of its own under the manifest's
``paths`` (see chipbench/README.md):

    workloads/<cell>.json        the cell: runner, traffic parameters
    <configs[].file>, and the .py beside it    the configuration and its builder
    runners/<runner>.py          run(job) -> the run's record
    end_to_end/<metric>.py       read(run) -> value, for --trace 0
    layer_metrics/<metric>.py    read(run) -> value or None, for --trace 1
    peaks.json                   device_kind -> published peaks
"""
import time

PROCESS_START = time.perf_counter()     # set-up is counted from here

import argparse
import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(Exception):
    """The run cannot give a result; the sentence says why."""


def say(msg):
    print(msg, flush=True)


def load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path):
    """A module from a file found by name (metric names hold dots)."""
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    """``BENCHMARK.json`` and the directories its names are looked up in:
    its own ``paths`` first, then this harness's directory."""

    def __init__(self, path):
        self.base = os.path.dirname(os.path.abspath(path))
        self.data = load_json(path)
        self.dirs = [os.path.join(self.base, p) for p in self.data["paths"]]
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    def entry(self, section, name):
        for item in self.data[section]:
            if item["name"] == name:
                return item
        known = ", ".join(item["name"] for item in self.data[section])
        raise BenchError(f"no entry {name!r} under {section!r} of the "
                         f"manifest (it has: {known})")

    def find(self, *parts):
        for d in self.dirs:
            path = os.path.join(d, *parts)
            if os.path.exists(path):
                return path
        raise BenchError(f"no file {os.path.join(*parts)} under any of "
                         f"{self.dirs}")

    def metrics_of(self, section, cell):
        """The section's metrics that this cell reports."""
        return [m for m in self.data[section]
                if "workloads" not in m or cell in m["workloads"]]


def read_metrics(manifest, section, directory, cell, run):
    """Each metric's own reader over the run's record; a reader that finds
    nothing to read returns None and its metric is left out of the line."""
    out = {}
    for metric in manifest.metrics_of(section, cell):
        reader = load_module(manifest.find(directory, metric["name"] + ".py"))
        value = reader.read(run)
        if value is None:
            say(f"[metric] {metric['name']}: nothing to read, left out")
            continue
        value = float(value)
        if not math.isfinite(value):
            raise BenchError(f"metric {metric['name']} read {value}")
        say(f"[metric] {metric['name']} = {value:.6g} {metric['unit']}")
        out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None, manifest_path=None, rehearse=False):
    """Run one cell; returns the exit code.  ``rehearse`` (the tests' entry,
    tests/chipbench/rehearse.py) tolerates a CPU, which the result line then
    names as its platform; the command the driver runs never sets it."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)        # the program under test
    try:
        line = run_cell(args, Manifest(
            manifest_path or os.path.join(ROOT, "BENCHMARK.json")), rehearse)
    except BenchError as e:
        say(f"chipbench: no result: {e}")
        return 1
    say(json.dumps(line))
    return 0


def run_cell(args, manifest, rehearse):
    entry = manifest.entry("workloads", args.workload)
    cell = load_json(manifest.find("workloads", entry["name"] + ".json"))
    config_file = os.path.join(
        manifest.base, manifest.entry("configs", entry["config"])["file"])
    config = load_json(config_file)
    model = load_module(os.path.splitext(config_file)[0] + ".py")
    runner = load_module(manifest.find("runners", cell["runner"] + ".py"))

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"[run] cell {entry['name']}  seed {args.seed}  window "
        f"{args.seconds:g} s  trace {args.trace}  device {device}")
    if device["platform"] != "tpu" and not rehearse:
        raise BenchError(
            f"JAX's default backend is {device['platform']!r}, not a TPU; "
            "this benchmark has no CPU result")
    if device["count"] < entry["chips"]:
        raise BenchError(f"the cell asks for {entry['chips']} chip(s) and "
                         f"JAX has {device['count']}")
    peaks = load_json(manifest.find("peaks.json")).get(device["kind"])
    if peaks is None and not rehearse:
        raise BenchError(f"no published peaks on record for device kind "
                         f"{device['kind']!r} (chipbench/peaks.json)")

    run = runner.run({
        "cell": cell, "config": config, "model": model, "peaks": peaks,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "devices": devices[:entry["chips"]], "process_start": PROCESS_START,
        "out_dir": os.path.join(HERE, "out", entry["name"]), "say": say})

    section, directory = (("per_layer", "layer_metrics") if args.trace
                          else ("end_to_end", "end_to_end"))
    metrics = read_metrics(manifest, section, directory, entry["name"], run)
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    trace = run.get("trace")
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    return line


if __name__ == "__main__":
    sys.exit(main())
