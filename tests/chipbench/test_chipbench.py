"""chipbench on the CPU: the harness, the train runner and every reader end
to end at toy sizes, the trace arithmetic against hand-computed values, and
the manifest against the rules a later PR must keep.

The chip's numbers come only from the chip (``python chipbench/run.py`` there);
what can be held to here is the control flow, the last line's contract, that
the command fails closed without a TPU, and the yardstick's arithmetic.  The
toy cells of ``toy/`` go through ``rehearse.py``, the one entry that tolerates
a CPU and that only these tests use.
"""
import importlib.util
import json
import math
import os
import re
import subprocess
import sys

import numpy as onp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import run as harness  # noqa: E402
from chipbench import stats, trace_reduce as tr  # noqa: E402

MANIFESTS = {"real": os.path.join(REPO, "BENCHMARK.json"),
             "toy": os.path.join(HERE, "toy", "BENCHMARK.json")}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(script, *argv, devices=1, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, script, *argv], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def _rehearse(cell, trace=0, devices=1, seed=3000000019):
    proc = _run(os.path.join(HERE, "rehearse.py"), "--workload", cell,
                "--seed", str(seed), "--seconds", "1", "--trace", str(trace),
                devices=devices)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def _names(manifest, section, cell):
    return {m["name"] for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]}


# ------------------------------------------------- the runner, end to end

@pytest.mark.parametrize("cell,devices", [
    ("toy-resnet-train", 1), ("toy-bert-mlm", 1),
    ("toy-resnet-train-dp4", 4)])
def test_rehearsal_prints_the_contracts_last_line(cell, devices):
    line, out = _rehearse(cell, devices=devices)
    toy = harness.load_json(MANIFESTS["toy"])
    assert set(line) == LINE_KEYS
    assert line["correct"] is True, out[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == _names(toy, "end_to_end", cell)
    units = {m["name"]: m["unit"] for m in toy["end_to_end"]}
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert math.isfinite(m["value"]) and m["value"] > 0
    # a rehearsal names the platform it ran on: never a TPU here
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": devices, "memory_peak_bytes": 0}
    assert out.splitlines()[0].startswith(f"[run] cell {cell} ")
    assert "'platform': 'cpu'" in out.splitlines()[0]
    assert "compile_count 1 == 1" in out
    assert f"on the cell's {devices} device(s)" in out


def test_traced_rehearsal_reports_layer_metrics_and_writes_a_trace():
    line, out = _rehearse("toy-resnet-train", trace=1)
    toy = harness.load_json(MANIFESTS["toy"])
    assert set(line) == LINE_KEYS       # no device plane: no breakdown
    assert line["correct"] is True, out[-3000:]
    # what a CPU cannot give is left out, not made up
    assert set(line["metrics"]) == {"dispatch_ms.train", "compile_s.train"}
    assert set(line["metrics"]) <= _names(toy, "per_layer",
                                          "toy-resnet-train")
    assert "busy_s" not in line["device"]
    written = re.search(r"traced into (\S+\.xplane\.pb)", out).group(1)
    assert os.path.dirname(written).startswith(
        os.path.join(REPO, "chipbench", "out", "toy-resnet-train"))
    # the thin reader, on the trace just written: the runner's own spans
    events = tr.read_events(written)
    spans = [e for e in events if e.plane == tr.HOST_PLANE
             and e.name in tr.HOST_SPANS]
    assert sorted(e.name for e in spans) == (["bench.dispatch"] * 4
                                             + ["bench.wait"] * 4)
    assert all(isinstance(e.start, int) and e.dur > 0 for e in spans)
    assert tr.reduce_trace(events, steps=4, device_ids=[0]) is None


def test_the_command_has_no_cpu_result():
    proc = _run(os.path.join(REPO, "chipbench", "run.py"), "--workload",
                harness.load_json(MANIFESTS["real"])["workloads"][0]["name"],
                "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "not a TPU" in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")
    assert "metrics" not in proc.stdout


def test_fewer_chips_than_the_cell_asks_for_is_no_result():
    proc = _run(os.path.join(HERE, "rehearse.py"), "--workload",
                "toy-resnet-train-dp4", "--seed", "1", "--seconds", "1",
                devices=1)
    assert proc.returncode != 0
    assert "asks for 4 chip(s) and JAX has 1" in proc.stdout
    assert "metrics" not in proc.stdout


# ------------------------------------------------------------ the manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(params=sorted(MANIFESTS))
def manifest(request):
    return harness.Manifest(MANIFESTS[request.param])


def test_manifest_names_units_and_keys(manifest):
    data = manifest.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= data["run_seconds"] <= 51
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [item["name"] for item in data[section]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    assert 1 <= len(data["command"]) <= 32
    lines = list(data["command"])
    for c in data["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in data["paths"])
        lines += [c["source"], c["why"]]
    lines += [m["layer"] for m in data["per_layer"]]
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in lines)
    for w in data["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in data["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in data["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(data["workloads"]) // 4) or "toy" in manifest.base
    for m in data["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in data["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    for m in data["end_to_end"] + data["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in data["end_to_end"]}
    assert len(json.dumps(data)) < 64 * 1024


def test_every_cell_finds_its_files_and_its_metrics(manifest):
    data = manifest.data
    cells = {w["name"] for w in data["workloads"]}
    assert {c["name"] for c in data["configs"]} == \
        {w["config"] for w in data["workloads"]}
    for w in data["workloads"]:
        cell = harness.load_json(manifest.find("workloads",
                                               w["name"] + ".json"))
        assert (cell["name"], cell["config"], cell["chips"]) == \
            (w["name"], w["config"], w["chips"])
        manifest.find("runners", cell["runner"] + ".py")
        config = manifest.entry("configs", w["config"])
        path = os.path.join(manifest.base, config["file"])
        assert harness.load_json(path)["name"] == w["config"]
        model = harness.load_module(os.path.splitext(path)[0] + ".py")
        for fn in ("build", "make_batch", "n_classes", "flops_per_sample"):
            assert callable(getattr(model, fn))
        e2e = {m["name"] for m in manifest.metrics_of("end_to_end",
                                                      w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = manifest.metrics_of("per_layer", w["name"])
        assert layer
        # a per-layer metric moves an end-to-end metric its cells report
        assert all(m["moves"] in e2e for m in layer)
    for section, directory in (("end_to_end", "end_to_end"),
                               ("per_layer", "layer_metrics")):
        for m in data[section]:
            assert set(m.get("workloads", cells)) <= cells
            reader = harness.load_module(
                manifest.find(directory, m["name"] + ".py"))
            assert callable(reader.read) and reader.__doc__
    layers = {m["name"]: m["layer"] for m in data["per_layer"]}
    assert all(1 <= len(v) <= 200 for v in layers.values())


def test_harness_and_runner_name_no_cell_configuration_or_metric():
    names = set()
    for path in MANIFESTS.values():
        data = harness.load_json(path)
        for section in ("configs", "workloads", "end_to_end", "per_layer"):
            names |= {item["name"] for item in data[section]}
    for source in ("run.py", os.path.join("runners", "train.py")):
        with open(os.path.join(REPO, "chipbench", source)) as f:
            text = f.read()
        assert not [n for n in names if n in text], source
        # and nothing of the repo's older benchmarks
        assert not re.search(r"import (bench|chip_smoke)\b|benchmark/", text)


def test_peaks_are_keyed_by_device_kind_with_their_source():
    peaks = harness.load_json(os.path.join(REPO, "chipbench", "peaks.json"))
    assert "TPU v5e" in peaks["_source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "cpu" not in peaks


# --------------------------------------------------------- configurations

def _config(name):
    path = os.path.join(REPO, "chipbench", "configs", name)
    return (harness.load_json(path + ".json"),
            harness.load_module(path + ".py"))


def test_resnet50_flops_per_sample():
    config, model = _config("resnet50_v1")
    assert model.flops_per_sample(config, {}) == 3 * 2 * 4.089e9
    assert (config["layers"], config["channels"], config["classes"]) == \
        ([3, 4, 6, 3], [64, 256, 512, 1024, 2048], 1000)


def test_bert_base_flops_per_step_against_xlas_count():
    config, model = _config("bert_base")
    traffic = harness.load_json(os.path.join(
        REPO, "chipbench", "workloads",
        "bert-base-mlm-b32-s512.json"))["traffic"]
    assert model.matmul_params(config) == 108_375_552
    per_step = model.flops_per_sample(config, traffic) * traffic["batch"]
    # XLA's own count of the compiled step for a described v5e (ISSUE 25):
    # 11.9 TFLOP, which includes the elementwise work this count leaves out
    assert abs(per_step - 11.9e12) / 11.9e12 < 0.05
    assert per_step == pytest.approx(11.58e12, rel=1e-3)


@pytest.mark.parametrize("name,traffic", [
    ("resnet50_v1", {}), ("bert_base", {"seq_len": 512})])
def test_batches_come_from_the_seed_alone(name, traffic):
    config, model = _config(name)
    big = 2**31 + 12345          # wider than 32 signed bits
    x0, y0 = model.make_batch(big, 0, 2, config, traffic)
    x1, y1 = model.make_batch(big, 0, 2, config, traffic)
    assert (x0 == x1).all() and (y0 == y1).all()
    assert x0.shape[0] == 2 and x0.dtype == x1.dtype
    x2, _ = model.make_batch(big, 1, 2, config, traffic)
    x3, _ = model.make_batch(big + 1, 0, 2, config, traffic)
    assert (x0 != x2).any() and (x0 != x3).any()
    assert y0.min() >= 0 and y0.max() < model.n_classes(config)


def test_bert_batch_masks_the_stated_share_and_keeps_the_labels():
    config, model = _config("bert_base")
    tokens, labels = model.make_batch(7, 0, 32, config, {"seq_len": 512})
    masked = tokens != labels
    assert (tokens[masked] == config["mask_token_id"]).all()
    assert labels.min() >= config["first_ordinary_token_id"]
    assert abs(masked.mean() - config["mask_fraction"]) < 0.01


def test_seed_is_folded_into_an_int32():
    train = harness.load_module(os.path.join(REPO, "chipbench", "runners",
                                             "train.py"))
    folded = [train.fold_seed(s) for s in (0, 1, 2**31 + 5, 2**40)]
    assert all(0 <= f < 2**31 for f in folded)
    assert len(set(folded)) == 4
    assert train.fold_seed(2**31 + 5) == folded[2]


# ------------------------------------------------------ metric arithmetic

def _handmade_run(**over):
    done = [10.1 + 0.1 * i for i in range(50)]
    done[20:] = [t + 0.05 for t in done[20:]]       # one stall of 50 ms
    run = {"window_open": 10.0, "process_start": 2.5, "step_done_at": done,
           "step_dispatch_s": [0.002] * 49 + [0.004], "samples_per_step": 8,
           "first_call_s": 4.2, "memory_peak_bytes": 3 * 2**30,
           "flops_per_sample": 1e9, "chips": 2,
           "peaks": {"bf16_flops_per_s": 1e12}, "trace": None}
    run.update(over)
    return run


def _read(directory, name, run):
    return harness.load_module(os.path.join(
        REPO, "chipbench", directory, name + ".py")).read(run)


def test_percentile_is_numpys():
    values = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    for q in (0, 5, 50, 95, 100):
        assert stats.percentile(values, q) == pytest.approx(
            onp.percentile(values, q))
    assert stats.median([1.0, 2.0]) == 1.5


def test_end_to_end_readers_on_a_handmade_run():
    run = _handmade_run()
    # 50 steps of 8 samples, the last ready 5.05 s after the window opened
    assert _read("end_to_end", "train_samples_per_s", run) == \
        pytest.approx(50 * 8 / 5.05)
    assert _read("end_to_end", "setup_s", run) == 7.5
    # 49 intervals: 48 of 100 ms and one of 150 ms; p95 sits at index 45.6
    assert _read("end_to_end", "step_ms_p95", run) == pytest.approx(100.0)
    assert max(stats.step_intervals_ms(run)) == pytest.approx(150.0)
    assert stats.percentile(stats.step_intervals_ms(run), 99) == \
        pytest.approx(100 + 50 * 0.52)


def test_layer_readers_on_a_handmade_run():
    run = _handmade_run()
    assert _read("layer_metrics", "dispatch_ms.train", run) == \
        pytest.approx(2.0)
    assert _read("layer_metrics", "compile_s.train", run) == 4.2
    assert _read("layer_metrics", "hbm_peak_gib", run) == 3.0
    # 1e9 FLOPs x 79.2 samples/s over 2 chips x 1e12
    assert _read("layer_metrics", "mfu.train", run) == pytest.approx(
        100 * 1e9 * (400 / 5.05) / 2e12)
    # nothing to read -> None, and the harness leaves the metric out
    assert _read("layer_metrics", "mfu.train",
                 _handmade_run(peaks=None)) is None
    assert _read("layer_metrics", "hbm_peak_gib",
                 _handmade_run(memory_peak_bytes=0)) is None
    for name in ("device_idle_share", "pallas_time_share",
                 "collective_ms.dp", "collective_exposed_ms.dp"):
        assert _read("layer_metrics", name, run) is None
    trace = {"idle_share_worst": 0.25, "busy_s": 2.0, "pallas_s": 0.5,
             "collective_s": 0.4, "collective_exposed_s": 0.1, "steps": 10}
    run = _handmade_run(trace=trace)
    assert _read("layer_metrics", "device_idle_share", run) == 25.0
    assert _read("layer_metrics", "pallas_time_share", run) == 25.0
    assert _read("layer_metrics", "collective_ms.dp", run) == \
        pytest.approx(40.0)
    assert _read("layer_metrics", "collective_exposed_ms.dp", run) == \
        pytest.approx(10.0)
    trace.update(collective_s=None, collective_exposed_s=None)
    assert _read("layer_metrics", "collective_ms.dp", run) is None


# ------------------------------------------------------- trace arithmetic

def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 2), (1, 3), (9, 10), (4, 4)]) == \
        [(0, 3), (5, 10)]
    assert tr.total([(0, 3), (5, 10)]) == 8
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.subtract([(0, 10)], [(0, 10)]) == []
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    spans = [("bench.dispatch", 0, 5), ("bench.wait", 5, 50)]
    assert tr.covering_span((3, 9), spans) == "bench.wait"
    assert tr.covering_span((0, 4), spans) == "bench.dispatch"
    assert tr.covering_span((60, 70), spans) == "none"


FUSION = ("%fusion.12 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(bf16[8,64]{1,0} "
          "%all-reduce.3), kind=kOutput, calls=%fused_computation.2")
FUSION7 = FUSION.replace("fusion.12", "fusion.7")
ALL_REDUCE = ("%all-reduce.3 = f32[64]{0:T(256)} all-reduce(f32[64]{0} "
              "%fusion.9), channel_id=1, replica_groups={{0,1}}")
AR_START = ("%all-reduce-start.1 = f32[64]{0} all-reduce-start(f32[64]{0} "
            "%fusion.9), channel_id=2")
KERNEL = ('%branch_0_fun.4 = f32[256,1024]{1,0} custom-call(bf16[256,1024] '
          '%pad.5), custom_call_target="tpu_custom_call"')
BITCAST = ('%custom-call.63 = bf16[256,256,3,3]{1,0} custom-call(bf16[2] '
           '%slice-done.231), custom_call_target="ConcatBitcast"')


def test_instructions_are_told_apart_by_their_text():
    assert tr.is_collective(ALL_REDUCE) and tr.is_collective(AR_START)
    assert not tr.is_collective(FUSION)     # an operand is not an opcode
    assert tr.is_pallas(KERNEL) and not tr.is_pallas(BITCAST)
    assert tr.family(FUSION) == tr.family(FUSION7) == "fusion"
    assert tr.family(AR_START) == "all-reduce-start"
    assert tr.short_name(FUSION).startswith(
        "fusion.12 = bf16[8,64] fusion(bf16[8,64] %all-reduce.3)")


def _handmade_trace():
    """Two devices, two steps, times in ns.  Device 0: fusion 0-40, the
    Pallas kernel 40-50, a synchronous all-reduce 50-70, idle 70-80 (the host
    was in bench.dispatch), fusion 80-100.  Device 1: fusion 0-60, an
    asynchronous all-reduce in flight 30-90 of which fusion.7 hides 30-60
    and 70-100, so 60-70 is exposed: the done at 60-70 is all that runs."""
    E, d0, d1 = tr.Event, "/device:TPU:0", "/device:TPU:1"
    done = AR_START.replace("-start", "-done")
    return [
        E(d0, "XLA Ops", FUSION, 0, 40), E(d0, "XLA Ops", KERNEL, 40, 10),
        E(d0, "XLA Ops", ALL_REDUCE, 50, 20), E(d0, "XLA Ops", FUSION, 80, 20),
        E(d0, "XLA Modules", "jit_step(1)", 0, 100),
        E(d1, "XLA Ops", FUSION, 0, 60), E(d1, "XLA Ops", done, 60, 10),
        E(d1, "XLA Ops", FUSION7, 70, 30),
        E(d1, "Async XLA Ops", AR_START, 30, 60),
        E("/device:TPU:2", "XLA Ops", FUSION, 0, 1000),     # not of the cell
        E("/host:CPU", "python3", "bench.dispatch", 65, 20),
        E("/host:CPU", "python3", "bench.wait", 85, 10),
        E("/host:CPU", "python3", "PjitFunction(step)", 66, 5),
    ]


def test_reduction_of_a_handmade_trace():
    r = tr.reduce_trace(_handmade_trace(), steps=2, device_ids=[0, 1])
    assert r["devices"] == 2 and r["steps"] == 2
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((90 + 100) / 2 * 1e-9)
    assert r["idle_share_worst"] == pytest.approx(0.10)     # device 0
    assert r["pallas_s"] == pytest.approx(10 / 2 * 1e-9)
    # device 0: 20 ns, all exposed; device 1: 60 ns in flight, 10 exposed
    assert r["collective_s"] == pytest.approx((20 + 60) / 2 * 1e-9)
    assert r["collective_exposed_s"] == pytest.approx((20 + 10) / 2 * 1e-9)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion (2 instructions)"] == pytest.approx(150 / 2 * 1e-9)
    assert ops["all-reduce (1 instructions)"] == pytest.approx(10e-9)
    assert r["breakdown"]["idle_gaps"] == [["bench.dispatch", 10e-9]]
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert r["top_instructions"][0][0].startswith("fusion.12 = ")
    # one chip of the two: its own numbers; a device with no events: None
    one = tr.reduce_trace(_handmade_trace(), steps=2, device_ids=[1])
    assert one["busy_s"] == pytest.approx(100e-9)
    assert one["idle_share_worst"] == 0 and one["pallas_s"] == 0
    assert tr.reduce_trace(_handmade_trace(), 2, device_ids=[3]) is None
    no_collectives = [e for e in _handmade_trace()
                      if not tr.is_collective(e.name)]
    assert tr.reduce_trace(no_collectives, 2, [0])["collective_s"] is None
