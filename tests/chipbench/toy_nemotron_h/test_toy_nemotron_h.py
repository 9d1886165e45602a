"""The cell of the decoder whose layers differ in kind, on the CPU:
``toy-nemotron-h-train`` through the harness, the runner that compares with
the layer-wise plain reference *and* reads the routers' counters, and the
nine readers this configuration brought, traced and untraced; faults planted
in the system, which the comparison has to refuse; the readers' arithmetic on
a handmade trace; and the manifest's new entries.  The manifest and
``rehearse.py`` of this directory stand beside those of
``tests/chipbench/toy/``, ``toy_joyai/`` and ``toy_falcon_h1/``, which are
not edited."""
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, REPO)

from chipbench import named_time, run as harness, scope_reduce  # noqa: E402

CELL = "toy-nemotron-h-train"
REAL_CELL = "nemotron3-super-train-ep32-b1-s4096"
NEW = ["mamba_layer_ms", "mamba_scan_roofline_pct", "latent_moe_route_ms",
       "latent_moe_dispatch_ms", "latent_moe_experts_roofline_pct",
       "latent_moe_proj_ms", "hybrid_attention_roofline_pct",
       "hybrid_mtp_ms", "hybrid_recompute_ms"]


def _rehearse(trace, cell=CELL, fault=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TOY_NEMOTRON_H_FAULT=fault)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "--workload",
         cell, "--seed", "3000000019", "--seconds", "1", "--trace",
         str(trace)], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_untraced_rehearsal_prints_the_contracts_last_line():
    line, out = _rehearse(0)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, out[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "step_ms_p95",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"
    assert "compile_count 1 == 1" in out
    assert out.count("[moe] ") == 3 and "moe.overflow_steps 0 == 0" in out
    assert "[reference]" not in out        # the comparison is the traced run's


def test_traced_rehearsal_compares_with_the_reference():
    line, out = _rehearse(1)
    assert line["correct"] is True, out[-4000:]
    # what a CPU cannot give is left out, not made up
    assert set(line["metrics"]) == {"dispatch_ms.train", "compile_s.train"}
    for name in NEW:
        assert f"[metric] {name}: nothing to read, left out" in out
    for said in ("float32 reference at the highest matmul precision, a "
                 "layer at a time", "the timed program's first loss:",
                 "the net's loss:", "the two heads' logits, relative L2:",
                 "every one of the 3 routers chose 4 experts a token",
                 "share of tokens whose 4th and next score lie within",
                 "share of tokens outside that margin whose choice differs",
                 # ... and of the timed program itself, after its first
                 # call: every gradient is held through its first moments
                 "every one of the timed program's 59 parameters and first "
                 "moments met the reference's",
                 "[check] ok   the timed program's first call moved every "
                 "parameter",
                 "the timed step's own gradients (its first moments) as one "
                 "vector, relative L2:",
                 "the worst single one of the timed step's own gradients, "
                 "relative L2:",
                 "the timed step's change of the parameters against the "
                 "reference's AdamW step, relative L2:",
                 "moe.overflow_steps 0 == 0 over 3 routed layers",
                 "[check] ok   the reference in float8_e4m3fn would be "
                 "refused"):
        assert said in out, said
    assert "FAIL" not in out
    (refused,) = re.findall(r"refused by: (.*)", out)
    assert set(refused.split(", ")) >= {"logits", "grads", "grad_worst",
                                        "update"}


# a fault planted in the system alone (cells/configs/toy_nemotron_h_faulty.py)
# has to come out as not correct, by at least the checks named here
TIMED_GRADS = "the timed step's own gradients (its first moments) as one"
TIMED_WORST = "the worst single one of the timed step's own gradients"
TIMED_UPDATE = "the timed step's change of the parameters against"
FAULTS = {
    "latent_projection_skipped": [TIMED_WORST, TIMED_UPDATE],
    "relu2_taken_as_relu": ["the two heads' logits", TIMED_GRADS,
                            TIMED_WORST],
    "top_k_taken_as_half": ["every one of the 3 routers chose 4 experts",
                            TIMED_WORST],
    "scaling_factor_left_out": [TIMED_WORST],
    "rotary_applied": [TIMED_WORST],
    "gate_after_norm": ["the two heads' logits", TIMED_GRADS, TIMED_UPDATE],
    "state_left_unchanged": [TIMED_UPDATE,
                             "the timed program's first call moved every"],
}


def test_the_faulty_cell_without_a_fault_is_correct():
    line, out = _rehearse(1, "toy-nemotron-h-faulty")
    assert line["correct"] is True and "FAIL" not in out, out[-4000:]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_as_not_correct(fault):
    line, out = _rehearse(1, "toy-nemotron-h-faulty", fault)
    assert line["correct"] is False, out[-4000:]
    checks = [l for l in out.splitlines() if l.startswith("[check] ")]
    for said in FAULTS[fault]:
        assert any(l.startswith("[check] FAIL " + said) for l in checks), \
            (said, checks)


def test_the_faults_are_the_faulty_configurations_own():
    faulty = harness.load_module(os.path.join(
        HERE, "cells", "configs", "toy_nemotron_h_faulty.py"))
    assert sorted(FAULTS) == sorted(faulty.FAULTS)


# ------------------------------------------------------------- the readers

def _ops():
    Op = scope_reduce.Op
    step = "jit(step)/jvp(forward)/layers/2"
    back = ("jit(step)/transpose(jvp(forward))/layers/2/jvp(forward)/"
            "layers/2/checkpoint")
    moe = ("jit(step)/transpose(jvp(forward))/layers/1/jvp(forward)/"
           "layers/1/checkpoint")
    return [
        Op(0, "%a", f"{step}/mamba/jit(ssd_scan)/ssd_scan/dot_general",
           0, 100),
        Op(0, "%b", f"{back}/rematted_computation/mamba/jit(ssd_scan)/"
           "ssd_scan/exp", 100, 100),
        Op(0, "%c", f"{back}/mamba/out_proj/dot_general", 200, 200),
        Op(0, "%d", f"{moe}/moe/jit(moe_route)/moe_route/top_k", 400, 300),
        Op(0, "%e", f"{moe}/rematted_computation/moe/jit(moe_ffn)/"
           "moe_dispatch/gather", 700, 500),
        Op(0, "%f", f"{moe}/moe/jit(moe_ffn)/moe_experts/pallas_call",
           1200, 800),
        Op(0, "%g", f"{moe}/moe/moe_latent_down/latent_down/dot_general",
           2000, 60),
        Op(0, "%h", f"{moe}/moe/moe_latent_up/latent_up/dot_general",
           2060, 40),
        Op(0, "%i", "jit(step)/jvp(forward)/mtp/block/0/attn/"
           "jit(dot_product_attention)/flash_attention/"
           "flash_attention_fwd/pallas_call", 2100, 1600),
        Op(0, "%j", "", 3700, 50),                  # no name: unscoped
    ]


def _run(tmp_path, monkeypatch, work=None):
    cell = {"name": "handmade", "traffic": {"trace_steps": 2, "batch": 1,
                                            "seq_len": 4096}}
    out = tmp_path / "out" / "handmade"
    out.mkdir(parents=True)
    (out / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scope_reduce, "HERE", str(tmp_path))
    monkeypatch.setattr(scope_reduce, "read_trace",
                        lambda path: (_ops(), [], []))
    monkeypatch.setattr(named_time, "_TIMES", {})

    class Model:
        ssm_scan_work = staticmethod(lambda c, t: work)
        gqa_attention_work = staticmethod(lambda c, t: work)
        latent_moe_experts_work = staticmethod(lambda c, t: work)

    return {"trace": {"busy_s": 1.0}, "cell": cell, "config": {},
            "model": Model, "peaks": {"bf16_flops_per_s": 2e12,
                                      "hbm_bytes_per_s": 1e12}}


def _read(name, run):
    return harness.load_module(os.path.join(
        REPO, "chipbench", "layer_metrics", name + ".py")).read(run)


def test_the_new_readers_on_a_handmade_trace(tmp_path, monkeypatch):
    # 2 traced steps, nanoseconds above: ms a step = ns / 1e6 / 2
    run = _run(tmp_path, monkeypatch, work=(0.8e6, 0.1e6))
    assert _read("mamba_layer_ms", run) == pytest.approx(400 / 2e6)
    assert _read("latent_moe_route_ms", run) == pytest.approx(300 / 2e6)
    assert _read("latent_moe_dispatch_ms", run) == pytest.approx(500 / 2e6)
    assert _read("latent_moe_proj_ms", run) == pytest.approx(100 / 2e6)
    assert _read("hybrid_mtp_ms", run) == pytest.approx(1600 / 2e6)
    assert _read("hybrid_recompute_ms", run) == pytest.approx(600 / 2e6)
    # 0.8e6 operations at 2e12/s = 0.4 us (bytes: 0.1 us) against 0.1 us a
    # step under ssd_scan, 0.4 under moe_experts, 0.8 under flash_attention
    assert _read("mamba_scan_roofline_pct", run) == pytest.approx(400.0)
    assert _read("latent_moe_experts_roofline_pct", run) == \
        pytest.approx(100.0)
    assert _read("hybrid_attention_roofline_pct", run) == pytest.approx(50.0)
    # bound by bytes instead
    run = _run(tmp_path / "b", monkeypatch, work=(1.0, 0.1e6))
    assert _read("latent_moe_experts_roofline_pct", run) == \
        pytest.approx(25.0)


def test_the_new_readers_find_nothing_where_nothing_is(tmp_path,
                                                       monkeypatch):
    run = _run(tmp_path, monkeypatch, work=(1.0, 1.0))
    monkeypatch.setattr(scope_reduce, "read_trace", lambda path: (
        [scope_reduce.Op(0, "%x", "jit(step)/jvp(forward)/features/0/conv",
                         0, 10)], [], []))
    assert all(_read(name, run) is None for name in NEW)
    assert all(_read(name, dict(run, trace=None)) is None for name in NEW)
    # a run record of an accepted runner holds no "model", and a program
    # that has no such scope (the parent's) gives nothing to read
    bare = {k: v for k, v in _run(tmp_path / "c", monkeypatch).items()
            if k != "model"}
    assert _read("mamba_scan_roofline_pct", bare) is None
    assert _read("latent_moe_route_ms", bare) == pytest.approx(300 / 2e6)


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_is_in_the_manifest_under_a_layer_of_perf_md(name):
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [REAL_CELL]
    assert entry["moves"] == "train_samples_per_s"
    assert entry["source"] == "device_trace"
    assert entry["unit"] == ("%" if name.endswith("_pct") else "ms")
    with open(os.path.join(REPO, "PERF.md"), encoding="utf-8") as f:
        section = f.read().split("## 3. Layers")[1].split("\n## ")[0]
    # PERF.md has the layer as a row of section 3 and names the metric there
    assert re.search(rf"^\| {entry['layer']} \|", section, re.M)
    assert f"`{name}`" in section
    reader = harness.load_module(os.path.join(
        REPO, "chipbench", "layer_metrics", name + ".py"))
    doc = " ".join(reader.__doc__.split())
    assert f"Layer: {entry['layer']}." in doc
    assert "Source: device trace." in doc and callable(reader.read)


def test_the_manifest_only_gained_entries_at_the_end_of_its_lists():
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert [m["name"] for m in manifest["per_layer"]][-9:] == NEW
    assert manifest["configs"][-1]["name"] == "nemotron3_super_120b"
    assert manifest["workloads"][-1]["name"] == REAL_CELL
    assert manifest["workloads"][-1]["chips"] == 1
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    for entry in manifest["configs"][-1:] + manifest["workloads"][-1:]:
        assert len(entry["why"]) <= 200
    # every older metric with a list of cells keeps it: none reports here
    for metric in manifest["per_layer"][:-9]:
        assert REAL_CELL not in metric.get("workloads", [])
