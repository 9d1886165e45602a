"""The hybrid state-space decoder's cell on the CPU: ``toy-falcon-h1-train``
through the harness, the runner that compares with the block-wise plain
reference and the five readers this configuration brought, traced and
untraced; faults planted in the system, which the comparison has to refuse;
the readers' arithmetic on a handmade trace; and the configuration's own
counts (``flops_per_sample``, the two kernels' work, the
parameters, the batches) against numbers computed by hand or by XLA.  The
manifest and ``rehearse.py`` of this directory stand beside those of
``tests/chipbench/toy/`` and ``toy_joyai/``, which are not edited."""
import json
import math
import os
import re
import subprocess
import sys

import numpy as onp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, REPO)

from chipbench import named_time, run as harness, scope_reduce  # noqa: E402

CELL = "toy-falcon-h1-train"
REAL_CELL = "falcon-h1-34b-train-b1-s4096"
NEW = ["ssm_scan_roofline_pct", "ssm_conv_ms", "ssm_mixer_ms",
       "gqa_attention_roofline_pct", "recompute_ms"]


def _rehearse(trace, cell=CELL, fault=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TOY_FALCON_H1_FAULT=fault)
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
    assert "[reference]" not in out        # the comparison is the traced run's


def test_traced_rehearsal_compares_with_the_reference():
    line, out = _rehearse(1)
    assert line["correct"] is True, out[-4000:]
    # what a CPU cannot give is left out, not made up
    assert set(line["metrics"]) == {"dispatch_ms.train", "compile_s.train"}
    for name in NEW:
        assert f"[metric] {name}: nothing to read, left out" in out
    for said in ("float32 reference at the highest matmul precision, a "
                 "block at a time", "the timed program's first loss:",
                 "the logits, relative L2:",
                 "all gradients as one vector, relative L2:",
                 "the worst single parameter's gradient, relative L2:",
                 "every one of the net's 35 gradients met the reference's",
                 # ... and of the timed program itself, after its first call
                 "every one of the timed program's 35 parameters and first "
                 "moments met the reference's",
                 "[check] ok   the timed program's first call moved every "
                 "parameter",
                 "the timed step's own gradients (its first moments) as one "
                 "vector, relative L2:",
                 "the worst single one of the timed step's own gradients, "
                 "relative L2:",
                 "the timed step's change of the parameters against the "
                 "reference's AdamW step, relative L2:",
                 "[check] ok   the reference in float8_e4m3fn would be "
                 "refused"):
        assert said in out, said
    assert "FAIL" not in out
    # the float8 control breaks the new limit too
    (refused,) = re.findall(r"refused by: (.*)", out)
    assert set(refused.split(", ")) >= {"logits", "grads", "grad_worst",
                                        "update"}


# a fault planted in the system alone (cells/configs/toy_falcon_h1_faulty.py)
# has to come out as not correct, and by the checks named here: the first
# two exist in the timed step only (the optimizer), which the comparison of
# the net outside the step cannot see
TIMED_GRADS = "the timed step's own gradients (its first moments) as one"
TIMED_UPDATE = "the timed step's change of the parameters against"
FAULTS = {
    "state_left_unchanged": (
        [TIMED_UPDATE, "the timed program's first call moved every"],
        ["all gradients as one vector", TIMED_GRADS]),
    "learning_rate_times_three": (
        [TIMED_UPDATE], ["all gradients as one vector", TIMED_GRADS]),
    "half_the_sequence": ([TIMED_GRADS, TIMED_UPDATE], ["the logits"]),
    "labels_one_late": (
        [TIMED_GRADS, TIMED_UPDATE],
        # at initialisation the loss is that of uniform logits whatever the
        # labels: the gradients catch a wrong shift, the loss does not
        ["the timed program's first loss", "the logits"]),
    "mixer_branch_dropped": (["the logits", TIMED_GRADS, TIMED_UPDATE], []),
    "head_multiplier_left_out": (
        ["the timed program's first loss", "the logits", TIMED_GRADS], []),
}


def test_the_faulty_cell_without_a_fault_is_correct():
    line, out = _rehearse(1, "toy-falcon-h1-faulty")
    assert line["correct"] is True and "FAIL" not in out, out[-4000:]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_as_not_correct(fault):
    fails, passes = FAULTS[fault]
    line, out = _rehearse(1, "toy-falcon-h1-faulty", fault)
    assert line["correct"] is False, out[-4000:]
    checks = [l for l in out.splitlines() if l.startswith("[check] ")]
    for said in fails:
        assert any(l.startswith("[check] FAIL " + said) for l in checks), \
            (said, checks)
    for said in passes:
        assert any(l.startswith("[check] ok   " + said) for l in checks), \
            (said, checks)


# ------------------------------------------------------------- the readers

def _ops():
    Op = scope_reduce.Op
    step = "jit(step)/jvp(forward)/layers/1"
    back = ("jit(step)/transpose(jvp(forward))/layers/1/jvp(forward)/"
            "layers/1/checkpoint")
    return [
        Op(0, "%a", f"{step}/mamba/jit(ssd_scan)/ssd_scan/dot_general",
           0, 100),
        Op(0, "%b", f"{back}/rematted_computation/mamba/jit(ssd_scan)/"
           "ssd_scan/exp", 100, 100),
        Op(0, "%c", f"{back}/mamba/jit(ssd_scan)/ssd_scan/while/body/mul",
           200, 200),
        Op(0, "%d", f"{step}/mamba/jit(causal_conv1d)/causal_conv1d/add",
           400, 300),
        Op(0, "%e", f"{back}/rematted_computation/ffn/gate_up/dot_general",
           700, 500),
        Op(0, "%f", f"{back}/attn/jit(dot_product_attention)/"
           "flash_attention/cond/branch_0_fun/flash_attention_bwd/"
           "pallas_call", 1200, 1600),
        Op(0, "%g", f"{step}/mamba/out_proj/dot_general", 2800, 1000),
        Op(0, "%h", "", 3800, 50),                  # no name: unscoped
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

    return {"trace": {"busy_s": 1.0}, "cell": cell, "config": {},
            "model": Model, "peaks": {"bf16_flops_per_s": 2e12,
                                      "hbm_bytes_per_s": 1e12}}


def _read(name, run):
    return harness.load_module(os.path.join(
        REPO, "chipbench", "layer_metrics", name + ".py")).read(run)


def test_the_new_readers_on_a_handmade_trace(tmp_path, monkeypatch):
    # 2 traced steps, nanoseconds above: ms a step = ns / 1e6 / 2
    run = _run(tmp_path, monkeypatch, work=(0.8e6, 0.1e6))
    assert _read("ssm_conv_ms", run) == pytest.approx(300 / 2e6)
    # forward, run again and backward, projections and all
    assert _read("ssm_mixer_ms", run) == pytest.approx(1700 / 2e6)
    assert _read("recompute_ms", run) == pytest.approx(600 / 2e6)
    # 0.8e6 operations at 2e12/s = 0.4 us (bytes: 0.1 us) against 0.2 us a
    # step under ssd_scan, and 0.8 us a step under flash_attention
    assert _read("ssm_scan_roofline_pct", run) == pytest.approx(200.0)
    assert _read("gqa_attention_roofline_pct", run) == pytest.approx(50.0)
    # bound by bytes instead
    run = _run(tmp_path / "b", monkeypatch, work=(1.0, 0.1e6))
    assert _read("ssm_scan_roofline_pct", run) == pytest.approx(50.0)


def test_the_new_readers_find_nothing_where_nothing_is(tmp_path,
                                                       monkeypatch):
    run = _run(tmp_path, monkeypatch, work=(1.0, 1.0))
    monkeypatch.setattr(scope_reduce, "read_trace", lambda path: (
        [scope_reduce.Op(0, "%x", "jit(step)/jvp(forward)/features/0/conv",
                         0, 10)], [], []))
    assert all(_read(name, run) is None for name in NEW)
    assert all(_read(name, dict(run, trace=None)) is None for name in NEW)
    # a run record of the accepted runner holds no "model"
    bare = {k: v for k, v in _run(tmp_path / "c", monkeypatch).items()
            if k != "model"}
    assert _read("ssm_scan_roofline_pct", bare) is None
    assert _read("ssm_conv_ms", bare) == pytest.approx(300 / 2e6)


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


# ------------------------------------------------------- the configuration

def _real():
    path = os.path.join(REPO, "chipbench", "configs", "falcon_h1_34b")
    cell = harness.load_json(os.path.join(
        REPO, "chipbench", "workloads", REAL_CELL + ".json"))
    return (harness.load_json(path + ".json"),
            harness.load_module(path + ".py"), cell["traffic"], cell)


def test_the_configuration_keeps_every_published_width():
    config, _, _, _ = _real()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [json.loads(line) for line in f
                  if '"Falcon-H1-34B-Instruct"' in line]
    published = row["config"]
    differ = {k for k, v in published.items() if config.get(k) != v}
    assert differ == {"num_hidden_layers", "vocab_size"}
    assert sorted(config["reduced"]) == sorted(differ)
    assert config["published"] == {"num_hidden_layers": 72,
                                   "vocab_size": 261120}
    assert (config["num_hidden_layers"], config["vocab_size"]) == \
        (4, 261120 // 8)
    assert config["recompute"] == "blocks"
    assert {"initializer_std", "mamba_init", "optimizer_params", "seq_len",
            "dtype", "recompute"} <= set(config["assumed"])
    manifest = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == "falcon_h1_34b"]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]


def test_the_cells_traffic_is_the_issues_to_the_number():
    _, _, traffic, cell = _real()
    assert traffic == {"batch": 1, "seq_len": 4096, "successors": 4,
                       "pool": 4, "queue_depth": 8, "warmup_steps": 5,
                       "trace_steps": 10}
    assert cell["runner"] == "train_vs_blockwise_reference"
    assert cell["reference"]["lower_precision_probe"] == "float8_e4m3fn"
    assert set(cell["reference"]["reasons"]) == {"loss", "logits", "grads",
                                                 "grad_worst", "update"}
    # a state left unchanged reads 1: the limit lies well under it
    assert 0.0 < cell["reference"]["update"] <= 0.6


def test_parameters_and_flops_against_hand_counts_and_xlas():
    config, model, traffic, _ = _real()
    assert model.attention_params(config) == 31_457_280
    assert model.mixer_matmul_params(config) == 47_349_760 + 20_971_520
    assert model.layer_params(config) == 430_120_032
    assert model.total_params(config) == 2_054_718_848
    assert model.matmul_params(config) == 4 * (
        31_457_280 + 68_321_280 + 330_301_440) + 5120 * 32640
    per_step = model.flops_per_sample(config, traffic) * traffic["batch"]
    assert per_step == pytest.approx(47.652e12, rel=1e-4)
    # XLA's own count of one block and the head compiled for a described
    # v5e without recomputation (PR 34): 14.7807 TFLOP.  It counts nothing
    # inside a Mosaic call, so it is held to this count less attention's
    # part (0.258 TFLOP); it counts the scan's diagonal blocks whole and the
    # elementwise work this leaves out
    one = dict(config, num_hidden_layers=1)
    attention, _ = model.gqa_attention_work(one, traffic)
    mine = model.flops_per_sample(one, traffic) - attention
    assert abs(mine - 14.7807e12) / 14.7807e12 < 0.01


def test_the_two_kernels_work_on_hand_computed_values():
    config, model, traffic, _ = _real()
    # the scan, forward a token a layer: C·Bᵀ 2 groups x 128 x 256 and
    # (L ⊙ CBᵀ)(Δx) 32 heads x 128 x 128 at half the chunk's square, own
    # state and carried output 32 heads x 2 x 2 x 128 x 256; three passes
    ops, moved = model.ssm_scan_work(config, traffic)
    assert ops == 4 * 4096 * 3 * (2 * 128 * 256 + 32 * 128 * 128
                                  + 32 * 4 * 128 * 256)
    # bfloat16 x, B, C (5,120 wide) three times and y twice; float32 Δ thrice
    assert moved == 4 * 4096 * (2 * (3 * 5120 + 2 * 4096) + 4 * 3 * 32)
    assert ops / 197e12 > moved / 819e9         # bound by operations
    # attention: 4 blocks x 4096 tokens x 20 heads x 3 x (128 + 128) x 4096
    ops, moved = model.gqa_attention_work(config, traffic)
    assert ops == 4 * 4096 * 20 * 3 * 256 * 4096
    # q, o and their gradients, q and o once more: 6 x 20 heads; k, v
    # likewise: 6 x 4 heads, read once a group
    assert moved == 4 * 2 * 4096 * 128 * 6 * (20 + 4)


def test_batches_are_markov_documents_from_the_seed_alone():
    config, model, traffic, _ = _real()
    a = model.make_batch(2 ** 31 + 11, 1, 1, config, traffic)
    b = model.make_batch(2 ** 31 + 11, 1, 1, config, traffic)
    c = model.make_batch(2 ** 31 + 11, 2, 1, config, traffic)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()
    tokens, labels = a
    assert tokens.shape == (1, 4096) and labels.shape == (1, 1, 4096)
    assert tokens.dtype == labels.dtype == onp.int32
    assert 0 <= tokens.min() and tokens.max() < 32640
    assert (labels[:, 0, :-1] == tokens[:, 1:]).all()
    # order 1 with 4 successors: a token is followed by at most 4 others,
    # over both batches (one table a seed)
    follows = {}
    for doc, last in ((tokens[0], labels[0, 0, -1]), (c[0][0], c[1][0, 0, -1])):
        for t, nxt in zip(doc, list(doc[1:]) + [last]):
            follows.setdefault(int(t), set()).add(int(nxt))
    assert max(len(v) for v in follows.values()) <= 4
    assert model.uniform_loss(config) == pytest.approx(math.log(32640))
