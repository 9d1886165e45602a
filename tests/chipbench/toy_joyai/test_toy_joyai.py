"""The routed decoder's cell on the CPU: ``toy-joyai-train`` through the
harness, the runner that compares with the plain reference and the five
readers this configuration brought, traced and untraced; the readers'
arithmetic on a handmade trace; and the configuration's own counts
(``flops_per_sample``, the two kernels' work, the batches) against numbers
computed by hand or by XLA.  The manifest and ``rehearse.py`` of this
directory stand beside those of ``tests/chipbench/toy/``, which are the
accepted benchmark's and are not edited."""
import json
import math
import os
import subprocess
import sys

import numpy as onp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, REPO)

from chipbench import named_time, run as harness, scope_reduce  # noqa: E402

CELL = "toy-joyai-train"
NEW = ["moe_route_ms", "moe_dispatch_ms", "moe_experts_roofline_pct",
       "mla_attention_roofline_pct", "mtp_ms"]


def _rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "--workload",
         CELL, "--seed", "3000000019", "--seconds", "1", "--trace",
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
    assert "moe.overflow_steps 0 == 0 over 3 routed layers" in out
    assert "[reference]" not in out        # the comparison is the traced run's


def test_traced_rehearsal_compares_with_the_reference():
    line, out = _rehearse(1)
    assert line["correct"] is True, out[-4000:]
    # what a CPU cannot give is left out, not made up
    assert set(line["metrics"]) == {"dispatch_ms.train", "compile_s.train"}
    for name in NEW:
        assert f"[metric] {name}: nothing to read, left out" in out
    for said in ("float32 reference at the highest matmul precision",
                 "the timed program's first loss:",
                 "the two heads' logits, relative L2:",
                 "all gradients as one vector, relative L2:",
                 "the worst single parameter's gradient, relative L2:",
                 "(compared under the program's own choice)",
                 "[check] ok   the reference in float8_e4m3fn would be "
                 "refused", "rows_held"):
        assert said in out, said
    assert "FAIL" not in out


# ------------------------------------------------------------- the readers

def _ops():
    Op = scope_reduce.Op
    step = "jit(step)/jvp(forward)"
    back = "jit(step)/transpose(jvp(forward))"
    return [
        Op(0, "%a", f"{step}/layers/1/ffn/jit(moe_route)/moe_route/dot",
           0, 100),
        Op(0, "%b", f"{back}/layers/1/ffn/jit(moe_ffn)/moe_dispatch/sort",
           100, 300),
        Op(0, "%c", f"{step}/layers/1/ffn/jit(moe_ffn)/moe_experts/"
           "cond/branch_0_fun/moe_experts_fwd/pallas_call", 400, 1000),
        Op(0, "%d", f"{step}/mtp/block/ffn/jit(moe_ffn)/moe_experts/mul",
           1400, 600),
        Op(0, "%e", f"{back}/mtp/block/attn/jit(dot_product_attention)/"
           "flash_attention/cond/branch_0_fun/flash_attention_bwd/"
           "pallas_call", 2000, 2000),
        Op(0, "%f", "", 4000, 50),                  # no name: unscoped
    ]


def _run(tmp_path, monkeypatch, work=None):
    cell = {"name": "handmade", "traffic": {"trace_steps": 2, "batch": 2,
                                            "seq_len": 4096}}
    out = tmp_path / "out" / "handmade"
    out.mkdir(parents=True)
    (out / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scope_reduce, "HERE", str(tmp_path))
    monkeypatch.setattr(scope_reduce, "read_trace",
                        lambda path: (_ops(), [], []))
    monkeypatch.setattr(named_time, "_TIMES", {})

    class Model:
        moe_experts_work = staticmethod(lambda c, t: work)
        mla_attention_work = staticmethod(lambda c, t: work)

    return {"trace": {"busy_s": 1.0}, "cell": cell, "config": {},
            "model": Model, "peaks": {"bf16_flops_per_s": 2e12,
                                      "hbm_bytes_per_s": 1e12}}


def _read(name, run):
    return harness.load_module(os.path.join(
        REPO, "chipbench", "layer_metrics", name + ".py")).read(run)


def test_the_new_readers_on_a_handmade_trace(tmp_path, monkeypatch):
    # 2 traced steps, nanoseconds above: ms a step = ns / 1e6 / 2
    run = _run(tmp_path, monkeypatch, work=(1.6e6, 0.1e6))
    assert _read("moe_route_ms", run) == pytest.approx(100 / 2e6)
    assert _read("moe_dispatch_ms", run) == pytest.approx(300 / 2e6)
    assert _read("mtp_ms", run) == pytest.approx(2600 / 2e6)
    # 1.6e6 operations at 2e12/s = 0.8 us (bytes: 0.1 us) against 0.8 us a
    # step under moe_experts, and 1.0 us a step under flash_attention
    assert _read("moe_experts_roofline_pct", run) == pytest.approx(100.0)
    assert _read("mla_attention_roofline_pct", run) == pytest.approx(80.0)
    # bound by bytes instead
    run = _run(tmp_path / "b", monkeypatch, work=(1.0, 0.4e6))
    assert _read("moe_experts_roofline_pct", run) == pytest.approx(50.0)


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
    assert _read("moe_experts_roofline_pct", bare) is None
    assert _read("moe_route_ms", bare) == pytest.approx(100 / 2e6)


# ------------------------------------------------------- the configuration

def _real():
    path = os.path.join(REPO, "chipbench", "configs", "joyai_llm_flash")
    traffic = harness.load_json(os.path.join(
        REPO, "chipbench", "workloads",
        "joyai-flash-train-ep16-b2-s4096.json"))["traffic"]
    return (harness.load_json(path + ".json"),
            harness.load_module(path + ".py"), traffic)


def test_the_configuration_keeps_every_published_width():
    config, _, _ = _real()
    published = {
        "hidden_size": 2048, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "v_head_dim": 128,
        "qk_head_dim": 192, "head_dim": 64, "intermediate_size": 7168,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_key_value_heads": 32, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "rope_theta": 32000000, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 131072, "n_group": 1, "topk_group": 1,
        "moe_layer_freq": 1, "ep_size": 1}
    assert {k: config[k] for k in published} == published
    assert config["router_outputs"] == 256      # the router stays whole
    assert sorted(config["reduced"]) == ["n_routed_experts",
                                         "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 129280 // 8)
    assert config["held_experts"] == [0, config["n_routed_experts"]]
    assert {"bias_update_gamma", "mtp_loss_weight", "optimizer_params",
            "initializer_std", "dtype", "buffer_factor",
            "seq_len"} <= set(config["assumed"])


def test_flops_per_step_against_xlas_count():
    config, model, traffic = _real()
    assert model.attention_params(config) == 26_345_472
    assert model.expert_params(config) == 4_718_592
    assert model.matmul_params(config) == pytest.approx(314.70e6, rel=1e-4)
    per_step = model.flops_per_sample(config, traffic) * traffic["batch"]
    assert per_step == pytest.approx(21.653e12, rel=1e-4)
    # XLA's own count of the compiled step for a described v5e (PR 30):
    # 14.965 TFLOP.  It counts nothing inside a Mosaic call, so it is held
    # to this count less the two kernels' parts (attention 6.185, the held
    # experts 0.580 TFLOP), and includes the elementwise work this leaves out
    attention, _ = model.mla_attention_work(config, traffic)
    experts, _ = model.moe_experts_work(config, traffic)
    assert abs(per_step - attention - experts - 14.965e12) / 14.965e12 < 0.02


def test_the_two_kernels_work_on_hand_computed_values():
    config, model, traffic = _real()
    # held experts: 8192 tokens x 8 x 16/256 = 4096 rows a routed block, 5
    # routed blocks, 6 operations a parameter a row
    ops, moved = model.moe_experts_work(config, traffic)
    assert ops == 5 * 6 * (3 * 2048 * 768) * 4096
    # bfloat16: 16 experts' weights read twice and their gradients written
    # once; a row's input, output and both gradients
    assert moved == 5 * 2 * (3 * 16 * 3 * 2048 * 768 + 4 * 4096 * 2048)
    # attention: 6 blocks x 8192 tokens x 32 heads x 3 x (192 + 128) x 4096
    ops, moved = model.mla_attention_work(config, traffic)
    assert ops == 6 * 8192 * 32 * 3 * 320 * 4096
    assert moved == 6 * 2 * 2 * 32 * 4096 * 3 * (2 * 192 + 2 * 128)


def test_batches_are_markov_documents_from_the_seed_alone():
    config, model, traffic = _real()
    a = model.make_batch(2 ** 31 + 11, 1, 2, config, traffic)
    b = model.make_batch(2 ** 31 + 11, 1, 2, config, traffic)
    c = model.make_batch(2 ** 31 + 11, 2, 2, config, traffic)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()
    tokens, labels = a
    assert tokens.shape == (2, 4097) and labels.shape == (2, 2, 4096)
    assert tokens.dtype == labels.dtype == onp.int32
    assert 0 <= tokens.min() and tokens.max() < 16160
    assert (labels[:, 0] == tokens[:, 1:]).all()
    assert (labels[:, 1, :-1] == tokens[:, 2:]).all()
    # order 1 with 4 successors: a token is followed by at most 4 others,
    # over both batches (one table a seed)
    follows = {}
    for doc in onp.concatenate([tokens, c[0]]):
        for t, nxt in zip(doc[:-1], doc[1:]):
            follows.setdefault(int(t), set()).add(int(nxt))
    assert max(len(v) for v in follows.values()) <= 4
    assert model.uniform_loss(config) == pytest.approx(1.3 * math.log(16160))
