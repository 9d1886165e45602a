"""CPU↔TPU check_consistency battery (SURVEY §4: the cross-backend
oracle, reference test_utils.py:1428 run with ctx_list=[cpu, gpu]).

Runs a small subset of scripts/tpu_consistency.py in a subprocess.  The
battery proper targets JAX's own ``tpu`` platform and therefore skips in
the tier-1 suite, which forces the CPU: the decision is taken inside the
test body from ``JAX_PLATFORMS`` alone — nothing here loads the TPU's
library to find out.  The harness itself (chunking, result parsing, the
artifact, exit codes) is exercised with the CPU compared against itself.
The on-chip consistency check that runs with every PR is
``chip_smoke.py``'s ``kernels`` phase.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SUBSET = "relu,dot,Convolution,BatchNorm,softmax,LayerNorm,take,topk"


def _battery(tmp_path, **env_overrides):
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""), **env_overrides)
    env.pop("XLA_FLAGS", None)
    out_path = str(tmp_path / "consistency.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tpu_consistency.py"),
         "--ops", SUBSET, "--deadline", "360", "--out", out_path],
        capture_output=True, text=True, timeout=420, env=env)
    return proc, out_path


def test_cpu_tpu_consistency_battery(tmp_path):
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        pytest.skip("JAX_PLATFORMS forces the CPU: no TPU to compare with")
    proc, out_path = _battery(tmp_path)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-500:])
    with open(out_path) as f:
        doc = json.load(f)
    assert doc["failed"] == 0 and doc["passed"] >= 1, doc


def test_harness_self_test_on_the_cpu(tmp_path):
    proc, out_path = _battery(tmp_path, JAX_PLATFORMS="cpu",
                              CONSIST_SELF_TEST="1")
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-500:])
    with open(out_path) as f:
        doc = json.load(f)
    assert doc["failed"] == 0 and doc["passed"] == len(SUBSET.split(","))


def test_refuses_without_a_tpu(tmp_path):
    proc, _ = _battery(tmp_path, JAX_PLATFORMS="cpu")
    assert proc.returncode == 2
    assert "no accelerator visible" in proc.stdout
