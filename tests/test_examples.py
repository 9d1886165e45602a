"""The examples/ scripts must stay runnable (smoke mode)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "done" in proc.stdout
    return proc.stdout


def test_train_mnist_smoke():
    _run("train_mnist.py", "--smoke")


def test_train_transformer_lm_smoke():
    out = _run("train_transformer_lm.py", "--smoke", "--dp", "2",
               "--tp", "2", "--pp", "2")
    assert "loss" in out


def test_train_dist_kvstore_via_launcher():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "-s", "1", "--kv-mode", "sync",
         "--launcher", "local", sys.executable,
         os.path.join(REPO, "examples", "train_dist_kvstore.py")],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert proc.stdout.count("done") == 2


def test_benchmark_score_smoke():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"  # examples run on the host in tests
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "benchmark_score.py"),
         "--models", "squeezenet1_1", "--batch-sizes", "2",
         "--image-shape", "3,64,64", "--dtype", "float32",
         "--steps", "2", "--warmup", "1"],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "img/s" in proc.stdout and "FAILED" not in proc.stdout


def test_train_ssd_smoke():
    out = _run("train_ssd.py", "--smoke")
    assert "loss" in out and "detections" in out


def test_train_bert_smoke():
    out = _run("train_bert.py", "--smoke", "--amp")
    assert "loss" in out


@pytest.mark.slow
def test_train_resnet_fused_smoke():
    # heaviest subprocess smoke in the suite (161s of the 870s tier-1
    # budget measured in PR 12): a fresh python+jax process training 4
    # fused-conv steps.  The `slow` CI stage keeps it covered, same
    # split as the fleet-SIGKILL / session-chaos subprocess proofs.
    _run("train_resnet_fused.py", "--cpu", "--batch", "2",
         "--image-size", "32", "--steps", "4")


def test_docs_name_only_python_files_that_exist():
    """Every `*.py` path README.md and docs/performance.md name in
    backticks exists: with a directory, under the repo root or the
    package; a bare name, as some file's name; a glob matches a file."""
    import glob
    import re
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "__pycache__")]
        names.update(f for f in files if f.endswith(".py"))
    missing = []
    for doc in ("README.md", os.path.join("docs", "performance.md")):
        with open(os.path.join(REPO, doc)) as f:
            spans = re.findall(r"`([^`\n]+)`", f.read())
        for path in {p for s in spans
                     for p in re.findall(r"[\w.*/-]+\.py\b", s)}:
            if "/" not in path:
                found = path in names
            else:
                found = any(glob.glob(os.path.join(base, path))
                            for base in (REPO, os.path.join(
                                REPO, "incubator_mxnet_tpu")))
            if not found:
                missing.append((doc, path))
    assert not missing, missing
