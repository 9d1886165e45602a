"""chip_smoke.py cannot pass without a chip, and its control flow is sound.

The script is the driver's proof that the system starts on the TPU; here, on
the CPU, what can be held to is that it FAILS closed (non-zero, no
``"ok": true``) unless every phase ran on a TPU, and that ``--rehearse`` — the
only way it tolerates a CPU — walks every phase end to end at toy sizes
(rehearsals 1 and 2 of the `on-chip-measurement` guide) without ever printing
the contract's result line.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*argv, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # the harness's 8 virtual devices
    return subprocess.run([sys.executable, SMOKE, *argv], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_fails_without_an_accelerator():
    proc = _run()
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no accelerator" in proc.stdout


@pytest.mark.parametrize("argv,count,phases", [
    ((), 1, ("[train] PASSED", "[kernels] PASSED", "[export] PASSED",
             "[serve] PASSED", "compile_count == 1", "compile_count 0")),
    (("--chips", "4"), 4, ("[dp4] PASSED", "all-reduce")),
])
def test_rehearsal_walks_every_phase_and_never_says_ok(argv, count, phases):
    proc = _run("--rehearse", *argv)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    for marker in phases:
        assert marker in proc.stdout, marker
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"rehearsal": True, "phases_passed": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": count}}
    assert '"ok"' not in proc.stdout
    if argv:    # --chips 4 runs the dp step and its comparison, nothing else
        assert "[train]" not in proc.stdout and "[serve]" not in proc.stdout


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "jax" not in vars(mod)       # the parent stays off JAX
    return mod


TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("failing", ["train", "kernels", "export", "serve"])
def test_any_failing_phase_fails_the_run(smoke, monkeypatch, capsys, failing):
    """Even with every other phase passing on a (pretend) TPU."""
    def run_child(name, args, env):
        if name == failing:
            raise smoke.PhaseFailed(f"{name}: injected mismatch")
        return {"device": TPU}

    def serve_and_query(args, env, device):
        if failing == "serve":
            raise smoke.PhaseFailed("serve: injected fallback")

    monkeypatch.setattr(smoke, "run_child", run_child)
    monkeypatch.setattr(smoke, "serve_and_query", serve_and_query)
    monkeypatch.setattr(smoke, "rebuild_native", lambda rehearse: None)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code == 1
    out = capsys.readouterr().out
    assert "injected" in out and '"ok"' not in out
    # the phases after the failing one still ran: one run shows everything
    phase = "serve" if failing == "export" else failing     # its first step
    assert f"phase(s) {phase} failed" in out


def test_last_line_is_the_contracts_when_every_phase_ran_on_a_tpu(
        smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "run_child",
                        lambda name, args, env: {"device": TPU})
    monkeypatch.setattr(smoke, "serve_and_query", lambda *a: None)
    monkeypatch.setattr(smoke, "rebuild_native", lambda rehearse: None)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    smoke.main()
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')


def test_a_phase_on_another_device_than_the_probe_fails(smoke, monkeypatch,
                                                        capsys):
    """A child that came up on the CPU while the probe saw the chip."""
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(
        smoke, "run_child",
        lambda name, args, env: {"device": cpu if name == "kernels" else TPU})
    monkeypatch.setattr(smoke, "serve_and_query", lambda *a: None)
    monkeypatch.setattr(smoke, "rebuild_native", lambda rehearse: None)
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code == 1
    assert '"ok"' not in capsys.readouterr().out
