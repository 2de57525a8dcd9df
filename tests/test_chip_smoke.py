"""chip_smoke.py off the chip: it must refuse to run on a CPU (no fallback, no
result line), and its explicitly requested toy rehearsal must drive every
phase — the control-flow check made before chip time is spent."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, timeout, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MAGGY_TPU_LOG_ROOT=str(tmp_path))
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, SMOKE, *args], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=timeout,
    )


@pytest.mark.slow
def test_refuses_a_cpu_and_rehearses_on_request(tmp_path):
    proc = _run([], tmp_path, timeout=120)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line to mistake for a pass

    proc = _run(
        ["--rehearse-on-cpu"], tmp_path, timeout=900,
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert all(line.startswith("[REHEARSAL") for line in lines[:-1])
    # the verdict line: exactly these keys, the device as JAX reports it
    device = {"platform": "cpu", "kind": "cpu", "count": 4}
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    summary = json.loads(lines[-2].split("summary: ", 1)[1])
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert summary["device"] == device
    assert list(summary["phases"]) == ["kernels", "trainer", "server", "hpo"]
    assert summary["phases"]["server"]["compile_counts"]["decode"] == 1
    assert summary["phases"]["hpo"]["leases"] == [0, 1, 2, 3]

    # a run that skips a phase can never pass
    proc = _run(["--rehearse-on-cpu", "--phases", "kernels"], tmp_path, timeout=300)
    assert proc.returncode != 0
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is False and set(verdict) == {"ok", "device"}
