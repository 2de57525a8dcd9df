"""Pod-mode HPO: remote trial executors + worker capacity recovery (VERDICT
r4 item 3). The reference gets cross-host trial executors and failed-task
re-execution from Spark (spark_driver.py:136-145, rpc.py:415-437); here any
host running the same script with MAGGY_TPU_ROLE=worker adds trial capacity,
a killed worker's trial is freed (re-registration or liveness timeout), and
a respawned worker rejoins the live experiment."""

import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from maggy_tpu import Searchspace, experiment
from maggy_tpu.config import HyperparameterOptConfig

pytestmark = pytest.mark.slow  # subprocess/multi-process tier

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

HPO_WORKER_SCRIPT = textwrap.dedent(
    """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from maggy_tpu import Searchspace, experiment
    from maggy_tpu.config import HyperparameterOptConfig

    def train(hparams, reporter):
        reporter.broadcast(float(hparams["x"]), step=0)
        time.sleep({trial_s})
        return {{"metric": float(hparams["x"])}}

    result = experiment.lagom(
        train,
        HyperparameterOptConfig(
            num_trials=10,
            optimizer="randomsearch",
            searchspace=Searchspace(x=("DOUBLE", [0.0, 1.0])),
            direction="max",
            es_policy="none",
            num_executors=2,
            hb_interval=0.05,
        ),
    )
    print("WORKER-DONE", result, flush=True)
    """
)


def _driver_config(worker_timeout=600.0, num_trials=30):
    return HyperparameterOptConfig(
        num_trials=num_trials,
        optimizer="randomsearch",
        searchspace=Searchspace(x=("DOUBLE", [0.0, 1.0])),
        direction="max",
        es_policy="none",
        num_executors=2,
        hb_interval=0.05,
        driver_addr="127.0.0.1:auto",  # placeholder: flags pod mode
        worker_timeout=worker_timeout,
    )


def _start_driver(result_holder, worker_timeout=600.0, trial_s=0.3, num_trials=30):
    def train(hparams, reporter):
        reporter.broadcast(float(hparams["x"]), step=0)
        time.sleep(trial_s)
        return {"metric": float(hparams["x"])}

    def run_driver():
        try:
            result_holder["result"] = experiment.lagom(
                train, _driver_config(worker_timeout, num_trials)
            )
        except BaseException as e:  # noqa: BLE001 - surfaced by the test
            result_holder["error"] = e

    t = threading.Thread(target=run_driver)
    t.start()
    deadline = time.time() + 30
    driver = None
    while time.time() < deadline:
        driver = experiment.CURRENT_DRIVER
        if driver is not None and driver.server is not None and driver.server.port:
            break
        time.sleep(0.05)
    assert driver is not None and driver.server is not None, "driver never started"
    assert driver.pod_mode
    return t, driver


def _worker_env(driver, tmp_path, partition="1"):
    env = dict(os.environ)
    env.update(
        {
            "MAGGY_TPU_ROLE": "worker",
            "MAGGY_TPU_DRIVER": f"127.0.0.1:{driver.server.port}",
            "MAGGY_TPU_SECRET": driver.server.secret,
            "MAGGY_TPU_PARTITION": partition,
            "MAGGY_TPU_LOG_ROOT": os.environ.get("MAGGY_TPU_LOG_ROOT", str(tmp_path)),
        }
    )
    return env


def _spawn_worker(script_path, env):
    return subprocess.Popen(
        [sys.executable, str(script_path)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def test_pod_hpo_worker_kill_and_respawn_completes_all_trials(tmp_env, tmp_path):
    """Kill a remote trial worker mid-ASHA-style run, respawn it (what
    ``maggy_tpu.run --respawn`` does): the respawned worker re-registers
    (fresh attempt nonce), the lost trial is freed, and the experiment ends
    with the FULL trial count."""
    result_holder = {}
    t, driver = _start_driver(result_holder, trial_s=0.4)

    script = tmp_path / "worker.py"
    script.write_text(HPO_WORKER_SCRIPT.format(repo=REPO, trial_s=0.4))
    env = _worker_env(driver, tmp_path)

    victim = _spawn_worker(script, env)
    time.sleep(2.0)  # well into the 30x0.4s trial stream
    victim.kill()
    victim.wait(timeout=30)

    # capacity recovery: the supervisor's respawn, into the LIVE experiment
    replacement = _spawn_worker(script, env)
    out, _ = replacement.communicate(timeout=120)
    assert replacement.returncode == 0, out[-2000:]

    t.join(timeout=120)
    assert not t.is_alive(), "driver did not finish"
    assert "error" not in result_holder, result_holder.get("error")
    result = result_holder["result"]
    # full trial count: budget completes despite the kill; at most the one
    # in-flight trial is ERROR (reference loses exactly the in-flight task)
    assert result["num_trials"] == 30
    assert result.get("errors", 0) <= 1
    assert result["best"] is not None


def test_pod_hpo_dead_worker_liveness_frees_trial_and_completes(tmp_env, tmp_path):
    """No respawn at all: the liveness sweep (worker_timeout) frees the dead
    worker's trial and the remaining capacity finishes the budget — the
    driver must NOT hang or abort."""
    result_holder = {}
    t, driver = _start_driver(
        result_holder, worker_timeout=2.0, trial_s=0.3, num_trials=20
    )

    script = tmp_path / "worker.py"
    script.write_text(HPO_WORKER_SCRIPT.format(repo=REPO, trial_s=0.3))
    victim = _spawn_worker(script, _worker_env(driver, tmp_path))
    time.sleep(2.0)
    victim.kill()
    victim.wait(timeout=30)

    t.join(timeout=120)
    assert not t.is_alive(), "driver hung after worker death"
    assert "error" not in result_holder, result_holder.get("error")
    result = result_holder["result"]
    assert result["num_trials"] == 20
    assert result.get("errors", 0) <= 1


RESPAWN_SCRIPT = textwrap.dedent(
    """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    sentinel = {sentinel!r}
    if os.environ.get("MAGGY_TPU_ROLE") == "worker" and not os.path.exists(sentinel):
        open(sentinel, "w").close()
        sys.exit(3)  # simulated crash before joining
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from maggy_tpu import Searchspace, experiment
    from maggy_tpu.config import HyperparameterOptConfig

    def train(hparams, reporter):
        reporter.broadcast(float(hparams["x"]), step=0)
        time.sleep(0.1)
        return {{"metric": float(hparams["x"])}}

    result = experiment.lagom(
        train,
        HyperparameterOptConfig(
            num_trials=40,
            optimizer="randomsearch",
            searchspace=Searchspace(x=("DOUBLE", [0.0, 1.0])),
            direction="max",
            es_policy="none",
            num_executors=2,
            hb_interval=0.05,
        ),
    )
    print("RESULT", result, flush=True)
    """
)


def test_run_launcher_respawn_recovers_worker(tmp_path):
    """`python -m maggy_tpu.run --respawn`: a worker rank that dies is
    respawned into the LIVE experiment (driver keeps running) and the run
    completes all trials."""
    sentinel = str(tmp_path / "crashed_once")
    script = tmp_path / "user_script.py"
    script.write_text(RESPAWN_SCRIPT.format(repo=REPO, sentinel=sentinel))
    env = dict(os.environ)
    env["MAGGY_TPU_LOG_ROOT"] = str(tmp_path / "logs")
    env["MAGGY_TPU_CONNECT_TIMEOUT"] = "30"  # bound a worker-vs-done race
    proc = subprocess.run(
        [
            sys.executable, "-m", "maggy_tpu.run",
            "--workers", "2", "--respawn", "2", str(script),
        ],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=180,
    )
    assert os.path.exists(sentinel), "worker never took the crash path"
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    # both ranks print RESULT: the driver's carries the study summary, the
    # worker's its role marker
    result_lines = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")]
    assert result_lines, proc.stdout[-2000:]
    assert any("'num_trials': 40" in l for l in result_lines), result_lines
    assert any("'role': 'trial_worker'" in l for l in result_lines), result_lines
    assert "respawning into the live experiment" in proc.stderr


LEASE_WORKER_SCRIPT = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    from maggy_tpu import Searchspace, experiment
    from maggy_tpu.config import HyperparameterOptConfig

    SERVED = [0]

    def train(hparams, reporter, ctx, devices):
        # the lease must be exactly the two devices named in
        # MAGGY_TPU_WORKER_DEVICES, and the injected ctx's mesh spans it
        assert len(devices) == 2, devices
        assert len(list(ctx.mesh.devices.flat)) == 2
        SERVED[0] += 1
        reporter.broadcast(float(hparams["x"]), step=0)
        return {{"metric": float(hparams["x"])}}

    result = experiment.lagom(
        train,
        HyperparameterOptConfig(
            num_trials=4,
            optimizer="randomsearch",
            searchspace=Searchspace(x=("DOUBLE", [0.0, 1.0])),
            direction="max",
            es_policy="none",
            num_executors=2,
            hb_interval=0.05,
        ),
    )
    print("LEASE-WORKER-DONE served", SERVED[0], flush=True)
    """
)


def test_pod_worker_device_lease(tmp_env, tmp_path):
    """MAGGY_TPU_WORKER_DEVICES leases a sub-slice of the worker host's
    devices to the remote trial executor — several workers can share one
    host, each trial training on its own devices."""
    result_holder = {}
    t, driver = _start_driver(result_holder, trial_s=0.4, num_trials=30)

    script = tmp_path / "worker.py"
    script.write_text(LEASE_WORKER_SCRIPT.format(repo=REPO))
    env = _worker_env(driver, tmp_path)
    env["MAGGY_TPU_WORKER_DEVICES"] = "1,2"
    worker = _spawn_worker(script, env)
    out, _ = worker.communicate(timeout=120)
    assert worker.returncode == 0, out[-2000:]
    assert "LEASE-WORKER-DONE" in out
    served = int(out.split("LEASE-WORKER-DONE served")[1].split()[0])
    assert served > 0, out[-1500:]  # the lease asserts must have actually run

    t.join(timeout=120)
    assert "error" not in result_holder, result_holder.get("error")
    assert result_holder["result"]["num_trials"] == 30
