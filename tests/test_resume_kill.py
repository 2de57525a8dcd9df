"""Experiment resume after a hard driver kill.

A subprocess runs a seeded random-search HPO and SIGKILLs ITSELF (driver,
server, and executor threads all die — the ungraceful crash) once enough
trials have been persisted. A second subprocess resumes via ``resume_from``
and must finish the experiment WITHOUT re-running any persisted trial
(``core/driver/hpo.py`` preload + suggestion-skip path).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess/multi-process tier

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

RUN_SCRIPT = textwrap.dedent(
    """
    import json, os, signal, sys, threading
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")  # the env var alone can lose
    # to an accelerator plugin's auto-registration

    from maggy_tpu import Searchspace, experiment
    from maggy_tpu.config import HyperparameterOptConfig

    KILL_AFTER = int(os.environ.get("MT_KILL_AFTER", "0"))
    ran_file = os.environ["MT_RAN_FILE"]
    lock = threading.Lock()

    def train(hparams, reporter):
        with lock:
            with open(ran_file, "a") as f:
                f.write(json.dumps(hparams) + "\\n")
        reporter.broadcast(hparams["x"], step=0)
        return hparams["x"]

    def killer():
        # SIGKILL the whole process (driver + executors) the moment enough
        # trials have PERSISTED — trial.json is the resume source of truth
        import time
        exp_dir = os.environ["MT_EXP_DIR"]
        while True:
            n = 0
            if os.path.isdir(exp_dir):
                for name in os.listdir(exp_dir):
                    if os.path.exists(os.path.join(exp_dir, name, "trial.json")):
                        n += 1
            if n >= KILL_AFTER:
                os.kill(os.getpid(), signal.SIGKILL)
            time.sleep(0.01)

    if KILL_AFTER:
        threading.Thread(target=killer, daemon=True).start()

    cfg = HyperparameterOptConfig(
        num_trials=16,
        optimizer="randomsearch",
        searchspace=Searchspace(x=("DOUBLE", [0.0, 1.0]), y=("DOUBLE", [0.0, 1.0])),
        direction="max",
        num_executors=2,
        es_policy="none",
        hb_interval=0.02,
        seed=21,
        resume_from=os.environ.get("MT_RESUME_FROM") or None,
    )
    result = experiment.lagom(train, cfg)
    print("DONE", result["num_trials"], flush=True)
    """
).format(repo=REPO)


def _persisted_params(exp_dir):
    out = []
    for name in os.listdir(exp_dir):
        path = os.path.join(exp_dir, name, "trial.json")
        if os.path.exists(path):
            try:
                with open(path) as f:
                    rec = json.load(f)
            except json.JSONDecodeError:
                # SIGKILL mid-write can truncate the newest record; the
                # production loader tolerates this too (load_finalized_trials)
                continue
            if rec.get("status") == "FINALIZED":
                out.append(tuple(sorted(rec["params"].items())))
    return out


def test_resume_after_sigkill(tmp_path):
    script = tmp_path / "hpo_script.py"
    script.write_text(RUN_SCRIPT)
    app_dir = tmp_path / "logs" / "application_resume_test_0001" / "1"

    env = dict(os.environ)
    env.update(
        {
            "MAGGY_TPU_LOG_ROOT": str(tmp_path / "logs"),
            "MAGGY_TPU_APP_ID": "application_resume_test_0001",
            "MAGGY_TPU_RUN_ID": "1",
            "MT_EXP_DIR": str(app_dir),
            "MT_RAN_FILE": str(tmp_path / "ran1.jsonl"),
            "MT_KILL_AFTER": "6",
            "JAX_PLATFORMS": "cpu",
        }
    )
    env.pop("XLA_FLAGS", None)
    first = subprocess.run(
        [sys.executable, str(script)],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert first.returncode == -9, (first.returncode, first.stderr[-1000:])
    persisted = _persisted_params(str(app_dir))
    # killer fired at 6 files on disk; the newest may be truncated mid-write
    assert len(persisted) >= 5
    assert len(persisted) < 16, "crash came too late to exercise resume"

    # resume into a fresh run dir, same seed -> same suggestion stream
    env2 = dict(env)
    env2.update(
        {
            "MAGGY_TPU_APP_ID": "application_resume_test_0002",
            "MT_RAN_FILE": str(tmp_path / "ran2.jsonl"),
            "MT_KILL_AFTER": "0",
            "MT_EXP_DIR": str(tmp_path / "unused"),
            "MT_RESUME_FROM": str(app_dir),
        }
    )
    second = subprocess.run(
        [sys.executable, str(script)],
        env=env2,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert second.returncode == 0, second.stderr[-2000:]
    assert "DONE 16" in second.stdout, second.stdout[-500:]

    with open(tmp_path / "ran2.jsonl") as f:
        reran = [tuple(sorted(json.loads(l).items())) for l in f]
    # nothing that survived the crash ran again...
    overlap = set(persisted) & set(reran)
    assert not overlap, f"{len(overlap)} persisted trials re-ran"
    # ...and together they cover the full experiment
    assert len(set(persisted) | set(reran)) == 16
