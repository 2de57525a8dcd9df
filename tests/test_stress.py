"""Control-plane stress: many near-zero-cost trials, max concurrency, mixed
early stops and flaky errors, tiny heartbeat interval — shakes out scheduling
races (the double-execution and misattribution races fixed during development
were exactly this shape). SURVEY §5.2: the reference has no race detection;
this adversarial load is the substitute."""

import threading

import pytest

from maggy_tpu import Searchspace, experiment
from maggy_tpu.config import HyperparameterOptConfig

pytestmark = pytest.mark.slow  # subprocess/multi-process tier


def test_hpo_stress_no_lost_or_duplicated_trials(tmp_env):
    ran = []
    ran_lock = threading.Lock()

    def train(hparams, reporter):
        with ran_lock:
            ran.append(round(hparams["x"], 9))
        for step in range(3):
            reporter.broadcast(hparams["x"] + step * 1e-3, step=step)
        if hparams["x"] > 0.95:  # a few flaky trials
            raise ValueError("flaky")
        return hparams["x"]

    cfg = HyperparameterOptConfig(
        num_trials=64,
        optimizer="randomsearch",
        searchspace=Searchspace(x=("DOUBLE", [0.0, 1.0]), y=("DOUBLE", [0.0, 1.0])),
        direction="max",
        num_executors=8,
        es_policy="median",
        es_interval=0,
        es_min=5,
        hb_interval=0.01,
        seed=9,
    )
    result = experiment.lagom(train, cfg)
    # every trial ran exactly once: no duplicates, no losses
    assert result["num_trials"] == 64
    assert len(ran) == 64, f"{len(ran)} executions for 64 trials"
    assert len(set(ran)) == 64, "a trial executed twice"
    assert result["errors"] >= 1  # the flaky band above 0.95 fired
    assert result["best"]["metric"] <= 0.95  # errored trials never win


def test_asha_stress_budget_accounting(tmp_env):
    """ASHA under max concurrency: rung arithmetic must hold exactly."""
    budgets = []
    lock = threading.Lock()

    def train(hparams, budget, reporter):
        with lock:
            budgets.append(int(budget))
        reporter.broadcast(hparams["x"], step=0)
        return hparams["x"]

    cfg = HyperparameterOptConfig(
        num_trials=32,
        optimizer="asha",
        searchspace=Searchspace(x=("DOUBLE", [0.0, 1.0])),
        direction="max",
        num_executors=8,
        es_policy="none",
        hb_interval=0.01,
        seed=4,
    )
    result = experiment.lagom(train, cfg)
    assert budgets.count(1) == 32
    assert budgets.count(2) == 16
    assert budgets.count(4) == 8
    assert result["num_trials"] == 56


def test_asha_256_trials_scale(tmp_env):
    """BASELINE config-2 shape at control-plane scale: 256 ASHA trials with a
    small REAL train step (jitted ridge-regression GD, compiled once) through
    the full driver/RPC/executor path. Asserts completion without deadlock,
    no leaked executor/heartbeat threads, and monotone trial completion.
    Runs in well under 3 minutes on the CI CPU mesh."""
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def gd_steps(w, X, y, lr, n):
        def body(_, w):
            grad = X.T @ (X @ w - y) / X.shape[0]
            return w - lr * grad

        return jax.lax.fori_loop(0, n, body, w)

    X = jnp.array([[1.0, 0.5], [0.3, 2.0], [1.5, 1.0], [0.2, 0.8]])
    y = jnp.array([1.0, 2.0, 1.8, 0.9])

    completions = []  # budget of each trial, in completion order
    lock = threading.Lock()

    def train(hparams, budget, reporter):
        w = gd_steps(jnp.zeros(2), X, y, hparams["lr"], 4 * int(budget))
        loss = float(jnp.mean((X @ w - y) ** 2))
        reporter.broadcast(-loss, step=0)
        with lock:
            completions.append(int(budget))
        return -loss

    before_threads = threading.active_count()
    cfg = HyperparameterOptConfig(
        num_trials=256,
        optimizer="asha",
        searchspace=Searchspace(lr=("DOUBLE", [0.001, 0.4])),
        direction="max",
        num_executors=8,
        es_policy="none",
        hb_interval=0.01,
        seed=11,
    )
    t0 = time.monotonic()
    result = experiment.lagom(train, cfg)
    wall = time.monotonic() - t0
    assert wall < 180, f"256-trial ASHA took {wall:.1f}s"

    # rung arithmetic at reduction factor 2: 256 + 128 + 64 + 32 + 16 + ...
    assert result["num_trials"] >= 256
    assert len(completions) == result["num_trials"]
    # ASHA promotion ordering: a rung-(r+1) trial is only *suggested* after
    # reduction_factor times as many rung-r trials have finished, so at every
    # prefix of the completion sequence n_r >= 2 * n_{r+1} must hold
    budgets_seen = sorted(set(completions))
    counts = {bgt: 0 for bgt in budgets_seen}
    for bgt in completions:
        counts[bgt] += 1
        for lo, hi in zip(budgets_seen, budgets_seen[1:]):
            assert counts[lo] >= 2 * counts[hi], (
                f"rung inversion: {counts[lo]}x budget-{lo} vs "
                f"{counts[hi]}x budget-{hi}"
            )
    # all executor worker + heartbeat threads joined (small slack for the
    # daemonized asyncio server thread shared across experiments)
    time.sleep(0.5)
    assert threading.active_count() <= before_threads + 2, (
        f"{threading.active_count() - before_threads} leaked threads"
    )
    assert result["best"]["metric"] <= 0.0
