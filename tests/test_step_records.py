"""One record a step from inside ``Trainer.fit``: the ``fit-steps`` thread's
``train.step_wait`` and ``train.step_device`` spans, the gauges it writes from
the same values, and what starts and ends it (docs/observability.md)."""

import dataclasses
import json
import os
import threading
import time

import jax
import numpy as np
import optax
import pytest

from maggy_tpu.models import sown
from maggy_tpu.telemetry import recorder as rec_mod
from maggy_tpu.telemetry.recorder import Telemetry
from maggy_tpu.train import trainer as trainer_mod
from tests.test_timeline import limit, tiny_trainer
from tests.test_tracing import load_tool

STEPS = 6
COUNTER_GAUGES = {key: name for row in sown.COUNTERS for key, name in row.gauges.items()}


@dataclasses.dataclass
class Run:
    records: list
    wall_ms: float
    out: dict
    step0: int

    def spans(self, name):
        return [r for r in self.records if r["kind"] == "span" and r["name"] == name]

    def gauges(self, name):
        return [r["value"] for r in self.records if r["kind"] == "gauge" and r["name"] == name]


def fit(trainer, state, data, steps=STEPS):
    """One ``fit`` call under a recorder of its own: the new state and the run."""
    tel = Telemetry(worker=0)
    step0 = int(state.step)
    with rec_mod.current(tel):
        t0 = time.perf_counter()
        state, out = trainer.fit(state, data, num_steps=steps)
        wall_ms = (time.perf_counter() - t0) * 1e3
    return state, Run(tel.drain_events(), wall_ms, out, step0)


@pytest.fixture(scope="module")
def runs():
    """A cold trainer's first ``fit`` of six steps and a warm second one."""
    trainer, state, data = tiny_trainer()
    state, cold = fit(trainer, state, data)
    _state, warm = fit(trainer, state, data)
    return cold, warm


@pytest.fixture(scope="module")
def moe_run():
    """Six steps of a toy expert share model, whose layers sow step counters."""
    from maggy_tpu.models import MoEConfig, MoEDecoder
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.data import synthetic_lm_batches

    cfg = MoEConfig.tiny_moe(experts_held=2, moe_d_ff=32)
    trainer = TrainContext.create("dp").trainer(MoEDecoder(cfg), optax.adamw(1e-3))
    data = synthetic_lm_batches(cfg.vocab_size, 8, 32, seed=0)
    state = trainer.make_state(jax.random.key(0), next(data))
    return fit(trainer, state, data)[1]


# ------------------------------------------------------------ the step spans


@limit(180)
def test_one_step_device_span_a_step_in_step_order(runs):
    for run in runs:
        steps = run.spans("train.step_device")
        assert [r["attrs"]["step"] for r in steps] == list(range(STEPS))
        assert [r["attrs"]["global_step"] for r in steps] == [run.step0 + i for i in range(STEPS)]


@limit(180)
def test_no_step_starts_before_its_predecessors_end(runs):
    for run in runs:
        steps = run.spans("train.step_device")
        for a, b in zip(steps, steps[1:]):
            assert b["ts"] >= a["ts"] + a["dur_ms"] / 1e3 - 1e-6


@limit(180)
def test_step_durations_sum_to_no_more_than_the_calls_wall_time(runs):
    for run in runs:
        total = sum(r["dur_ms"] for r in run.spans("train.step_device"))
        assert 0 < total <= run.wall_ms


@limit(180)
def test_one_live_wait_a_step_on_a_thread_of_its_own(runs):
    for run in runs:
        waits = run.spans("train.step_wait")
        assert [r["attrs"]["step"] for r in waits] == list(range(STEPS))
        loop_tids = {r["tid"] for r in run.spans("train_step")}
        assert len({r["tid"] for r in waits}) == 1 and not {r["tid"] for r in waits} & loop_tids
        # the journaled span lies on the same thread and ends where its wait ended
        for wait, step in zip(waits, run.spans("train.step_device")):
            assert step["tid"] == wait["tid"]
            assert step["ts"] + step["dur_ms"] / 1e3 == pytest.approx(wait["ts"] + wait["dur_ms"] / 1e3, abs=5e-3)


@limit(180)
def test_step_attributes_are_the_steps_own_output(runs):
    cold, _warm = runs
    steps = cold.spans("train.step_device")
    assert steps[-1]["attrs"]["loss"] == cold.out["loss"]
    assert all(r["attrs"]["tokens"] == 8 * 32 for r in steps)
    losses = [r["attrs"]["loss"] for r in steps]
    assert len(set(losses)) == STEPS  # every step's own reading, not the last one's six times
    assert "mtp_loss" not in steps[0]["attrs"] and not set(steps[0]["attrs"]) & set(COUNTER_GAUGES)


@limit(180)
def test_only_the_step_that_compiled_says_so(runs):
    cold, warm = runs
    assert [r["attrs"]["compiled"] for r in cold.spans("train.step_device")] == [True] + [False] * (STEPS - 1)
    assert not any(r["attrs"]["compiled"] for r in warm.spans("train.step_device"))


@limit(180)
def test_step_time_is_gauged_once_a_step_that_did_not_compile(runs):
    cold, warm = runs
    assert len(cold.gauges("step_time_ms")) == STEPS - 1 and len(warm.gauges("step_time_ms")) == STEPS


@limit(180)
def test_compile_time_is_gauged_once_for_the_step_that_compiled(runs):
    cold, warm = runs
    assert len(cold.gauges("compile_time_ms")) == 1 and not warm.gauges("compile_time_ms")
    # the compiled step's span runs from its dispatch, so it covers the compile
    assert cold.gauges("compile_time_ms")[0] == cold.spans("train.step_device")[0]["dur_ms"]


@limit(180)
def test_step_time_is_the_spans_duration(runs):
    _cold, warm = runs
    assert warm.gauges("step_time_ms") == [r["dur_ms"] for r in warm.spans("train.step_device")]


@limit(180)
def test_the_watchers_records_carry_the_runs_trace_id(runs):
    for run in runs:
        (trace,) = {r["trace"] for r in run.spans("train_step")}
        assert {r["trace"] for r in run.spans("train.step_device") + run.spans("train.step_wait")} == {trace}


@limit(180)
def test_the_loop_waits_for_the_watcher_inside_the_return_drain(runs):
    for run in runs:
        (drain,) = [r for r in run.spans("train.drain") if r["attrs"].get("why") == "return"]
        last = run.spans("train.step_device")[-1]
        assert drain["ts"] + drain["dur_ms"] / 1e3 >= last["ts"] + last["dur_ms"] / 1e3 - 1e-6


# -------------------------------------------------------- the step counters


@limit(240)
def test_last_spans_slots_are_the_calls_own(moe_run):
    assert moe_run.spans("train.step_device")[-1]["attrs"]["moe_slots"] == moe_run.out["moe_slots"] > 0


@limit(240)
def test_every_step_counter_is_an_attribute_of_every_step(moe_run):
    counters = set(COUNTER_GAUGES) & set(moe_run.out)
    assert {"moe_slots", "moe_slots_dropped", "moe_load_max_over_mean", "moe_rows_visited_share", "moe_combine_rows_share"} <= counters
    for r in moe_run.spans("train.step_device"):
        assert counters <= set(r["attrs"])


@limit(240)
def test_every_counters_gauge_is_written_every_step(moe_run):
    for key in set(COUNTER_GAUGES) & set(moe_run.out):
        values = moe_run.gauges(COUNTER_GAUGES[key])
        assert values == [r["attrs"][key] for r in moe_run.spans("train.step_device")], key


# ------------------------------------------------- what starts and ends it


@pytest.fixture()
def thread_names(monkeypatch):
    """The name of every thread ``train/trainer.py`` starts."""
    names = []

    class Named(threading.Thread):
        def start(self):
            names.append(self.name)
            super().start()

    monkeypatch.setattr(trainer_mod.threading, "Thread", Named)
    return names


@limit(180)
def test_a_live_recorder_starts_one_watcher_a_call(thread_names):
    trainer, state, data = tiny_trainer()
    fit(trainer, state, data, steps=2)
    assert thread_names.count("fit-steps") == 1
    assert not [t for t in threading.enumerate() if t.name == "fit-steps"]


@limit(180)
def test_no_watcher_under_null_telemetry(thread_names, monkeypatch):
    monkeypatch.setenv("MAGGY_TPU_TELEMETRY", "0")
    trainer, state, data = tiny_trainer()
    with rec_mod.current(None):
        assert not rec_mod.get().active
        _state, out = trainer.fit(state, data, num_steps=3)
    assert "fit-steps" not in thread_names and out["loss"] > 0


def raising_after(data, n):
    for _ in range(n):
        yield next(data)
    raise RuntimeError("the input pipeline broke")


@limit(180)
@pytest.mark.parametrize("prefetch", [0, 2])
def test_a_step_that_raises_leaves_no_watcher_behind(prefetch):
    trainer, state, data = tiny_trainer()
    tel = Telemetry(worker=0)
    with rec_mod.current(tel), pytest.raises(RuntimeError, match="input pipeline broke"):
        trainer.fit(state, raising_after(data, 3), num_steps=STEPS, prefetch=prefetch)
    assert not [t for t in threading.enumerate() if t.name == "fit-steps"]
    # the steps that ran are on record all the same
    steps = [r for r in tel.drain_events() if r["kind"] == "span" and r["name"] == "train.step_device"]
    assert [r["attrs"]["step"] for r in steps] == [0, 1, 2]


# ------------------------------------------------------------- the readers


@limit(180)
def test_analyze_trace_reads_the_steps_time_not_the_dispatch(tmp_path, monkeypatch, capsys):
    """A device that takes 50 ms a step (the watcher's wait, slowed): the
    report's ``step_ms_mean`` is that, within the call's wall time over its
    steps, where the dispatch of the tiny step takes a few ms."""
    trainer, state, data = tiny_trainer()
    state, _ = fit(trainer, state, data, steps=1)  # compile outside the measured call
    real = jax.block_until_ready

    def slow(x):
        if threading.current_thread().name == "fit-steps":
            time.sleep(0.05)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", slow)
    _state, run = fit(trainer, state, data)
    os.makedirs(tmp_path / "telemetry")
    with open(tmp_path / "telemetry" / "worker_0.jsonl", "w") as f:
        for r in run.records:
            f.write(json.dumps(r) + "\n")
    assert load_tool("analyze_trace").main([str(tmp_path), "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)["step_summary"]
    assert summary["steps"] == STEPS
    assert 50.0 <= summary["step_ms_mean"] <= run.wall_ms / STEPS
    assert summary["step_ms_mean"] == pytest.approx(np.mean([r["dur_ms"] for r in run.spans("train.step_device")]))
