"""The step-record readers (``benchmark/step_records.py``): each reduction on
hand-written records (a window mark, two verify steps before it, ten steps
inside it, a traced pair of waits and device runs), and the seven metric files
over a worker file written from the same records."""

import json
import os
import types

import pytest

from benchmark import run, step_records

HERE = os.path.dirname(os.path.abspath(__file__))
SEVEN = ("train.step_device_ms_mean", "train.step_device_ms_first", "train.step_device_ms_last",
         "train.window_in_steps_share", "train.step_done_lag_ms_p50", "train.moe_slots_mean",
         "train.moe_slots_growth")
EXPERT_CELLS = ["train-glm47flash-packed8k", "train-lfm2moe-packed8k", "train-keyevl2-long32k",
                "train-lagunas21-packed8k", "train-sdar30b-packed8k", "train-smallthinker21b-long16k"]
MARKS = {"process": 100.0, "devices": 105.0, "built": 120.0, "compiled": 130.0, "window": 140.0}
WINDOW_END, WINDOW_S = 190.0, 49.5


def span(name, ts, dur_s, tid=2, **attrs):
    return {"kind": "span", "name": name, "ts": ts, "dur_ms": dur_s * 1e3, "worker": "0", "tid": tid, "attrs": attrs}


def step(ts, dur_s, i, slots=None, **attrs):
    if slots is not None:
        attrs["moe_slots"] = float(slots)
    return span("train.step_device", ts, dur_s, step=i % 2, global_step=i, compiled=False, tokens=16384,
                loss=5.0 - 0.01 * i, **attrs)


# ten steps of a window from 140 to 190, two a ``fit`` call: the step slows
# from 4 to 6 s while the slots on held experts double
DURS = [4.0, 4.0, 4.5, 4.5, 5.0, 5.0, 5.5, 5.5, 6.0, 6.0]
SLOTS = [100, 100, 120, 120, 140, 160, 180, 190, 200, 200]
STARTS = [140.0 + sum(DURS[:i]) for i in range(10)]
RECORDS = [
    span("train.make_state", 107.0, 6.0, tid=1),  # a marker: the program records set-up
    step(125.0, 4.0, 0, 90), step(134.0, 4.0, 1, 95),  # the verify steps: before the window
    # a step that started before the mark and ended after it would be the window's; none does here
    *[step(STARTS[i], DURS[i], 2 + i, SLOTS[i]) for i in range(10)],
    span("train.step_wait", 140.0, 4.0, step=0),  # the live wait: not a step record
    span("train_step", 140.0, 0.002, tid=1, step=0),
    step(191.0, 4.0, 12, 300),  # after the window's end (another run's, or the reference's)
    {"kind": "gauge", "name": "step_time_ms", "ts": 144.0, "value": 4000.0, "worker": "0"},
]


def steps_of(records=RECORDS):
    return step_records.window_steps([r for r in records if r["kind"] == "span"], MARKS["window"], WINDOW_END)


# ------------------------------------------------------------------ reductions


def test_the_windows_steps_are_those_that_ended_in_it_in_order():
    steps = steps_of()
    assert [r["attrs"]["global_step"] for r in steps] == list(range(2, 12))
    shuffled = steps_of(RECORDS[::-1])
    assert [r["attrs"]["global_step"] for r in shuffled] == list(range(2, 12))


def test_a_step_belongs_to_the_window_its_end_lies_in():
    edge = [step(138.0, 4.0, 1), step(186.0, 4.0, 2), step(188.0, 4.0, 3)]
    assert [r["attrs"]["global_step"] for r in step_records.window_steps(edge, 140.0, 190.0)] == [1, 2]


@pytest.mark.parametrize("reduction, value, why", [
    (step_records.mean_ms, 5000.0, "the ten durations' mean"),
    (lambda s: step_records.mean_ms(s[:2]), 4000.0, "the first call's two steps"),
    (lambda s: step_records.mean_ms(s[-2:]), 6000.0, "the last call's two steps"),
    (lambda s: step_records.attr_mean(s, "moe_slots"), 151.0, "a count a step, every step"),
    (lambda s: step_records.growth(s, 2, "moe_slots"), 2.0, "the last two steps' mean over the first two's"),
    (lambda s: step_records.attr_mean(s, "loss"), 5.0 - 0.01 * 6.5, "any attribute"),
])
def test_reductions_by_hand(reduction, value, why):
    assert reduction(steps_of()) == pytest.approx(value), why


def test_a_model_without_the_counter_reads_nothing():
    dense = [step(STARTS[i], DURS[i], 2 + i) for i in range(10)]
    assert step_records.attr_mean(dense, "moe_slots") is None
    assert step_records.growth(dense, 2, "moe_slots") is None
    assert step_records.mean_ms([]) is None


def test_growth_from_no_slots_is_not_a_number():
    assert step_records.growth([step(140.0, 1.0, 0, 0), step(141.0, 1.0, 1, 10)], 1, "moe_slots") is None


# the traced pair: two runs of the step on the device's line, another program between
# them, and the waits that ended 0.2 and 0.4 ms after the runs did (ns, the trace's clock)
MODULES = [(1_000_000, 5_000_000, "jit_train_step(123)"), (5_000_100, 5_000_900, "jit_reseed(7)"),
           (5_001_000, 9_000_000, "jit_train_step(123)")]
WAITS = [(5_002_000, 9_400_000), (1_100_000, 5_200_000)]


def test_the_lag_pairs_waits_and_runs_in_order():
    runs = step_records.step_runs(MODULES)
    assert runs == [(1_000_000, 5_000_000), (5_001_000, 9_000_000)]
    assert step_records.done_lags_ms(WAITS, runs) == pytest.approx([0.2, 0.4])


def test_a_trace_with_another_number_of_runs_than_waits_pairs_nothing():
    assert step_records.done_lags_ms(WAITS, step_records.step_runs(MODULES)[:1]) == []
    assert step_records.done_lags_ms([], []) == [] and step_records.step_runs([]) == []


# ---------------------------------------------------------------- the files


def write(path, records):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def obs_of(**more):
    cell = types.SimpleNamespace(marks=dict(MARKS), window=[MARKS["window"], WINDOW_END],
                                 mix={"steps_per_chunk": 2}, trace_dir="/nowhere")
    return dict({"cell": cell, "window_s": WINDOW_S}, **more)


@pytest.fixture()
def recorded(tmp_path, monkeypatch):
    write(str(tmp_path / "app" / "1" / "telemetry" / "worker_0.jsonl"), RECORDS)
    monkeypatch.setenv("MAGGY_TPU_LOG_ROOT", str(tmp_path))
    return obs_of()


@pytest.mark.parametrize("name, value", [
    ("train.step_device_ms_mean", 5000.0),
    ("train.step_device_ms_first", 4000.0),
    ("train.step_device_ms_last", 6000.0),
    ("train.window_in_steps_share", 50.0 / WINDOW_S * 100.0),
    ("train.moe_slots_mean", 151.0),
    ("train.moe_slots_growth", 2.0),
])
def test_every_reader_reads_the_worker_file(recorded, name, value):
    assert run.reader(name).read(recorded) == pytest.approx(value)


def test_the_profilers_start_and_stop_are_not_the_windows(recorded):
    """A traced run: the profiler took 0.25 s to start, and 3 s to stop after the
    fourth step (which ended at 157): the steps' 50 s are held against 49.5 - 3.25."""
    recorded["cell"].trace_window = [140.25, 160.0]
    assert step_records.profiler_s(recorded, steps_of()) == pytest.approx(0.25 + 3.0)
    assert run.reader("train.window_in_steps_share").read(recorded) == pytest.approx(50.0 / (WINDOW_S - 3.25) * 100.0)
    recorded["cell"].trace_window = [None, None]  # an untraced run
    assert step_records.profiler_s(recorded, steps_of()) == 0.0


def test_the_lag_reader_reads_the_median_of_the_traced_steps(recorded, monkeypatch):
    recorded["trace"] = {"window_s": 8.0}
    monkeypatch.setattr(step_records, "traced_lags_ms", lambda obs: [0.2, 0.4, 0.9])
    assert run.reader("train.step_done_lag_ms_p50").read(recorded) == pytest.approx(0.4)


def test_the_lag_reader_reads_nothing_without_a_trace(recorded):
    assert run.reader("train.step_done_lag_ms_p50").read(recorded) is None  # no obs["trace"]
    recorded["trace"] = {"window_s": 8.0}
    assert run.reader("train.step_done_lag_ms_p50").read(recorded) is None  # no file under trace_dir


def test_the_recorded_trace_of_a_program_without_the_wait_reads_nothing():
    """``recorded/train.xplane.pb`` is a trace of the parent's program: device
    runs, and no ``train.step_wait`` on its host plane."""
    from benchmark import trace

    devices, hosts = trace.read_planes(os.path.join(HERE, "recorded", "train.xplane.pb"))
    waits = [ev for plane in hosts for line in plane.lines for ev in line.events if ev.name == step_records.WAIT]
    assert not waits
    runs = step_records.step_runs(trace.line_events(devices[0], "XLA Modules")) if devices else []
    assert step_records.done_lags_ms([], runs) == []


def test_a_program_without_the_step_records_reads_nothing(tmp_path, monkeypatch):
    """The parent commit: its worker file has set-up's spans and no ``train.step_device``."""
    old = [r for r in RECORDS if r.get("name") not in (step_records.STEP, step_records.WAIT)]
    write(str(tmp_path / "app" / "1" / "telemetry" / "worker_0.jsonl"), old)
    monkeypatch.setenv("MAGGY_TPU_LOG_ROOT", str(tmp_path))
    obs = obs_of(trace={"window_s": 8.0})
    assert [run.reader(n).read(obs) for n in SEVEN] == [None] * 7


@pytest.mark.parametrize("obs", [{}, {"cell": types.SimpleNamespace(marks={"process": 1.0}, window=[None, None])}])
def test_without_marks_or_files_nothing_is_read(obs, tmp_path, monkeypatch):
    monkeypatch.setenv("MAGGY_TPU_LOG_ROOT", str(tmp_path))
    assert [run.reader(n).read(obs) for n in SEVEN] == [None] * 7


def test_the_seven_are_in_the_contract():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert set(SEVEN) <= set(per_layer)
    for name in SEVEN:
        assert per_layer[name]["moves"] == "train_tok_s_chip"
    for name in SEVEN[:5]:
        assert per_layer[name]["layer"] == "Trainer" and per_layer[name]["workloads"] == cells
    for name in SEVEN[5:]:
        assert per_layer[name]["layer"] == "Models" and per_layer[name]["source"] == "program_counter"
        assert per_layer[name]["workloads"] == EXPERT_CELLS
    assert per_layer["train.step_done_lag_ms_p50"]["source"] == "device_trace"
    assert {per_layer[n]["source"] for n in SEVEN[:4]} == {"program_span"}
