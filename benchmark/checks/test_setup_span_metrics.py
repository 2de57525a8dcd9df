"""The set-up readers (``benchmark/setup_spans.py``): each reduction on a
small synthetic record list, and the nine metric files over two recorded
worker files, a cold set-up and a warm one of the same cell
(``recorded/setup_cold``, ``recorded/setup_warm``: ``run.py --rehearse-cpu``
with a compile cache, span records and the closing snapshot only, ``ts`` moved
so the process starts at 1,000 s. Toy sizes on the CPU: the records' shape is
the point, not their seconds)."""

import json
import os
import types

import pytest

from benchmark import run, setup_spans

HERE = os.path.dirname(os.path.abspath(__file__))
NINE = ("setup.make_state_s", "setup.trace_lower_s", "setup.backend_compile_s", "setup.cache_load_s",
        "setup.cache_misses", "setup.first_step_run_s", "setup.harness_compile_s", "setup.unnamed_s",
        "train.compile_ms_in_window")
MARKS = {"process": 100.0, "devices": 105.0, "built": 120.0, "compiled": 130.0, "window": 140.0}


def span(name, ts, dur_s, parent=None, tid=1, **attrs):
    rec = {"kind": "span", "name": name, "ts": ts, "dur_ms": dur_s * 1e3, "worker": "0", "tid": tid}
    if parent:
        rec["parent"] = parent
    if attrs:
        rec["attrs"] = attrs
    return rec


def backend(ts, dur_s, parent, cache, tid=1, **attrs):
    return span("compile.backend", ts, dur_s, parent, tid, fun_name="jit(f)", cache=cache, **attrs)


# one set-up, by hand: the process starts at 100, the backend is up at 105,
# the window runs from 140 to 190
RECORDS = [
    span("train_fn", 106.0, 90.0, partition=0),  # a wrapper: covers everything, names nothing
    span("train.make_state", 107.0, 6.0, "train_fn"),
    span("compile.trace", 107.0, 2.0, "train.make_state", fun_name="init_fn"),
    span("compile.trace", 107.5, 0.5, "train.make_state", fun_name="_normal"),  # inside init_fn's
    span("compile.trace", 109.0, 1.0, "train.make_state", fun_name="init_fn"),
    span("compile.lower", 110.0, 1.0, "train.make_state", fun_name="jit(init_fn)"),
    backend(111.0, 2.0, "train.make_state", "hit", cache_load_ms=1500.0),
    # the train function's own program: no span of the program's around it
    span("compile.trace", 114.0, 1.0, "train_fn", fun_name="reseed"),
    span("compile.lower", 115.0, 0.5, "train_fn", fun_name="jit(reseed)"),
    backend(115.5, 3.0, "train_fn", "miss"),
    span("train_step", 120.0, 8.0, "train_fn", step=0),
    span("compile.trace", 120.0, 2.0, "train_step", fun_name="train_step"),
    span("compile.lower", 122.0, 1.0, "train_step", fun_name="jit(train_step)"),
    backend(123.0, 4.0, "train_step", "miss"),
    span("train.drain", 128.0, 3.0, "train_fn", step=0, why="compile"),
    span("train.drain", 131.0, 0.5, "train_fn", why="return"),
    span("train_step", 132.0, 0.1, "train_fn", step=0),  # the second fit call: no compile
    # another thread compiles while the loop thread is inside make_state
    span("compile.lower", 110.5, 2.0, None, tid=2, fun_name="jit(g)"),
    # before the process's own start (an earlier run's record in the file) and inside the window
    span("compile.trace", 90.0, 5.0, None, fun_name="stale"),
    span("shard_batch", 138.0, 4.0, None, tid=3, step=7),  # crosses the window's start
    span("compile.lower", 150.0, 0.25, "train_step", fun_name="jit(train_step)"),
    backend(150.25, 0.5, "train_step", "miss"),
    {"kind": "gauge", "name": "step_time_ms", "ts": 150.0, "value": 1.0, "worker": "0"},
    {"kind": "snapshot", "worker": "0", "ts": 195.0, "counters": {"compile.cache_misses": 3, "compile.cache_hits": 1}},
]


@pytest.fixture()
def setup():
    return setup_spans.Setup(RECORDS, MARKS, window_end=190.0)


@pytest.mark.parametrize("reduction, value, why", [
    (setup_spans.make_state_s, 6.0, "the one train.make_state"),
    # loop thread: [107, 109) and [109, 110) trace, [110, 111) lower, reseed [114, 115.5),
    # the step [120, 123): 4 + 1.5 + 3; the other thread's lower 2; the stale trace clipped away
    (setup_spans.trace_lower_s, 10.5, "overlaps on a thread once, threads summed, clipped to the process"),
    (setup_spans.backend_compile_s, 7.0, "the two misses before the window; the hit and the late miss not"),
    (setup_spans.cache_load_s, 1.5, "the hit's cache_load_ms"),
    (setup_spans.cache_misses, 2, "the counter's 3 less the miss inside the window"),
    # 8 + 3 s of spans less the stages under them, [120, 127)
    (setup_spans.first_step_run_s, 4.0, "the first train_step and its drain, less their stages"),
    # reseed's [114, 118.5) on the loop thread and [110.5, 112.5) on the other
    (setup_spans.harness_compile_s, 6.5, "stages whose parent is a wrapper or none"),
    # [107, 113) [114, 118.5) [120, 131.5) [132, 132.1) and shard_batch's [138, 140)
    (setup_spans.named_s, 24.1, "the union over threads, wrappers left out, clipped to the window's start"),
    (setup_spans.unnamed_s, 35.0 - 24.1, "devices to window less what is named"),
    (setup_spans.compile_ms_in_window, 750.0, "the lower and the backend at 150"),
])
def test_reductions_by_hand(setup, reduction, value, why):
    assert reduction(setup) == pytest.approx(value), why


def test_union_counts_an_overlap_once():
    assert setup_spans.union_s([(0, 4), (1, 2), (3, 6), (8, 9)]) == 7
    assert setup_spans.union_s([]) == 0.0


def test_clip_to_process_start_and_window_start(setup):
    names = [r["attrs"].get("fun_name") for r in setup.before_window(setup_spans.STAGES)]
    assert "stale" not in names and names.count("jit(train_step)") == 1
    # a span that starts exactly at a mark belongs to what the mark opens
    edge = setup_spans.Setup([span("train.make_state", 140.0, 1.0), span("compile.trace", 100.0, 1.0)], MARKS, 190.0)
    assert setup_spans.make_state_s(edge) == 0.0 and setup_spans.trace_lower_s(edge) == 1.0


def test_parts_add_up(setup):
    """``setup.build_s + compile_s + warm_s`` (devices to window) is what the
    program's spans name plus ``setup.unnamed_s``."""
    phases = MARKS["window"] - MARKS["devices"]
    assert setup_spans.named_s(setup) + setup_spans.unnamed_s(setup) == pytest.approx(phases)


def test_a_step_that_never_compiled_has_no_first_run():
    s = setup_spans.Setup([span("train.make_state", 107.0, 1.0)], MARKS, 190.0)
    assert setup_spans.first_step_run_s(s) is None
    # a warm trainer: the first step traced nothing, so no drain follows it
    s = setup_spans.Setup([span("train.make_state", 107.0, 1.0), span("train_step", 110.0, 0.25)], MARKS, 190.0)
    assert setup_spans.first_step_run_s(s) == 0.25


# ---------------------------------------------------------------- the files


def recorded(which):
    """An ``obs`` whose cell carries the recorded run's marks."""
    with open(os.path.join(HERE, "recorded", f"setup_{which}", "marks.json")) as f:
        meta = json.load(f)
    cell = types.SimpleNamespace(marks=meta["marks"], window=[meta["marks"]["window"], meta["window_end"]])
    return {"cell": cell}


@pytest.fixture()
def log_root(monkeypatch):
    def point(which):
        # the files' modification times are real and the marks' clock is
        # made up, far earlier: "written since the process started" holds
        monkeypatch.setenv("MAGGY_TPU_LOG_ROOT", os.path.join(HERE, "recorded", f"setup_{which}"))
        return recorded(which)

    return point


@pytest.mark.parametrize("name", NINE)
@pytest.mark.parametrize("which", ["cold", "warm"])
def test_every_reader_reads_the_recorded_runs(log_root, which, name):
    value = run.reader(name).read(log_root(which))
    assert value is not None and value >= 0
    if name == "train.compile_ms_in_window":
        assert value == 0


def test_cold_and_warm_differ_where_they_should(log_root):
    cold = {n: run.reader(n).read(log_root("cold")) for n in NINE}
    warm = {n: run.reader(n).read(log_root("warm")) for n in NINE}
    assert cold["setup.cache_misses"] == 5 and warm["setup.cache_misses"] == 0
    assert cold["setup.backend_compile_s"] > 1.0 and warm["setup.backend_compile_s"] == 0.0
    assert cold["setup.cache_load_s"] == 0.0 and 0.0 < warm["setup.cache_load_s"] < cold["setup.backend_compile_s"]
    # the cache saves no tracing and no lowering
    assert warm["setup.trace_lower_s"] > 0.5 * cold["setup.trace_lower_s"]
    assert warm["setup.make_state_s"] < cold["setup.make_state_s"]
    for got in (cold, warm):
        assert got["setup.harness_compile_s"] > 0 and got["setup.first_step_run_s"] > 0


@pytest.mark.parametrize("which", ["cold", "warm"])
def test_recorded_parts_add_up(log_root, which):
    obs = log_root(which)
    s = setup_spans.load(obs)
    m = obs["cell"].marks
    assert setup_spans.named_s(s) + run.reader("setup.unnamed_s").read(obs) == pytest.approx(m["window"] - m["devices"])
    assert 0 < setup_spans.named_s(s) < m["window"] - m["devices"]


def write(path, records, mtime=None):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
        f.write('{"kind": "span", "name": "torn\n')
    if mtime is not None:
        os.utime(path, (mtime, mtime))


def cell_of(marks=MARKS, window_end=190.0):
    return {"cell": types.SimpleNamespace(marks=dict(marks), window=[marks.get("window"), window_end])}


def test_a_program_without_the_spans_reads_nothing(tmp_path, monkeypatch):
    """The parent commit: its worker file has spans, none of them set-up's."""
    old = [r for r in RECORDS if r.get("name") not in setup_spans.MARKERS]
    write(str(tmp_path / "app" / "1" / "telemetry" / "worker_0.jsonl"), old)
    monkeypatch.setenv("MAGGY_TPU_LOG_ROOT", str(tmp_path))
    obs = cell_of()
    assert [run.reader(n).read(obs) for n in NINE] == [None] * 9


def test_only_this_runs_files_are_read(tmp_path, monkeypatch):
    now = os.path.getmtime(str(tmp_path))
    marks = {k: v - MARKS["process"] + now - 50.0 for k, v in MARKS.items()}
    shift = marks["process"] - MARKS["process"]
    moved = [dict(r, ts=r["ts"] + shift) for r in RECORDS]
    write(str(tmp_path / "app_b" / "1" / "telemetry" / "worker_0.jsonl"), moved)
    # an earlier run of the same checkout left its file beside it
    write(str(tmp_path / "app_a" / "1" / "telemetry" / "worker_0.jsonl"), moved, mtime=marks["process"] - 600.0)
    write(str(tmp_path / "app_b" / "1" / "telemetry" / "driver.jsonl"), moved)  # not a worker's
    monkeypatch.setenv("MAGGY_TPU_LOG_ROOT", str(tmp_path))
    obs = cell_of(marks, marks["window"] + 50.0)
    assert run.reader("setup.make_state_s").read(obs) == pytest.approx(6.0)
    assert run.reader("setup.cache_misses").read(obs) == 2


@pytest.mark.parametrize("obs", [
    {}, {"cell": types.SimpleNamespace(marks={"process": 1.0}, window=[None, None])},
])
def test_without_marks_or_files_nothing_is_read(obs, tmp_path, monkeypatch):
    monkeypatch.setenv("MAGGY_TPU_LOG_ROOT", str(tmp_path))
    assert run.reader("setup.unnamed_s").read(obs) is None


def test_the_nine_are_in_the_contract():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NINE[:-1]:
        assert per_layer[name]["layer"] == "set-up" and per_layer[name]["moves"] == "setup_s"
        assert "workloads" not in per_layer[name] and per_layer[name]["better"] == "lower"
    last = per_layer[NINE[-1]]
    assert last["moves"] == "train_tok_s_chip" and last["workloads"] == per_layer["train.compiles_in_window"]["workloads"]
    assert list(per_layer)[-9:] == list(NINE)
