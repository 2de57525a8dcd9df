"""The span and scope readers (``benchmark/spans.py``) on synthetic event
lists, and the ``op_name`` reader on the recorded trace of PR 23 (whose
program had flax's module scopes and none of the named ones)."""

import os
import types

import pytest

from benchmark import run, spans

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded", "train.xplane.pb")

STEP = "jit(train_step)/"
BWD = STEP + "transpose(jvp(Decoder))/while/body/closed_call/checkpoint/"


@pytest.mark.parametrize(
    "op_name, group",
    [
        (STEP + "jvp(Decoder)/while/body/closed_call/layers/layer/attn/wq/dot_general:", "attn"),
        (BWD + "rematted_computation/layers/layer/attn_norm/rsqrt:", "attn"),
        (BWD + "layers/layer/mlp/w_down/dot_general:", "mlp"),
        (BWD + "layers/layer/moe/moe.experts/dot_general:", "mlp"),
        (STEP + "jvp(loss)/jit(log_softmax)/reduce_sum:", "loss"),
        (STEP + "transpose(jvp(loss))/jit(take_along_axis)/scatter-add:", "loss"),
        (STEP + "transpose(jvp(Decoder))/lm_head/dot_general:", "loss"),
        (STEP + "jvp(Decoder)/final_norm/rsqrt:", "loss"),
        (STEP + "optimizer/add:", "optimizer"),
        (STEP + "transpose(jvp(Decoder))/while/body/dynamic_update_slice:", "scan"),
        (BWD + "rematted_computation/layers/layer/add:", "scan"),
        (STEP + "jvp(Decoder)/while:", "scan"),
        (STEP + "jvp(Decoder)/gather:", None),
        # a primitive or a parameter that spells a scope's name is not under it
        (STEP + "loss:", None),
        ("state.params['layers']['layer']['attn']['wq']['kernel'].value", None),
        ("", None),
        (None, None),
    ],
)
def test_scope_of(op_name, group):
    assert spans.scope_of(op_name, spans.TRAIN_GROUPS, spans.SCAN_GROUPS) == group


def fake_obs(ops, busy, spans_by_thread, gaps, lo=0, hi=1000):
    tl = spans.Timeline.__new__(spans.Timeline)
    tl.ops, tl.busy_ns, tl.lo, tl.hi = [ops], [busy], lo, hi
    tl.gaps, tl.threads, tl._scope_times = gaps, spans_by_thread, {}
    cell = types.SimpleNamespace(trace_dir=f"synthetic-{id(tl)}", chips=1)
    spans._LOADED[cell.trace_dir] = tl
    return {"cell": cell, "trace": {}, "needed_flops": 1.0}


def read(name, obs):
    return run.reader(name).read(obs)


SHARES = ("train.scope_attn_share", "train.scope_mlp_share", "train.scope_loss_share",
          "train.scope_optimizer_share", "train.scope_scan_share", "train.unscoped_share")


def test_scope_shares_partition_busy_time():
    ops = [
        (0, 100, "%a", BWD + "layers/layer/attn/wq/dot_general:"),
        (100, 350, "%b", BWD + "rematted_computation/layers/layer/mlp/w_up/dot_general:"),
        (350, 450, "%c", STEP + "jvp(loss)/jit(log_softmax)/sub:"),
        (450, 500, "%d", STEP + "optimizer/mul:"),
        (500, 520, "%e", STEP + "transpose(jvp(Decoder))/while/body/dynamic_update_slice:"),
        (520, 560, "%f", STEP + "jvp(Decoder)/gather:"),
        (560, 580, "%g", None),
    ]
    # 20 ns of busy time belong to no listed operation (a loop's own event)
    obs = fake_obs(ops, busy=600, spans_by_thread=[], gaps=[])
    got = {n: read(n, obs) for n in SHARES}
    assert got["train.scope_attn_share"] == pytest.approx(100 / 6)
    assert got["train.scope_mlp_share"] == pytest.approx(250 / 6)
    assert got["train.scope_loss_share"] == pytest.approx(100 / 6)
    assert got["train.scope_optimizer_share"] == pytest.approx(50 / 6)
    assert got["train.scope_scan_share"] == pytest.approx(20 / 6)
    assert got["train.unscoped_share"] == pytest.approx(80 / 6)
    assert sum(got.values()) == pytest.approx(100.0)
    assert read("train.recompute_share", obs) == pytest.approx(250 / 6)


def test_a_program_that_names_nothing_reads_none():
    obs = fake_obs([(0, 10, "%a", None), (10, 20, "%b", None)], busy=20, spans_by_thread=[], gaps=[])
    assert all(read(n, obs) is None for n in SHARES + ("train.recompute_share",))


def test_innermost_span_wins():
    nested = [(0, 100, "outer"), (10, 30, "inner"), (20, 25, "innermost"), (60, 70, "inner")]
    assert spans.innermost(nested) == [
        (0, 10, "outer"), (10, 20, "inner"), (20, 25, "innermost"), (25, 30, "inner"),
        (30, 60, "outer"), (60, 70, "inner"), (70, 100, "outer"),
    ]
    # a child that the clock lets outlast its parent is cut to it
    assert spans.innermost([(0, 10, "a"), (5, 12, "b"), (20, 30, "a")]) == [(0, 5, "a"), (5, 10, "b"), (20, 30, "a")]


def test_idle_goes_to_the_loop_threads_innermost_span():
    loop = [
        (0, 100, "train.fit_setup"),
        (100, 140, "train.input_wait"),
        (140, 150, "train_step"),
        (400, 520, "train.drain"),
        (600, 700, "train.fit_setup"),
        (620, 640, "train.drain"),  # overlapping spans: the inner one takes its part
    ]
    other = [(0, 1000, "shard_batch")]  # another thread's span names no idle time
    gaps = [(50, 120), (145, 160), (500, 530), (610, 650), (900, 1000)]
    obs = fake_obs([], busy=1, spans_by_thread=[other, loop], gaps=gaps)
    idle = spans.idle_by_span(gaps, loop)
    assert idle == {"train.fit_setup": 50 + 10 + 10, "train.input_wait": 20, "train_step": 5,
                    "train.drain": 20 + 20, None: 10 + 10 + 100}
    assert sum(idle.values()) == sum(e - s for s, e in gaps)
    assert read("train.idle_in_drain_share", obs) == pytest.approx(4.0)
    assert read("train.idle_in_input_share", obs) == pytest.approx(9.0)
    assert read("train.idle_unnamed_share", obs) == pytest.approx(12.0)
    assert read("train.dispatch_ms_p50", obs) == pytest.approx(10 / 1e6)


def test_without_program_spans_the_span_readers_read_none():
    obs = fake_obs([], busy=1, spans_by_thread=[[(0, 10, "shard_batch")]], gaps=[(0, 5)])
    for name in ("train.idle_in_drain_share", "train.idle_in_input_share",
                 "train.idle_unnamed_share", "train.dispatch_ms_p50"):
        assert read(name, obs) is None


NEW = SHARES + ("train.recompute_share", "train.idle_in_drain_share", "train.idle_in_input_share",
                "train.idle_unnamed_share", "train.dispatch_ms_p50")


@pytest.mark.parametrize("name", NEW)
def test_without_a_trace_every_reader_reads_none(name, tmp_path):
    cell = types.SimpleNamespace(trace_dir=str(tmp_path), chips=1)
    assert read(name, {"cell": cell, "needed_flops": 1.0}) is None  # --trace 0
    assert read(name, {"cell": cell, "trace": {}, "needed_flops": 1.0}) is None  # no file
    assert read(name, {}) is None


def test_gaps_of():
    assert spans.gaps_of([[10, 20], [20, 30], [50, 60]], 0, 100) == [(0, 10), (30, 50), (60, 100)]
    assert spans.gaps_of([], 5, 9) == [(5, 9)]


@pytest.fixture(scope="module")
def recorded():
    return spans.Timeline(RECORDED)


def test_op_names_are_read_from_the_event_metadata(recorded):
    table = spans.read_op_names(RECORDED)["/device:TPU:0"]
    assert len(table) > 250  # of 526 operations; the rest (copies, converts the compiler added) carry none
    flash = [v for k, v in table.items() if k.startswith("%flash_fwd")]
    assert flash and all(v.endswith("attn/jit(flash_attention)/flash_fwd/pallas_call:") for v in flash)
    assert any(spans.REMAT_MARKER in v for v in flash) and not all(spans.REMAT_MARKER in v for v in flash)


def test_recorded_trace_by_scope(recorded):
    """PR 23's program: module scopes only, so the optimizer and the
    log-softmax are unscoped there; the window and busy time are
    ``trace.reduce``'s."""
    assert recorded.window / 1e9 == pytest.approx(4.442836601)
    assert recorded.busy / 1e9 == pytest.approx(4.419552827)
    t = recorded.scope_time(spans.TRAIN_GROUPS, spans.SCAN_GROUPS)
    share = {k: v / recorded.busy * 100 for k, v in t.items()}
    assert share["mlp"] == pytest.approx(46.6, abs=0.1)
    assert share["attn"] == pytest.approx(24.4, abs=0.1)
    assert share["loss"] == pytest.approx(13.5, abs=0.1)
    assert "optimizer" not in share and share[None] == pytest.approx(14.3, abs=0.1)
    assert recorded.remat_time() / recorded.busy * 100 == pytest.approx(13.65, abs=0.05)
    assert sum(t.values()) <= recorded.busy
    # no program span in that trace: idle time has no name yet
    assert recorded.threads == [] and recorded.thread_of("train_step") is None
    assert sum(e - s for s, e in recorded.gaps) == recorded.window - recorded.busy
