"""The trace reduction on a small recorded trace: 16 train steps of the
Mistral-7B training cell on one v5e (``recorded/train.xplane.pb``, PR 23)."""

import os
import shutil

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "recorded"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "recorded", "train.xplane.pb"), d / "host.xplane.pb")
    return trace.reduce(str(d.parent.parent.parent))


def test_window_and_busy(summary):
    assert summary["window_s"] == pytest.approx(4.442836601)
    assert summary["busy_s"] == pytest.approx(4.419552827)
    assert 0 < summary["busy_s"] <= summary["window_s"]


def test_programs_and_kernels_are_found_by_name(summary):
    steps = summary["module_s"]["jit_train_step"]
    assert len(steps) == 16 and sum(steps) == pytest.approx(4.4198, abs=1e-3)
    flash = {n.split(" ")[0].split(".")[0] for n, _ in summary["device_ops"] if n.startswith("flash_")}
    assert flash == {"flash_fwd", "flash_dq", "flash_dkv"}
    # the loops that hold the layers are busy time, not operations of their own
    assert not any(n.startswith("while") for n, _ in summary["device_ops"])
    assert sum(t for _, t in summary["device_ops"]) <= summary["busy_s"] * 1.0001


def test_idle_gaps_are_named_by_the_host(summary):
    gaps = dict(summary["idle_gaps"])
    assert gaps and sum(gaps.values()) <= summary["window_s"] - summary["busy_s"] + 1e-9
    assert all(isinstance(n, str) and n for n in gaps)


def test_interval_arithmetic():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace.covered([[0, 3], [5, 6]]) == 4
    assert trace.op_label("%fusion.7 = f32[2,4096]{1,0:T(8,128)} fusion(f32[2] %p)") == "fusion.7 f32[2,4096]"
    assert trace.is_container("%while.10 = (s32[], f32[2]) while(...)")


def test_a_trace_with_no_device_plane_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.reduce(str(tmp_path))
