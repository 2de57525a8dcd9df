"""Drives the ``train_packed_ref`` kind end to end at toy sizes on the CPU for
the cell ``train-keyevl2-long32k``, as ``test_train_lfm2.py`` does for the
LFM2 cell: the ``Cell`` is built from ``checks/tiny.keye-vl-2.0-30b-a3b.json``
with ``run.merge``; a sound run is judged correct with its counters read, both
controls are judged not correct; the five readers this cell brings read a
synthetic timeline, and find nothing (and do not raise) in the recorded trace
of a program that has none of their scopes and kernels."""

import argparse
import json
import os
import types

import pytest

from benchmark import counts_keye, run, spans, trace
from benchmark.kinds import train_packed_ref

CELL = "train-keyevl2-long32k"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("train.sparse_index_share", "train.sparse_select_share", "train.sparse_index_roofline",
       "train.sparse_attn_roofline", "train.sparse_flash_roofline")


def toy(seed, seconds=1.0):
    os.environ["MAGGY_TPU_COMPILE_CACHE"] = "0"
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    with open(os.path.join(run.HERE, "checks", "tiny.keye-vl-2.0-30b-a3b.json")) as f:
        tiny = json.load(f)
    config = run.merge(config, tiny["config"])
    mix = run.merge(mix, tiny["traffic"][mix["kind"]])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse_cpu=True)
    return run.Cell(args, workload, config, mix), config, mix


def test_the_cell_names_this_kind_and_configuration():
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert mix["kind"] == train_packed_ref.KIND and workload["chips"] == 1
    assert config["name"] == "keye-vl-2.0-30b-a3b" and train_packed_ref.KIND in config
    assert mix["rows_per_chip"] == 1 and mix["seq_len"] == 32768 and mix["pool_batches"] == 8
    d = mix["documents"]
    assert d["min"] == d["max"] == d["median"] == mix["seq_len"] and d["sigma"] == 0  # one document a row, no padding
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | {"train.moe_experts_roofline", "train.moe_slots_dropped", "train.attn_kernel_share",
                       "train.scope_scan_share"} <= listed
    assert not {"train.mla_proj_share", "train.moe_shared_share", "train.mtp_share", "train.conv_op_share",
                "train.flash_roofline"} & listed
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_tok_s_chip")["workloads"]


def test_the_configuration_states_its_cut_and_keeps_every_width():
    _bench, _workload, config, _mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from benchmark import configs

    ref = configs.load_reference(config)
    s = ref.sizes(config, train_packed_ref.KIND)
    assert (s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"], s["moe_d_ff"]) == (2048, 32, 4, 128, 768)
    assert (s["n_experts"], s["top_k"], s["held"], s["index_heads"], s["index_head_dim"], s["topk"]) == (128, 8, 16, 16, 64, 2048)
    assert (s["n_layers"], s["vocab"], s["max_positions"]) == (4, 18992, 32768)
    assert set(config["reduced"]) == set(config["why_reduced"]) == {k for k, v in config.items() if isinstance(v, dict) and "published" in v}
    spec = ref.leaf_spec(s)
    total = sum(int(__import__("math").prod(shape)) * max(stacked, 1) for shape, stacked, _std, _mean in spec.values())
    assert total == config["parameters"]["total"] == 465391104
    # needed operations: the selection keeps 12.1% of the causal pairs of a 32,768-token document
    selected, causal = counts_keye.pairs([32768], s["topk"])
    assert (selected, causal) == (2048 * 2049 // 2 + (32768 - 2048) * 2048, 32768 * 32769 // 2)
    assert 0.120 < selected / causal < 0.122
    assert counts_keye.flash_flops(s, [32768]) == 3 * 4 * 32 * 128 * 4 * selected
    assert counts_keye.index_flops_forward(s, [32768]) == 2 * 16 * 64 * 4 * causal
    assert counts_keye.flash_visited_flops(s, [32768]) == 3 * 4 * 32 * 128 * 4 * causal


def test_sound_run_is_correct_and_reads_its_counters(capsys):
    cell, _config, mix = toy(2**31 + 13)
    result = train_packed_ref.run(cell)
    out = capsys.readouterr().out.splitlines()
    comparisons = [json.loads(l[len("comparison "):]) for l in out if l.startswith("comparison ")]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["end_to_end"]["train_tok_s_chip"] > 0
    names = {c["name"] for c in comparisons}
    assert {"loss_step1_abs_gap", "slots_step1_rel_gap", "grad_sample_worst_leaf_difference",
            "grad_sample_routed_worst_leaf_difference", "slots_dropped_in_window"} <= names
    obs = result["obs"]
    assert len(obs["counters"]) == obs["steps"] // mix["steps_per_chunk"]
    assert all(c["moe_slots"] > 0 and c["moe_slots_dropped"] == 0 for c in obs["counters"])
    assert obs["needed_flops"] > 0 and obs["kernels"] == ["xla_dense"]  # the CPU's dispatch
    assert "moe.index_q" in obs["program"]["grad_norm"] and obs["program"]["grad_norm"]["moe.index_q"] > 0


def test_every_control_is_judged_not_correct():
    _cell, config, mix = toy(5)
    verdicts = train_packed_ref.controls(config, mix, 5)
    assert set(verdicts) == {"float8_operands", "bfloat16_state"}
    assert not any(v.correct for v in verdicts.values())
    failed = {name: {r["name"].split(".")[-1] for r in v.rows if not r["ok"]} for name, v in verdicts.items()}
    assert "grad_sample_worst_leaf_difference" in failed["float8_operands"]
    assert "delta_norm_worst_leaf_gap" in failed["bfloat16_state"]


def fake_obs(ops, busy, device_ops):
    tl = spans.Timeline.__new__(spans.Timeline)
    tl.ops, tl.busy_ns, tl.lo, tl.hi = [ops], [busy], 0, busy
    tl.gaps, tl.threads, tl._scope_times = [], [], {}
    cell, _config, mix = toy(7)
    cell.trace_dir = f"synthetic-{id(tl)}"
    spans._LOADED[cell.trace_dir] = tl
    from benchmark import configs

    sizes = configs.load_reference(cell.config).sizes(cell.config, train_packed_ref.KIND)
    return {"cell": cell, "trace": {"device_ops": device_ops, "busy_s": busy / 1e9}, "needed_flops": 1.0,
            "sizes": sizes, "chips": 1, "traced_steps": [0], "device_kind": "TPU v5 lite"}, mix


def test_the_new_readers_on_a_synthetic_timeline():
    layer = "jit(train_step)/jvp(MoEDecoder)/while/body/closed_call/layers/layer/attn/"
    ops = [
        (0, 100, "%index_scores.1", layer + "sparse.index/index_scores/pallas_call:"),
        (100, 130, "%fusion.1", layer + "sparse.index/index_q/dot_general:"),
        (130, 180, "%sparse_select.1", layer + "sparse.select/sparse_select/pallas_call:"),
        (180, 400, "%fusion.2", layer + "sparse.index_loss/while/body/dot_general:"),
        (400, 700, "%flash_fwd.1", layer + "jit(flash_attention)/flash_fwd/pallas_call:"),
        (700, 1000, "%fusion.3", "jit(train_step)/optimizer/add:"),
    ]
    obs, mix = fake_obs(ops, 1000, [["%flash_fwd.1", 300e-9], ["%index_scores.1", 100e-9], ["%fusion.2", 220e-9]])
    read = lambda name: run.reader(name).read(obs)
    assert read("train.sparse_index_share") == pytest.approx(40.0)
    assert read("train.sparse_select_share") == pytest.approx(5.0)
    # two traced steps of this toy mix: needed operations over the kernel's time and the peak
    from benchmark.peaks import peaks_for

    docs = counts_keye.traced_documents(obs)
    assert len(docs) == mix["steps_per_chunk"] and all(sum(d) <= mix["seq_len"] * mix["rows_per_chip"] for d in docs)
    peak = peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    want = sum(counts_keye.index_flops_forward(obs["sizes"], d) for d in docs) / 100e-9 / peak * 100
    assert read("train.sparse_index_roofline") == pytest.approx(want)
    want = sum(counts_keye.flash_flops(obs["sizes"], d) for d in docs) / 300e-9 / peak * 100
    assert read("train.sparse_attn_roofline") == pytest.approx(want)
    # the same kernels' time under the pairs they visit: every causal pair inside a document
    want = sum(counts_keye.flash_visited_flops(obs["sizes"], d) for d in docs) / 300e-9 / peak * 100
    assert read("train.sparse_flash_roofline") == pytest.approx(want)
    assert read("train.sparse_flash_roofline") > read("train.sparse_attn_roofline")


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(tmp_path):
    """The recorded trace of PR 23's dense program, and a run with no trace:
    every reader returns None and none raises (the parent's side of a traced
    run of another cell)."""
    import shutil

    recorded = str(tmp_path)
    os.makedirs(os.path.join(recorded, "plugins", "profile", "recorded"))
    shutil.copy(os.path.join(HERE, "recorded", "train.xplane.pb"),
                os.path.join(recorded, "plugins", "profile", "recorded", "host.xplane.pb"))
    summary = trace.reduce(recorded)
    cell = types.SimpleNamespace(trace_dir=recorded, chips=1, mix={}, seed=1)
    obs = {"cell": cell, "trace": summary, "needed_flops": 1.0, "sizes": {"vocab": 32768}, "chips": 1,
           "traced_steps": [0], "device_kind": "TPU v5 lite"}
    assert [run.reader(n).read(obs) for n in NEW] == [None] * len(NEW)
    assert [run.reader(n).read({"sizes": {}}) for n in NEW] == [None] * len(NEW)
