"""Drives the ``train_packed_ref`` kind end to end at toy sizes on the CPU for
the cell ``train-smallthinker21b-long16k``, as ``test_train_laguna.py`` does
for the Laguna cell: the ``Cell`` is built from
``checks/tiny.smallthinker-21ba3b-instruct.json`` with ``run.merge``; a sound
run is judged correct with its counters read, both controls and the four
planted faults are judged not correct; ``counts_smallthinker.py`` is held
against a count by hand; the reader this cell brings reads a synthetic
timeline beside the window's four, and finds nothing (and does not raise) in
the recorded trace of a program that has none of its scopes."""

import argparse
import json
import math
import os
import types

import pytest

from benchmark import counts_laguna, counts_smallthinker, run, spans, trace
from benchmark.kinds import train_packed_ref

CELL = "train-smallthinker21b-long16k"
NAME = "smallthinker-21ba3b-instruct"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("train.moe_preroute_share",)
WINDOW = ("train.window_attn_share", "train.window_kernel_share", "train.window_flash_roofline",
          "train.full_flash_roofline")
SHARE = ("train.moe_route_share", "train.moe_experts_share", "train.moe_experts_roofline",
         "train.moe_load_max_over_mean", "train.moe_slots_dropped")


def toy(seed, seconds=1.0):
    os.environ["MAGGY_TPU_COMPILE_CACHE"] = "0"
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    with open(os.path.join(run.HERE, "checks", f"tiny.{NAME}.json")) as f:
        tiny = json.load(f)
    config = run.merge(config, tiny["config"])
    mix = run.merge(mix, tiny["traffic"][mix["kind"]])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse_cpu=True)
    return run.Cell(args, workload, config, mix), config, mix


def test_the_cell_names_this_kind_and_configuration():
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert mix["kind"] == train_packed_ref.KIND and workload["chips"] == 1 and workload["traffic"] == "long16k"
    assert config["name"] == NAME and train_packed_ref.KIND in config
    assert mix["rows_per_chip"] == 1 and mix["seq_len"] == 16384 and mix["pool_batches"] == 8
    assert mix["documents"] == {"distribution": "lognormal", "median": 16384, "sigma": 0.0, "min": 16384, "max": 16384}
    from benchmark import traffic

    assert traffic.packed_rows(mix) == [[16384]] * 8  # one document a row, no padding, no packing
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | set(WINDOW) | set(SHARE) | {"train.attn_qkv_proj_share", "train.scope_scan_share", "train.mfu"} <= listed
    assert not {"train.moe_shared_share", "train.attn_gate_share", "train.mla_proj_share", "train.mtp_share",
                "train.conv_op_share", "train.flash_roofline", "train.sparse_index_share"} & listed
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"] if m["name"] in NEW)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_tok_s_chip")["workloads"]
    assert len(bench["workloads"]) == 8 and all(w["chips"] == 1 for w in bench["workloads"])


def test_the_configuration_states_its_cut_and_keeps_every_width():
    _bench, _workload, config, _mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from benchmark import configs

    ref = configs.load_reference(config)
    s = ref.sizes(config, train_packed_ref.KIND)
    assert (s["d_model"], s["moe_d_ff"], s["shared_d_ff"], s["n_heads"], s["n_kv_heads"], s["head_dim"]) == (2560, 768, 0, 28, 4, 128)
    assert (s["n_experts"], s["top_k"], s["held"], s["offset"], s["n_dense"]) == (64, 6, 16, 0, 0)
    assert (s["window"], s["rope_theta"], s["norm_eps"], s["max_positions"], s["vocab"]) == (4096, 1.5e6, 1e-6, 16384, 37984)
    assert s["window_layout"] == s["rope_layout"] == [0, 1, 1, 1] and s["heads_per_layer"] == [28] * 4
    assert s["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3
    assert config["max_position_embeddings"] == 16384 and "max_position_embeddings" not in config["reduced"]
    assert set(config["reduced"]) == set(config["why_reduced"]) == {k for k, v in config.items() if isinstance(v, dict) and "published" in v}
    for key in ("assumed", "departures", "deployment", "parameters", "precision", "planned_peak_gib"):
        assert config[key], key
    assert set(config[train_packed_ref.KIND]["limits"]) <= set(config[train_packed_ref.KIND]["limits_why"]) | {"mtp_loss_abs"}
    spec = ref.leaf_spec(s)
    total = sum(math.prod(shape) * max(stacked, 1) for shape, stacked, _std, _mean in spec.values())
    assert total == config["parameters"]["total"] == 656529920
    fields = ref.program_fields(config, train_packed_ref.KIND)
    assert (fields["route_from"], fields["expert_act"], fields["full_rope"], fields["router"]) == ("layer_input", "relu", False, "softmax")
    assert (fields["sliding_window"], fields["sliding_rope_theta"], fields["n_shared_experts"], fields["n_dense_layers"]) == (4096, 1.5e6, 0, 0)
    assert config[train_packed_ref.KIND]["optimizer"]["lr"] == 7.3e-6
    with open(os.path.join(run.ROOT, config["control"])) as f:
        assert set(json.load(f)["variants"]) == {"float8_operands", "bfloat16_state", "route_after_attention", "silu_experts",
                                                 "rope_on_global", "no_window"}


def test_the_needed_operations_of_the_cells_step_by_hand():
    """One document of 16,384 through one period of four layers: the issue's arithmetic."""
    _bench, _workload, config, _mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from benchmark import configs

    s = configs.load_reference(config).sizes(config, train_packed_ref.KIND)
    n, w = 16384, 4096
    inside, causal = w * (w + 1) // 2 + (n - w) * w, n * (n + 1) // 2
    assert counts_laguna.pairs([n], w) == (inside, causal) and 0.4374 < inside / causal < 0.4376
    assert counts_smallthinker.attention_params(s) == 20971520 and counts_smallthinker.expert_params(s) == 5898240
    assert counts_smallthinker.matmul_params_per_token(s) == 4 * (20971520 + 163840) + 2560 * 37984
    slots = n * 6 * 4 // 4  # a quarter of the six choices a token, four layers
    total = counts_smallthinker.train_flops(s, [n], slots)
    assert total == 3 * (2 * (counts_smallthinker.matmul_params_per_token(s) * n + 5898240 * slots)
                         + 4 * 128 * 28 * (3 * inside + causal))
    assert 34.6e12 < total < 34.8e12
    assert counts_laguna.window_flash_flops(s, [n]) == 3 * 4 * 128 * 28 * 3 * inside
    assert counts_laguna.full_flash_flops(s, [n]) == 3 * 4 * 128 * 28 * causal


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
    assert obs["program"]["grad_norm"]["p0.router"] > 0 and obs["program"]["grad_norm"]["p3.experts_gate"] > 0


def test_every_control_is_judged_not_correct():
    _cell, config, mix = toy(5)
    verdicts = train_packed_ref.controls(config, mix, 5)
    assert set(verdicts) == {"float8_operands", "bfloat16_state", "route_after_attention", "silu_experts",
                             "rope_on_global", "no_window"}
    assert not any(v.correct for v in verdicts.values())
    failed = {name: {r["name"].split(".")[-1] for r in v.rows if not r["ok"]} for name, v in verdicts.items()}
    assert "grad_sample_worst_leaf_difference" in failed["float8_operands"]
    assert "delta_norm_worst_leaf_gap" in failed["bfloat16_state"]
    # the planted faults, by the limits held against them: another selection moves the routed
    # gradients (as many slots fall on the held experts either way, on other tokens), another
    # activation or mask the norms and the samples
    assert "grad_sample_routed_worst_leaf_difference" in failed["route_after_attention"]
    assert {"grad_norm_worst_leaf_gap", "grad_sample_routed_worst_leaf_difference"} <= failed["silu_experts"]
    assert "grad_sample_worst_leaf_difference" in failed["rope_on_global"]
    assert "grad_sample_worst_leaf_difference" in failed["no_window"]


def fake_obs(ops, busy):
    tl = spans.Timeline.__new__(spans.Timeline)
    tl.ops, tl.busy_ns, tl.lo, tl.hi = [ops], [busy], 0, busy
    tl.gaps, tl.threads, tl._scope_times = [], [], {}
    cell, _config, mix = toy(7)
    cell.trace_dir = f"synthetic-{id(tl)}"
    spans._LOADED[cell.trace_dir] = tl
    from benchmark import configs

    sizes = configs.load_reference(cell.config).sizes(cell.config, train_packed_ref.KIND)
    return {"cell": cell, "trace": {"device_ops": [], "busy_s": busy / 1e9}, "needed_flops": 1.0,
            "sizes": sizes, "chips": 1, "traced_steps": [0], "device_kind": "TPU v5 lite"}, mix


def test_the_new_reader_and_the_windows_four_on_a_synthetic_timeline():
    """Names as the step compiled for a v5e carries them (``compile_step.py --out``)."""
    fwd = "jit(train_step)/jvp(MoEDecoder)/while/body/closed_call/layers/"
    bwd = "jit(train_step)/transpose(jvp(MoEDecoder))/while/body/closed_call/layers/layers/checkpoint/"
    ops = [
        (0, 100, "%flash_fwd.30", fwd + "layer_0/layer/attn/jit(flash_attention)/flash_fwd/pallas_call:"),
        (100, 250, "%flash_bwd.11", bwd + "layer_1/layer/attn/jit(flash_attention)/flash_bwd/pallas_call:"),
        (250, 300, "%fusion.1", fwd + "layer_2/layer/attn/wq/dot_general:"),
        (300, 340, "%fusion.2", fwd + "layer_0/layer/moe.preroute/moe.route/moe.route/router/dot_general:"),
        (340, 360, "%fusion.3", bwd + "rematted_computation/layer_3/layer/moe.preroute/moe.route/moe.dispatch/eq:"),
        (360, 400, "%fusion.4", bwd + "layer_3/layer/moe.preroute/moe.route/moe.route/jit(take_along_axis)/scatter-add:"),
        (400, 480, "%flash_fwd.33", fwd + "layer_3/layer/attn/jit(flash_attention)/flash_fwd/pallas_call:"),
        (480, 700, "%gmm.4", fwd + "layer_0/layer/moe/while/body/jit(_chunk_experts)/moe.experts/jit(gmm)/pallas_call:"),
        (700, 1000, "%fusion.5", "jit(train_step)/optimizer/add:"),
    ]
    obs, mix = fake_obs(ops, 1000)
    read = lambda name: run.reader(name).read(obs)
    assert read("train.moe_preroute_share") == pytest.approx(10.0)  # 40 + 20 + 40 under moe.preroute
    assert read("train.moe_route_share") == pytest.approx(10.0)  # the same operations: they keep their own scopes
    assert read("train.window_attn_share") == pytest.approx(28.0)  # 150 + 50 + 80 under the windowed layers' attn
    assert read("train.window_kernel_share") == pytest.approx(23.0)
    from benchmark.peaks import peaks_for

    docs = counts_laguna.traced_documents(obs)
    peak = peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    want = sum(counts_laguna.window_flash_flops(obs["sizes"], d) for d in docs) / 230e-9 / peak * 100
    assert read("train.window_flash_roofline") == pytest.approx(want)
    want = sum(counts_laguna.full_flash_flops(obs["sizes"], d) for d in docs) / 100e-9 / peak * 100
    assert read("train.full_flash_roofline") == pytest.approx(want)


def test_the_new_reader_finds_nothing_in_a_program_without_the_scope(tmp_path):
    """The recorded trace of PR 23's dense program, and a run with no trace:
    the reader returns None and does not raise (the parent's side of a traced
    run of another cell; the parent cannot run this one)."""
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
