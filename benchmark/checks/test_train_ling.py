"""Drives the ``train_packed_ref`` kind end to end at toy sizes on the CPU for
the cell ``train-ling3flash-packed8k``, as ``test_train_smallthinker.py`` does
for its cell: the ``Cell`` is built from ``checks/tiny.ling-3.0-flash.json``
with ``run.merge``; a sound run is judged correct with its counters read, both
controls and the five planted faults are judged not correct;
``counts_ling.py`` is held against the issue's arithmetic at the cell's size;
the four readers this cell brings read a synthetic timeline and step records,
and find nothing (and do not raise) in the recorded trace of a program that has
none of their scopes."""

import argparse
import json
import os
import types

import pytest

from benchmark import counts_ling, run, spans, step_records, trace
from benchmark.kinds import train_packed_ref

CELL = "train-ling3flash-packed8k"
NAME = "ling-3.0-flash"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("train.kda_op_share", "train.kda_scan_share", "train.kda_scan_roofline", "train.kda_chunks_cut_share",
       "train.kda_conv_share")
JOINED = ("train.mla_proj_share", "train.moe_route_share", "train.moe_experts_share", "train.moe_shared_share",
          "train.moe_experts_roofline", "train.moe_load_max_over_mean", "train.moe_slots_dropped", "train.moe_slots_mean",
          "train.moe_slots_growth")


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


def full_sizes():
    _bench, _workload, config, _mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from benchmark import configs

    return config, configs.load_reference(config).sizes(config, train_packed_ref.KIND)


def test_the_cell_names_this_kind_and_configuration():
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert mix["kind"] == train_packed_ref.KIND and workload["chips"] == 1 and workload["traffic"] == "packed8k-r1"
    assert config["name"] == NAME and train_packed_ref.KIND in config
    assert (mix["rows_per_chip"], mix["seq_len"], mix["pool_batches"], mix["order_seed"]) == (1, 8192, 32, 7)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | set(JOINED) | {"train.mfu", "train.attn_kernel_share", "train.attn_gate_share"} <= listed
    assert not {"train.mtp_share", "train.conv_op_share", "train.flash_roofline",
                "train.scope_scan_share", "train.window_attn_share"} & listed  # the layers run unrolled
    # a share of the step that a better form of the scope cuts reads better lower, as its siblings do
    assert all(m["better"] == "lower" for m in bench["per_layer"] if m["name"].endswith("_share") and "kda_" in m["name"]
               and m["name"] != "train.kda_chunks_cut_share")
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s_chip" for m in bench["per_layer"] if m["name"] in NEW)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_tok_s_chip")["workloads"]
    assert bench["workloads"][-1]["name"] == CELL and len(bench["workloads"]) >= 9
    assert all(w["chips"] == 1 for w in bench["workloads"]) and len(bench["workloads"][-1]["why"]) <= 200
    entry = bench["configs"][-1]
    assert entry["name"] == NAME and entry["reduced"] == config["reduced"] and len(entry["reduced"]) <= 16


def test_the_configuration_states_its_cut_and_keeps_every_width():
    config, s = full_sizes()
    assert (s["d_model"], s["d_ff"], s["moe_d_ff"], s["n_heads"], s["kda_dim"], s["conv_kernel"]) == (2560, 6144, 768, 32, 128, 4)
    assert (s["kv_rank"], s["d_nope"], s["d_rope"], s["d_v"], s["decay_floor"], s["kda_chunk"]) == (512, 128, 64, 128, -5.0, 64)
    assert (s["n_experts"], s["n_group"], s["topk_group"], s["top_k"], s["held"], s["offset"], s["n_shared"]) == (512, 8, 4, 8, 8, 0, 1)
    assert (s["routed_scaling"], s["rope_theta"], s["norm_eps"], s["max_positions"], s["vocab"], s["n_dense"]) == (2.5, 6e6, 1e-6, 8192, 19648, 1)
    assert s["layer_types"] == ["kda", "kda", "kda", "kda", "mla", "kda", "kda"]  # published layers 1 to 7
    assert set(config["reduced"]) == set(config["why_reduced"]) == {k for k, v in config.items() if isinstance(v, dict) and "published" in v}
    assert config["num_hidden_layers"] == {"published": 42, "train_packed_ref": 7} and config["q_lora_rank"] is None
    for key in ("assumed", "departures", "deployment", "parameters", "precision", "planned_peak_gib"):
        assert config[key], key
    assert len([k for k in config["assumed"] if k[1] == "_"]) == 10  # (a) to (j)
    assert set(config[train_packed_ref.KIND]["limits"]) <= set(config[train_packed_ref.KIND]["limits_why"]) | {"mtp_loss_abs"}
    assert config["parameters"]["total"] == 822033344
    from benchmark import configs

    fields = configs.load_reference(config).program_fields(config, train_packed_ref.KIND)
    assert fields["layer_types"] == ("kda",) * 4 + ("latent_attention",) + ("kda",) * 2
    assert (fields["q_lora_rank"], fields["attn_gate"], fields["n_group"], fields["topk_group"], fields["kda_head_dim"]) == (0, True, 8, 4, 128)
    with open(os.path.join(run.ROOT, config["control"])) as f:
        assert set(json.load(f)["variants"]) == {"float8_operands", "bfloat16_state", "scalar_decay", "no_delta_correction",
                                                 "state_crosses_documents", "conv_crosses_documents", "no_group_limit"}


def test_the_needed_operations_of_the_cells_step_by_hand():
    """One row of 8,192 through the seven layers: the issue's arithmetic."""
    _config, s = full_sizes()
    d, n = 2560, 8192
    assert counts_ling.kda_params(s) == 5 * d * 4096 + 2 * d * 32 == 52592640
    assert counts_ling.mla_params(s) == d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d + d * 32 == 31965184
    assert counts_ling.expert_params(s) == 5898240
    per_token = 6 * 52592640 + 31965184 + 3 * d * 6144 + 6 * (d * 512 + 5898240) + d * 19648
    assert counts_ling.matmul_params_per_token(s) == per_token
    docs, slots = [600] * 13 + [392], 6 * 1024
    pairs = sum(k * (k + 1) // 2 for k in docs)
    scan = counts_ling.kda_scan_flops_forward(s, n)
    assert scan == int(2 * (64 * 128 + 64 * 64 / 6 + 32.5 * 256 + 3 * 128 * 128 + 32.5 * 128) * 32 * 6 * n)
    total = counts_ling.train_flops(s, docs, slots)
    assert total == 3 * (2 * (per_token * n + 5898240 * slots) + 2 * 32 * 320 * pairs + scan)
    assert 23e12 < total < 27e12 and 0.02 < 3 * scan / total < 0.04  # the scans about 3% of what a step needs
    assert 0.55 < 3 * 2 * 6 * 52592640 * n / total < 0.65  # the six KDA operators' products about 60%
    # by bytes a KDA layer's scan is about 1.4 ms a step at 819 GB/s, and the bytes bound it
    a_layer = counts_ling.kda_scan_bytes(s, n) / 6
    assert 1.3e-3 < a_layer / 819e9 < 1.5e-3 and a_layer / 819e9 > counts_ling.kda_scan_flops(s, n) / 6 / 197e12


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
    norms = obs["program"]["grad_norm"]
    assert norms["l0.A_log"] > 0 and norms["l3.dt_bias"] > 0 and norms["l4.wkv_b"] > 0 and norms["l6.q_conv"] > 0


def test_every_control_is_judged_not_correct():
    _cell, config, mix = toy(5)
    verdicts = train_packed_ref.controls(config, mix, 5)
    assert set(verdicts) == {"float8_operands", "bfloat16_state", "scalar_decay", "no_delta_correction",
                             "state_crosses_documents", "conv_crosses_documents", "no_group_limit"}
    assert not any(v.correct for v in verdicts.values())
    failed = {name: {r["name"].split(".")[-1] for r in v.rows if not r["ok"]} for name, v in verdicts.items()}
    assert "grad_sample_worst_leaf_difference" in failed["float8_operands"]
    assert "delta_norm_worst_leaf_gap" in failed["bfloat16_state"]
    # the planted faults, by the limits held against them: another recurrence, other taps or a state
    # from another document move the gradients of the layers' own leaves; another selection moves
    # the count of slots on the held experts and the routed gradients
    for fault in ("scalar_decay", "no_delta_correction", "state_crosses_documents", "conv_crosses_documents"):
        assert "grad_sample_worst_leaf_difference" in failed[fault], fault
    assert {"slots_step1_rel_gap", "grad_sample_routed_worst_leaf_difference"} <= failed["no_group_limit"]


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


def test_the_new_readers_on_a_synthetic_timeline():
    """Names as the step compiled for a v5e carries them (``compile_step.py --out``)."""
    fwd = "jit(train_step)/jvp(MoEDecoder)/layers_2/layer/"
    bwd = "jit(train_step)/transpose(jvp(MoEDecoder))/layers_2/checkpoint/"
    ops = [
        (0, 100, "%fusion.1", fwd + "kda/kda.in_proj/wq/dot_general:"),
        (100, 150, "%kda_fwd.3", fwd + "kda/kda.scan/kda_fwd/pallas_call:"),
        (150, 200, "%fusion.2", fwd + "kda/kda.scan/exp:"),
        (200, 300, "%kda_bwd.3", bwd + "layer/kda/kda.scan/kda_bwd/pallas_call:"),
        (300, 340, "%fusion.3", bwd + "rematted_computation/layer/kda/kda.conv/mul:"),
        (340, 400, "%fusion.4", bwd + "layer/kda/kda.out/wo/dot_general:"),
        (400, 480, "%flash_fwd.1", "jit(train_step)/jvp(MoEDecoder)/layers_3/layer/attn/jit(flash_attention)/flash_fwd/pallas_call:"),
        (480, 490, "%fusion.5", "jit(train_step)/jvp(MoEDecoder)/layers_3/layer/attn/mla.kv/wkv_b/dot_general:"),
        (490, 500, "%fusion.7", "jit(train_step)/jvp(MoEDecoder)/layers_3/layer/attn/attn.gate/w_head_gate/dot_general:"),
        (500, 1000, "%fusion.6", "jit(train_step)/optimizer/add:"),
    ]
    obs, mix = fake_obs(ops, 1000)
    read = lambda name: run.reader(name).read(obs)
    assert read("train.kda_op_share") == pytest.approx(40.0)  # everything under the module ``kda``
    assert read("train.kda_scan_share") == pytest.approx(20.0)  # 50 + 50 + 100 under kda.scan, kernels or not
    assert read("train.kda_conv_share") == pytest.approx(4.0)  # the taps' replay in the backward
    assert read("train.mla_proj_share") == pytest.approx(2.0)  # the latent layer outside the flash kernels, its gate too
    assert read("train.attn_gate_share") == pytest.approx(1.0)  # the latent layer's gate a head
    from benchmark.peaks import peaks_for

    peaks = peaks_for("TPU v5 lite")
    positions = int(mix["steps_per_chunk"]) * int(mix["rows_per_chip"]) * int(mix["seq_len"])
    needed = max(counts_ling.kda_scan_flops(obs["sizes"], positions) / peaks["bf16_flops_per_s"],
                 counts_ling.kda_scan_bytes(obs["sizes"], positions) / peaks["hbm_bytes_per_s"])
    assert read("train.kda_scan_roofline") == pytest.approx(needed / 200e-9 * 100)


def test_the_cut_share_is_the_mean_of_the_steps_attribute():
    steps = [{"name": step_records.STEP, "ts": float(i), "dur_ms": 10.0, "attrs": {"kda_chunks_cut_share": share}}
             for i, share in enumerate([0.05, 0.07, 0.06])]
    assert step_records.attr_mean(steps, "kda_chunks_cut_share") == pytest.approx(0.06)
    assert run.reader("train.kda_chunks_cut_share").read({"sizes": {}}) is None  # no records: nothing, and no raise


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(tmp_path):
    """The recorded trace of PR 23's dense program, and a run with no trace:
    the readers return None and do not raise (the parent's side of a traced
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
    assert [run.reader(n).read(obs) for n in NEW[:3]] == [None] * 3
    assert [run.reader(n).read({"sizes": {}}) for n in NEW] == [None] * len(NEW)
