"""Drives the ``train_packed_ref`` kind end to end at toy sizes on the CPU for
the cell ``train-lagunas21-packed8k``, as ``test_train_keye.py`` does for the
Keye cell: the ``Cell`` is built from ``checks/tiny.laguna-s-2.1.json`` with
``run.merge``; a sound run is judged correct with its counters read, both
controls and the three planted faults are judged not correct;
``counts_laguna.py`` is held against a brute-force count on small documents; the five readers this cell brings read a
synthetic timeline, and find nothing (and do not raise) in the recorded trace
of a program that has none of their modules and scopes."""

import argparse
import json
import math
import os
import types

import numpy as np
import pytest

from benchmark import counts_laguna, run, spans, trace
from benchmark.kinds import train_packed_ref

CELL = "train-lagunas21-packed8k"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("train.window_attn_share", "train.window_kernel_share", "train.window_flash_roofline",
       "train.full_flash_roofline", "train.attn_gate_share")


def toy(seed, seconds=1.0):
    os.environ["MAGGY_TPU_COMPILE_CACHE"] = "0"
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    with open(os.path.join(run.HERE, "checks", "tiny.laguna-s-2.1.json")) as f:
        tiny = json.load(f)
    config = run.merge(config, tiny["config"])
    mix = run.merge(mix, tiny["traffic"][mix["kind"]])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse_cpu=True)
    return run.Cell(args, workload, config, mix), config, mix


def test_the_cell_names_this_kind_and_configuration():
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert mix["kind"] == train_packed_ref.KIND and workload["chips"] == 1 and workload["traffic"] == "packed8k-r1"
    assert config["name"] == "laguna-s-2.1" and train_packed_ref.KIND in config
    assert mix["rows_per_chip"] == 1 and mix["seq_len"] == 8192 and mix["pool_batches"] == 32
    from benchmark import traffic

    same = {k: v for k, v in traffic.load_mix("packed8k").items() if k in ("seq_len", "documents", "documents_per_chip", "order_seed")}
    assert {k: mix[k] for k in same} == same  # the 32 packed rows of packed8k, one a step
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | {"train.moe_experts_roofline", "train.moe_slots_dropped", "train.moe_shared_share",
                       "train.attn_kernel_share", "train.scope_scan_share"} <= listed
    assert not {"train.mla_proj_share", "train.mtp_share", "train.conv_op_share", "train.flash_roofline",
                "train.sparse_index_share"} & listed
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"] if m["name"] in NEW)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_tok_s_chip")["workloads"]


def test_the_configuration_states_its_cut_and_keeps_every_width():
    _bench, _workload, config, _mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from benchmark import configs

    ref = configs.load_reference(config)
    s = ref.sizes(config, train_packed_ref.KIND)
    assert (s["d_model"], s["d_ff"], s["moe_d_ff"], s["shared_d_ff"], s["n_kv_heads"], s["head_dim"]) == (3072, 12288, 1024, 1024, 8, 128)
    assert (s["n_heads"], s["sliding_heads"], s["window"], s["heads_per_layer"]) == (48, 72, 512, [48, 72, 72, 72, 48])
    assert (s["n_experts"], s["top_k"], s["held"], s["routed_scaling"], s["n_dense"]) == (256, 10, 8, 2.5, 1)
    assert s["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert (s["vocab"], s["max_positions"]) == (12544, 8192)
    full, sliding = s["rope"]["full_attention"], s["rope"]["sliding_attention"]
    assert (full["theta"], full["width"], sliding["theta"], sliding["width"]) == (500000.0, 64, 10000.0, 128)
    assert full["yarn"] == {"factor": 128.0, "original": 8192, "beta_fast": 32.0, "beta_slow": 1.0,
                            "attention_factor": 1.4852030263919618} and "yarn" not in sliding
    assert set(config["reduced"]) == set(config["why_reduced"]) == {k for k, v in config.items() if isinstance(v, dict) and "published" in v}
    assert {"gate", "router", "shared_expert", "qk_norm", "activation", "window", "yarn", "optimizer"} <= set(config["assumed"])
    spec = ref.leaf_spec(s)
    total = sum(math.prod(shape) * max(stacked, 1) for shape, stacked, _std, _mean in spec.values())
    assert total == config["parameters"]["total"] == 811018496
    fields = ref.program_fields(config, train_packed_ref.KIND)
    assert (fields["sliding_window"], fields["sliding_heads"], fields["rope_share"], fields["attn_gate"]) == (512, 72, 0.5, True)
    assert fields["rope_yarn"] == (128.0, 8192, 32.0, 1.0, 1.4852030263919618) and fields["router"] == "softmax"


def brute_pairs(doc_lengths, window):
    """Count the pairs one by one from an explicit mask over the documents laid end to end."""
    seg = np.repeat(np.arange(len(doc_lengths)), doc_lengths)
    at = np.arange(len(seg))
    ahead = at[:, None] - at[None, :]
    causal = (ahead >= 0) & (seg[:, None] == seg[None, :])
    return int((causal & (ahead < window)).sum()), int(causal.sum())


@pytest.mark.parametrize("docs,window", [([5, 40, 1, 33], 32), ([200], 64), ([3, 3, 3], 8), ([64, 65, 63], 64), ([100, 7], 1)])
def test_counts_against_a_brute_force_count_on_small_documents(docs, window):
    assert counts_laguna.pairs(docs, window) == brute_pairs(docs, window)
    s = {"window": window, "head_dim": 16, "heads_per_layer": [4, 6, 6, 6, 4],
         "layer_types": ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"], "n_dense": 1}
    inside, causal = brute_pairs(docs, window)
    assert counts_laguna.window_flash_flops(s, docs) == 3 * 2 * 2 * 16 * 18 * inside
    assert counts_laguna.full_flash_flops(s, docs) == 3 * 2 * 2 * 16 * 8 * causal


def test_the_mix_keeps_a_third_of_the_causal_pairs_inside_the_window():
    from benchmark import traffic

    docs = [n for row in traffic.packed_rows(traffic.load_mix("packed8k-r1")) for n in row]
    inside, causal = counts_laguna.pairs(docs, 512)
    assert sum(docs) == 261291 and 0.3211 < inside / causal < 0.3212
    assert 394.7 < inside / sum(docs) < 394.8 and 1229.1 < causal / sum(docs) < 1229.2


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
    assert obs["program"]["grad_norm"]["p1.head_gate"] > 0 and obs["program"]["grad_norm"]["d0.head_gate"] > 0


def test_every_control_is_judged_not_correct():
    _cell, config, mix = toy(5)
    verdicts = train_packed_ref.controls(config, mix, 5)
    assert set(verdicts) == {"float8_operands", "bfloat16_state", "no_window", "no_gate", "unscaled_route"}
    assert not any(v.correct for v in verdicts.values())
    failed = {name: {r["name"].split(".")[-1] for r in v.rows if not r["ok"]} for name, v in verdicts.items()}
    assert "grad_sample_worst_leaf_difference" in failed["float8_operands"]
    assert "delta_norm_worst_leaf_gap" in failed["bfloat16_state"]
    # the planted faults, by the limits held against them: a norm moves with a part of the model left out
    assert {"grad_norm_worst_leaf_gap", "grad_sample_worst_leaf_difference"} <= failed["no_window"]
    assert {"grad_norm_worst_leaf_gap", "grad_sample_worst_leaf_difference"} <= failed["no_gate"]
    assert {"grad_norm_worst_leaf_gap", "grad_sample_routed_worst_leaf_difference"} <= failed["unscaled_route"]


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
    """Names as the step compiled for a v5e carries them (``compile_step.py --out``)."""
    fwd = "jit(train_step)/jvp(MoEDecoder)/while/body/closed_call/layers/"
    bwd = "jit(train_step)/transpose(jvp(MoEDecoder))/while/body/closed_call/layers/layers/checkpoint/"
    ops = [
        (0, 100, "%flash_fwd.30", fwd + "layer_0/layer/attn/jit(flash_attention)/flash_fwd/pallas_call:"),
        (100, 250, "%flash_bwd.11", bwd + "layer_1/layer/attn/jit(flash_attention)/flash_bwd/pallas_call:"),
        (250, 300, "%fusion.1", fwd + "layer_2/layer/attn/wq/dot_general:"),
        (300, 320, "%fusion.2", fwd + "layer_2/layer/attn/attn.gate/w_head_gate/dot_general:"),
        (320, 400, "%flash_fwd.33", fwd + "layer_3/layer/attn/jit(flash_attention)/flash_fwd/pallas_call:"),
        (400, 440, "%flash_fwd.29", "jit(train_step)/jvp(MoEDecoder)/dense_0/layer/attn/jit(flash_attention)/flash_fwd/pallas_call:"),
        (440, 450, "%fusion.3", "jit(train_step)/jvp(MoEDecoder)/dense_0/layer/attn/attn.gate/logistic:"),
        (450, 700, "%fusion.4", fwd + "layer_0/layer/moe/moe.shared/shared/w_up/dot_general:"),
        (700, 1000, "%fusion.5", "jit(train_step)/optimizer/add:"),
    ]
    obs, mix = fake_obs(ops, 1000, [])
    read = lambda name: run.reader(name).read(obs)
    assert read("train.window_attn_share") == pytest.approx(32.0)  # 100 + 150 + 50 + 20 under the sliding layers' attn
    assert read("train.window_kernel_share") == pytest.approx(25.0)
    assert read("train.attn_gate_share") == pytest.approx(3.0)
    from benchmark.peaks import peaks_for

    docs = counts_laguna.traced_documents(obs)
    assert len(docs) == mix["steps_per_chunk"] and all(sum(d) <= mix["seq_len"] * mix["rows_per_chip"] for d in docs)
    peak = peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    want = sum(counts_laguna.window_flash_flops(obs["sizes"], d) for d in docs) / 250e-9 / peak * 100
    assert read("train.window_flash_roofline") == pytest.approx(want)
    want = sum(counts_laguna.full_flash_flops(obs["sizes"], d) for d in docs) / 120e-9 / peak * 100
    assert read("train.full_flash_roofline") == pytest.approx(want)


def test_the_new_readers_find_nothing_in_a_program_without_the_modules(tmp_path):
    """The recorded trace of PR 23's dense program (under another
    configuration's sizes and under this one's), and a run with no trace:
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
    from benchmark import configs

    _cell, config, _mix = toy(3)
    obs["sizes"] = configs.load_reference(config).sizes(config, train_packed_ref.KIND)
    assert [run.reader(n).read(obs) for n in NEW] == [None] * len(NEW)  # a dense program's scan names ``layer``, no ``layer_<j>``
    assert [run.reader(n).read({"sizes": {}}) for n in NEW] == [None] * len(NEW)
